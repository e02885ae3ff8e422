"""QAFeL: Quantized Asynchronous Federated Learning (Algorithms 1-3).

Generic over the learning task: the algorithm is parameterized by a
``loss_fn(params, batch, key) -> scalar`` and operates on parameter pytrees,
so the same implementation trains the paper's 4-layer CNN and every
assigned decoder architecture.

Two surfaces:

* **Jittable round math** (``client_update``, ``server_apply``/
  ``server_apply_flat``): pure functions used by the host-level async
  simulator, by the fused device flush, and by the distributed pjit'd round
  step in ``repro.distributed``.
* **Host orchestration** (``QAFeL`` class): server state, buffer, hidden
  state, staleness bookkeeping, wire encoding. The async event timeline
  itself lives in ``repro.sim`` and drives this class.

The server state is **device-resident and flat**: ``x``, ``x-hat`` and the
momentum live as flat f32 vectors keyed by one ``TreeLayout``, and the
entire buffer flush — fused dequantize-accumulate of the K packed uploads,
momentum + server update, broadcast quantize-pack, and the hidden-state
apply of the decoded broadcast bits — executes as ONE jitted,
buffer-donated dispatch (``repro.kernels.ops.server_flush_step``). Tree
views materialize lazily (and are cached per server step) only at the
eval / client-update boundaries. See DESIGN.md ("Device-resident flat
server state").

FedBuff is recovered *exactly* with identity quantizers (the paper's
infinite-precision limit) — ``repro.core.fedbuff.make_fedbuff`` is that
special case, and a test asserts bit-identical trajectories.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.tree import tree_sub
from repro.core.buffer import FlushBatch, UpdateBuffer
from repro.core.hidden_state import HiddenState
from repro.core.protocol import (CLIENT_UPDATE, HIDDEN_BROADCAST, Message,
                                 TrafficMeter, decode_message,
                                 encode_message_flat, frame_chunk_messages,
                                 frame_cohort_messages, frame_packed_message,
                                 packed_qsgd_chunk_payload)
from repro.core.quantizers import (Quantizer, TreeLayout, flatten_tree,
                                   make_quantizer, packed_identity_payload,
                                   packed_qsgd_payload)
from repro.core.staleness import StalenessMonitor
from repro.obs import spans


@dataclasses.dataclass(frozen=True)
class QAFeLConfig:
    client_lr: float = 0.01
    server_lr: float = 1.0
    server_momentum: float = 0.0  # FedBuff's beta (0.3 in the paper's runs)
    buffer_size: int = 10  # K
    local_steps: int = 1  # P
    client_quantizer: Any = "qsgd4"  # spec/string; "identity" -> FedBuff upload
    server_quantizer: Any = "qsgd4"
    staleness_scaling: bool = True  # 1/sqrt(1+tau) down-weighting (Fig. 3 runs)
    # 0 = unbounded (Assumption 3.4 monitored only). > 0 is a real drop
    # policy: ``receive`` rejects uploads with tau > max_staleness before
    # they reach the buffer, and the rejects show up in the TrafficMeter /
    # StalenessMonitor summaries.
    max_staleness: int = 0

    def cq(self) -> Quantizer:
        return make_quantizer(self.client_quantizer)

    def sq(self) -> Quantizer:
        return make_quantizer(self.server_quantizer)


# ---------------------------------------------------------------------------
# Jittable round math
# ---------------------------------------------------------------------------


def local_sgd_scan(loss_fn: Callable, lr: float, y0, batches, keys, *,
                   with_loss: bool = False):
    """The ONE local-SGD loop (Algorithm 2 lines 2-4): a ``lax.scan`` of P
    plain SGD steps from ``y0``, one ``(batch, key)`` slice per step.

    Shared by every client-side surface — ``client_update`` (host simulator,
    fused cohort step) and the distributed round's in-graph client bodies
    (``repro.distributed.steps``) — so all engines run the identical
    compiled step math. ``with_loss=True`` additionally stacks the per-step
    losses (``value_and_grad``; the distributed round reports them),
    ``with_loss=False`` keeps the pure-gradient path bit-for-bit as before.

    Returns ``(y_final, losses-or-None)``.
    """
    def sgd_step(y, inp):
        batch, k = inp
        if with_loss:
            l, g = jax.value_and_grad(loss_fn)(y, batch, k)
        else:
            l, g = None, jax.grad(loss_fn)(y, batch, k)
        y = jax.tree.map(lambda yi, gi: (yi - lr * gi).astype(yi.dtype), y, g)
        return y, l

    return jax.lax.scan(sgd_step, y0, (batches, keys))


def client_update(loss_fn: Callable, qcfg: QAFeLConfig, x_hat, batches, key,
                  *, with_loss: bool = False):
    """Algorithm 2: y_0 <- x-hat; P local SGD steps; delta = y_P - y_0.

    batches: a pytree whose leaves have leading dim P (one slice per local
    step). Returns the *unquantized* delta (quantization is applied by the
    caller — in-graph wire encode for the fused cohort step and the
    distributed round, host wire encoding for the simulator).
    ``with_loss=True`` additionally returns the (P,) per-step losses
    (``(delta, losses)``) — the distributed round's metric; the default
    keeps the pure-gradient path bit-for-bit as before.

    Sign convention: the paper's Section 2 text sends Q_c(y_{P-1} - y_0) and
    the server ascends x + eta_g * Delta-bar; Algorithm 2 line 5 writes
    Q_c(y_0 - y_p). We follow the text (delta = y_P - y_0, i.e. a descent
    direction) — see DESIGN.md for the discrepancy note.
    """
    keys = jax.random.split(key, qcfg.local_steps)
    y_final, losses = local_sgd_scan(loss_fn, qcfg.client_lr, x_hat,
                                     batches, keys, with_loss=with_loss)
    delta = tree_sub(y_final, x_hat)
    return (delta, losses) if with_loss else delta


def client_update_flat(loss_fn: Callable, qcfg: QAFeLConfig, spec, layout,
                       hidden_flat, batches, k_train, k_enc, flag, *, b: int,
                       with_loss: bool = False, batched: Optional[bool] = None,
                       taps: bool = False, tap_gather=None,
                       chunk_rows: Optional[int] = None, row_block=None,
                       residual=None, basis_seed=None):
    """Flat-in / packed-out client pipeline: the traceable body of the fused
    cohort train+encode dispatch (``kernels.ops.cohort_train_encode_step``).

    Takes the server's device-resident flat x-hat, unflattens it to the
    model pytree *inside* the computation, runs the (vmapped, for b > 1)
    local-SGD scan, flattens the delta stack to ``(b, d)``, and runs the
    batched quantize-pack in the same graph — no stacked delta pytree, no
    ``hidden_tree`` materialization, and no separate encode dispatch ever
    exist on the client path.

    Bit-exactness contract (same as the fused server flush): the flat
    delta stack — the pre-fusion ``client_update`` jit's output boundary,
    whose consumer is the encode's mul/add-heavy norm math — is pinned with
    ``kernels.ops.hard_boundary`` so XLA cannot FMA-contract the local-SGD
    subtraction into the bucket-norm reduction. The in-jit unflatten needs
    NO boundary: slices are exact data movement, so the scan body sees
    bit-identical operands whether x-hat leaves arrive as materialized jit
    arguments (the old path) or as in-graph views of ``hidden_flat``.
    Encode dither: b == 1 uses the single-message threefry path, b > 1 the
    batched counter-hash path, matching the host-side wire entries message
    for message.

    Returns ``{"packed", "norms"}`` for a qsgd ``spec``, else ``{"flat"}``
    (identity's flat payload IS its wire format — the FedBuff fast path;
    top_k/rand_k have data-dependent wire shapes and are sliced/encoded by
    the host from the same flat output). ``with_loss=True`` returns
    ``(out, losses)`` — the distributed round's metric thread. ``batched``
    overrides the b==1 dispatch/dither convention (see inline note): the
    sharded cohort step's per-device slice may hold one member and must
    still emit the batched counter-hash wire bits. ``taps=True`` adds a
    ``"taps"`` output — per-member delta norm + relative quantization error
    of the ACTUAL wire bits (the packed codes are decoded back in-graph),
    see ``repro.obs.taps.COHORT_TAP_NAMES`` — at the cost of one more
    hard-boundary cond in the same dispatch. ``tap_gather`` (from the jit
    factory) pins the tap inputs to a replicated layout first, so a
    sharded caller's tap reductions keep the meshless f32 grouping.

    ``chunk_rows`` tiles the qsgd encode over fixed-size wire-row chunks
    inside the same dispatch (``quantizers.qsgd_encode_flat2d``) — the
    chunked-streaming mode of the LLM-scale substrate; bit-invisible
    because the dither keys on global element indices. ``row_block``
    (``(axis_name, n_model)``, batched callers inside a 2-D shard_map only)
    makes this device emit ONLY its model-axis row segment of the packed
    codes: the flat delta is padded to ``n_model`` whole-bucket-row
    segments, this device's segment is sliced out, and the counter-hash
    dither is keyed by the segment's global row offset — so the
    concatenation over model ranks is the single-device wire bits exactly,
    and full packed codes never materialize per device. Taps under
    ``row_block`` all_gather the packed segments back (the ONLY model-axis
    collective on the cohort path, and it moves wire-sized uint8 codes,
    not f32).

    A lowrank ``spec`` is the projection-subspace upload: the (b, d)
    error-feedback ``residual`` stack is added to the delta stack, the sum
    is sketch-projected to (b, d_r) under the round's (2,) uint32
    ``basis_seed`` (``quantizers.lowrank_project_flat2d``), the SUBSPACE
    vector is quantize-packed through the ordinary qsgd wire entries
    (``chunk_rows`` tiles it the same way — chunk-invariant because the
    dither keys global subspace indices), and the packed bits are decoded
    back in-graph so the NEW residual — what the quantized subspace message
    failed to carry, ``c - S^T qdq(S c)`` — comes out of the SAME dispatch
    as a ``"residual"`` output. The residual-corrected stack and its
    projection are pinned behind one shared hard boundary before the
    encode's norm math (the lowrank entry in
    ``kernels.ops._cohort_boundaries``). Lowrank taps are the 3-column
    variant (``obs.taps.COHORT_TAP_NAMES_LOWRANK``): message norm,
    full-space relative error (the residual ratio) and subspace-only
    quantization error.
    """
    from repro.core.quantizers import (flatten_stacked_leaves,
                                       qsgd_encode_flat2d, qsgd_encode_rows)
    from repro.kernels import ops as kops  # local import: kernels are optional
    from repro.kernels import qsgd as _kq

    # ``batched`` decouples the dispatch shape from the dither/stacking
    # convention: a sharded tier-group's per-device slice can hold ONE
    # member and must still run the batched convention (stacked inputs,
    # counter-hash dither) so the wire bits match the single-device
    # whole-cohort dispatch member for member. Default: b > 1.
    batched = (b > 1) if batched is None else batched
    boundary = functools.partial(kops.hard_boundary, flag)
    x_hat = layout.unflatten(hidden_flat)
    fn = functools.partial(client_update, loss_fn, qcfg, with_loss=with_loss)
    if not batched:
        res = fn(x_hat, batches, k_train)
    else:
        res = jax.vmap(fn, in_axes=(None, 0, 0))(x_hat, batches, k_train)
    deltas, losses = res if with_loss else (res, None)
    flat2d = boundary(flatten_stacked_leaves(jax.tree.leaves(deltas), b))
    if spec.kind == "qsgd":
        if row_block is None:
            packed, norms = qsgd_encode_flat2d(flat2d, k_enc, spec.bits,
                                               threefry=not batched,
                                               chunk_rows=chunk_rows)
        else:
            # 2-D mesh: encode ONLY this device's model-axis row segment of
            # the (already-trained, model-replicated) delta stack; the
            # global row offset keys the dither, so the model-concatenated
            # codes equal the single-device encode bit for bit
            if not batched:
                raise ValueError("row_block requires the batched "
                                 "counter-hash dither convention")
            axis, nm = row_block
            d = flat2d.shape[1]
            rows = -(-d // _kq.LANES)
            rows_pad = -(-rows // nm) * nm
            cpad = rows_pad * _kq.LANES - d
            xp = flat2d if not cpad else jnp.concatenate(
                [flat2d, jnp.zeros((b, cpad), flat2d.dtype)], axis=1)
            x3 = xp.reshape(b, rows_pad, _kq.LANES)
            rows_l = rows_pad // nm
            midx = jax.lax.axis_index(axis)
            x3_l = jax.lax.dynamic_slice_in_dim(x3, midx * rows_l, rows_l,
                                                axis=1)
            seeds = jnp.asarray(k_enc).reshape(b, -1)[:, :2].astype(jnp.uint32)
            packed, norms = qsgd_encode_rows(
                x3_l, seeds, spec.bits, (midx * rows_l).astype(jnp.uint32),
                chunk_rows=chunk_rows)
        out = {"packed": packed, "norms": norms}
    elif spec.kind == "lowrank":
        from repro.core.quantizers import (lowrank_expand_flat2d,
                                           lowrank_project_flat2d)
        from repro.obs.taps import decode_qsgd_stack
        seeds = jnp.asarray(basis_seed).reshape(-1)[:2].astype(jnp.uint32)
        c2d = flat2d if residual is None else flat2d + residual
        if tap_gather is not None:
            # a mesh caller's c2d arrives d-sharded; the projection's
            # g-element group sums must run in the meshless (replicated)
            # grouping or the wire bits drift (see _cohort_step_fn)
            c2d = tap_gather(c2d)
        y2d = lowrank_project_flat2d(c2d, seeds, spec.group)
        # one cond pins the pair: the encode's bucket-norm math and the
        # error-feedback subtraction below both consume materialized
        # operands, so mesh/chunk variants cannot FMA-contract differently
        c2d, y2d = boundary((c2d, y2d))
        packed, norms = qsgd_encode_flat2d(y2d, k_enc, spec.bits,
                                           threefry=not batched,
                                           chunk_rows=chunk_rows)
        qy2d = decode_qsgd_stack(packed, norms, spec.bits, y2d.shape[1])
        xq2d = lowrank_expand_flat2d(qy2d, seeds, spec.group, c2d.shape[1])
        out = {"packed": packed, "norms": norms, "residual": c2d - xq2d}
        if taps:
            from repro.obs.taps import cohort_tap_rows_lowrank
            tc = c2d if tap_gather is None else tap_gather(c2d)
            te = out["residual"] if tap_gather is None else tap_gather(
                out["residual"])
            out["taps"] = cohort_tap_rows_lowrank(boundary, tc, te, y2d, qy2d)
        return (out, losses) if with_loss else out
    else:
        out = {"flat": flat2d}
    if taps:
        from repro.obs.taps import cohort_tap_rows, decode_qsgd_stack
        q2d = None
        t2d = flat2d if tap_gather is None else tap_gather(flat2d)
        if spec.kind == "qsgd":
            # the qdq half of the error tap decodes the ACTUAL wire bits —
            # the exact vector the server will accumulate — in the same
            # graph; identity uploads wire the raw delta (error exactly 0)
            # and sparse kinds are host-encoded after the dispatch
            # (reported as 0 here)
            p_, n_ = out["packed"], out["norms"]
            if row_block is not None:
                # gather-to-replicated BEFORE reducing along d (the taps
                # sharding-invariance law): every model rank reconstructs
                # the full wire bits and reduces the single-device shapes
                p_ = jax.lax.all_gather(p_, row_block[0], axis=1, tiled=True)
                n_ = jax.lax.all_gather(n_, row_block[0], axis=1, tiled=True)
            q2d = decode_qsgd_stack(p_, n_, spec.bits, flat2d.shape[1])
            if tap_gather is not None:
                q2d = tap_gather(q2d)
        out["taps"] = cohort_tap_rows(boundary, t2d, q2d)
    return (out, losses) if with_loss else out


def server_apply_flat(x, momentum, delta, *, lr, beta, boundary=None):
    """The ONE FedBuff server-update implementation (Algorithm 1 line 12 +
    server momentum): m <- beta m + Delta-bar; x <- x + eta_g m.

    Operates on single arrays — the server's flat f32 vectors, or one pytree
    leaf at a time (``server_apply`` maps it over trees for the distributed
    round). ``beta is None`` disables momentum.

    ``boundary`` is the fused flush's materialization hook
    (``repro.kernels.ops.hard_boundary``): eagerly each multiply and add is
    its own dispatch, but inside one jitted computation XLA would contract
    the scalar multiply into its consumer's add (FMA) and change bits, so
    the fused caller pins the products at a hard boundary. Eager and
    in-graph tree callers leave it None.

    Returns ``(x_new, momentum_new)``.
    """
    hard = boundary if boundary is not None else (lambda v: v)
    if beta is not None:
        t1 = hard(beta * momentum)
        momentum = (t1 + delta).astype(delta.dtype)
    else:
        momentum = delta
    t2 = hard(lr * momentum)
    x = (t2 + x).astype(x.dtype)
    return x, momentum


def server_apply(qcfg: QAFeLConfig, x, momentum, delta_bar):
    """Pytree view of ``server_apply_flat`` (the distributed round and the
    FedBuff identity-limit drivers hold trees)."""
    beta = qcfg.server_momentum if qcfg.server_momentum else None
    leaves_x, treedef = jax.tree.flatten(x)
    leaves_m = jax.tree.leaves(momentum)
    leaves_d = jax.tree.leaves(delta_bar)
    out = [server_apply_flat(xi, mi, di, lr=qcfg.server_lr, beta=beta)
           for xi, mi, di in zip(leaves_x, leaves_m, leaves_d)]
    return (jax.tree.unflatten(treedef, [o[0] for o in out]),
            jax.tree.unflatten(treedef, [o[1] for o in out]))


@functools.lru_cache(maxsize=32)
def _jitted_client_update(loss_fn: Callable, qcfg: QAFeLConfig):
    """jit(client_update) cached by (loss_fn, qcfg): benchmark sweeps build
    many QAFeL instances over the same task and should compile once. The
    cache is bounded because loss_fn closures can capture datasets — an
    unbounded cache would pin them for the process lifetime."""
    return jax.jit(functools.partial(client_update, loss_fn, qcfg))


@jax.jit
def _hidden_drift_ratio(x_flat, hidden_flat):
    """|| x - x-hat || / || x || as ONE jitted flat reduction (the device
    sync happens only when the caller converts the result to float)."""
    d = (x_flat - hidden_flat).astype(jnp.float32)
    num = jnp.sqrt(jnp.sum(d * d))
    den = jnp.sqrt(jnp.sum(x_flat.astype(jnp.float32) ** 2))
    return num / jnp.maximum(den, 1e-30)


# ---------------------------------------------------------------------------
# Host orchestration
# ---------------------------------------------------------------------------


def _count_h2d(*arrays) -> None:
    """Add the window's host wire arrays handed to a flush dispatch to the
    host-link counter (device arrays cross nothing). The (K,) weights and
    the (2,) key also go as host arrays, 48 bytes at K=10: they are not
    wire bytes and are not counted."""
    spans.h2d_bytes += sum(a.nbytes for a in arrays
                           if isinstance(a, np.ndarray))


def place_flat_on_mesh(flat, mesh, n: int) -> jnp.ndarray:
    """Canonicalize a flat f32 vector (any padding) to the mesh's
    segment-aligned padded length and place it with the flat-vector
    NamedSharding. Always returns a fresh buffer (the flush donates these,
    so no two state vectors may alias)."""
    from repro.sharding.rules import (flat_padded_len, flat_vector_sharding,
                                      mesh_flat_extent)

    n_pad = flat_padded_len(n, mesh_flat_extent(mesh))
    flat = jnp.asarray(flat, jnp.float32).reshape(-1)[:n]
    if n_pad > n:
        flat = jnp.concatenate([flat, jnp.zeros((n_pad - n,), flat.dtype)])
    else:
        # already aligned: force a copy — a full-range slice is a no-op
        # view, and two donated state vectors must never share a buffer
        flat = jnp.array(flat, copy=True)
    return jax.device_put(flat, flat_vector_sharding(mesh))


@dataclasses.dataclass
class ServerState:
    """Device-resident server state.

    ``x`` (full-precision model), ``x-hat`` (shared hidden state) and the
    server momentum are flat f32 vectors in the coordinate space of one
    ``TreeLayout``. The flush updates them in place (buffer donation); tree
    views are materialized lazily and cached per server step — they exist
    only at the eval / client-update boundaries, never on the flush path.

    With a ("data",) ``mesh`` the vectors are ``jax.NamedSharding``-placed:
    each device owns one contiguous, 128-bucket-row-aligned segment
    (``sharding.rules.flat_vector_spec``), the vectors are zero-padded to
    ``sharding.rules.flat_padded_len`` so segments align to wire bucket
    rows, and the flush runs as the sharded single dispatch
    (``kernels.ops.server_flush_step_sharded``) — bit-identical to the
    single-device path. ``layout.total_size`` stays the TRUE coordinate
    count; tree views and wire payloads never see the padding.
    """

    x_flat: jnp.ndarray
    hidden_flat: jnp.ndarray
    momentum_flat: jnp.ndarray
    layout: TreeLayout
    t: int = 0  # server step counter (model version)
    mesh: Any = dataclasses.field(default=None, repr=False, compare=False)
    _x_tree: Any = dataclasses.field(default=None, repr=False, compare=False)
    _hidden_tree: Any = dataclasses.field(default=None, repr=False, compare=False)

    @staticmethod
    def init(params0, mesh=None) -> "ServerState":
        flat, layout = flatten_tree(params0)
        if mesh is not None:
            flat = place_flat_on_mesh(flat, mesh, layout.total_size)
            return ServerState(
                x_flat=flat,
                hidden_flat=place_flat_on_mesh(flat, mesh, layout.total_size),
                momentum_flat=place_flat_on_mesh(jnp.zeros_like(flat), mesh,
                                                 layout.total_size),
                layout=layout, t=0, mesh=mesh)
        return ServerState(x_flat=flat, hidden_flat=jnp.array(flat),
                           momentum_flat=jnp.zeros_like(flat),
                           layout=layout, t=0)

    @property
    def n(self) -> int:
        """TRUE coordinate count (the wire dimension d); ``x_flat`` may be
        longer when segment-aligned-padded for a mesh."""
        return self.layout.total_size

    @property
    def x(self):
        """Lazy (cached) tree view of the full-precision server model."""
        if self._x_tree is None:
            self._x_tree = self.layout.unflatten(self.x_flat)
        return self._x_tree

    @property
    def hidden_tree(self):
        """Lazy (cached) tree view of the shared hidden state x-hat."""
        if self._hidden_tree is None:
            self._hidden_tree = self.layout.unflatten(self.hidden_flat)
        return self._hidden_tree

    @property
    def hidden(self) -> HiddenState:
        """Back-compat wrapper: ``state.hidden.value`` is the x-hat tree view."""
        return HiddenState(value=self.hidden_tree)

    @property
    def momentum(self):
        """Tree view of the server momentum (uncached; diagnostics only)."""
        return self.layout.unflatten(self.momentum_flat)


class QAFeL:
    """Server + client logic of Algorithms 1-3, driven by an event loop.

    ``mesh`` (a ("data",) mesh from ``launch.mesh.make_sim_mesh``) turns on
    the sharded flat substrate: the server state lives as NamedSharding-
    placed segment vectors, the flush runs the sharded single dispatch,
    and the cohort train+encode step shards cohort members — all
    bit-identical to the single-device path at the same seed.

    ``telemetry`` (a ``repro.obs.RunTracer``) turns on structured run
    tracing: one typed event per upload / drop / flush / broadcast, and —
    when the tracer has ``taps=True`` — the fused dispatches additionally
    emit their in-dispatch metric tap vectors (``repro.obs.taps``), which
    land on the events and in ``metrics()``. With ``telemetry=None``
    (default) every dispatch keeps its pre-telemetry signature and the
    trajectory is bit-identical to a pre-telemetry run.
    """

    def __init__(self, qcfg: QAFeLConfig, loss_fn: Callable, params0,
                 mesh=None, telemetry=None, chunk_rows=None,
                 basis_seed: int = 0):
        self.qcfg = qcfg
        self.loss_fn = loss_fn
        self.cq = qcfg.cq()
        self.sq = qcfg.sq()
        self.mesh = mesh
        self.telemetry = telemetry
        # LLM-scale streaming: tile the client encode and the sharded flush
        # over fixed-size wire-row chunks (bit-invisible; see
        # kernels.ops.server_flush_step_sharded / quantizers.qsgd_encode_*)
        self.chunk_rows = int(chunk_rows) if chunk_rows else None
        # in-flight chunk-streamed uploads, keyed by (client, stream, version)
        self._pending_chunks: Dict[Any, list] = {}
        # lowrank upload subspace: the run-level basis seed (the per-round
        # sketch is keyed (basis_seed, server version) via
        # kernels.qsgd.basis_seeds — both sides derive it, no extra wire
        # bytes) and the per-client error-feedback residual store. The
        # server OWNS the residuals in this simulator because the hidden
        # state already lives here; a real deployment keeps each residual
        # on its client — the math is identical (see DESIGN.md).
        self.basis_seed = int(basis_seed)
        self._residuals: Dict[Any, Any] = {}
        self._taps = bool(telemetry is not None and telemetry.taps)
        self.state = ServerState.init(params0, mesh=mesh)
        # the runtime-True predicate behind the fused flush's hard
        # materialization boundaries (see kernels.ops.hard_boundary)
        self._flag = jnp.asarray(True)
        # Packed mode: the buffer stores uploads as wire tensors (uint8 codes
        # + bucket norms) and dequantizes once per flush inside the fused
        # server_flush_step.
        self.buffer = UpdateBuffer(capacity=qcfg.buffer_size, quantizer=self.cq)
        self.meter = TrafficMeter()
        self.staleness = StalenessMonitor(max_allowed=qcfg.max_staleness)

    # -- client side ------------------------------------------------------
    def round_basis_seed(self):
        """The (2,) uint32 sketch-basis seed of the CURRENT round: keyed
        (run basis_seed, server version) so the basis rotates every server
        step — a fixed basis would starve its orthogonal complement and
        bias the error feedback forever. Both sides derive it from the
        version they already share; no extra bytes ship."""
        from repro.kernels import qsgd as _kq
        return _kq.basis_seeds(self.basis_seed, self.state.t)

    def client_residuals(self, clients) -> jnp.ndarray:
        """Stack the (b, d) error-feedback residuals for ``clients`` (ids,
        one per cohort member; unseen ids start at zero). Lowrank client
        state: what previous quantized subspace messages failed to carry."""
        d = self.state.n
        zero = None
        rows = []
        for cid in clients:
            r = self._residuals.get(cid)
            if r is None:
                if zero is None:
                    zero = jnp.zeros((d,), jnp.float32)
                r = zero
            rows.append(jnp.asarray(r).reshape(-1))
        return jnp.stack(rows)

    def store_residuals(self, clients, residual2d) -> None:
        """Write back the fused step's NEW (b, d) residual stack, one row
        per member of ``clients`` (padding rows already sliced off)."""
        for i, cid in enumerate(clients):
            self._residuals[cid] = residual2d[i]

    def run_client(self, batches, key, client=None) -> Tuple[Message, int]:
        """Algorithm 2 on the CURRENT hidden state; returns (message, version).

        One fused train+encode dispatch (``kernels.ops.
        cohort_train_encode_step`` at b=1): the flat x-hat goes in, the
        packed wire payload comes out — no ``hidden_tree`` view and no
        separate encode dispatch, bit-identical to the pre-fusion
        two-dispatch path. The cohort engine takes the same entry with
        b = cohort_size, so both engines share one client pipeline.

        ``client`` is the caller's client id — lowrank uploads key their
        error-feedback residual on it (omitted/None uses one shared slot,
        fine for single-client drivers).

        In the async simulator the caller records the version now and
        delivers the message later (after the sampled training duration).
        """
        from repro.kernels import ops as kops  # local import: kernels optional

        k_train, k_enc = jax.random.split(key)
        st = self.state
        lowrank = self.cq.spec.kind == "lowrank"
        kw = {}
        bseed = None
        if lowrank:
            bseed = self.round_basis_seed()
            kw = {"residual": self.client_residuals([client]),
                  "basis_seed": bseed}
        out = kops.cohort_train_encode_step(
            self.loss_fn, self.qcfg, self.cq.spec, st.layout, st.hidden_flat,
            batches, k_train, k_enc, self._flag, b=1, mesh=self.mesh,
            taps=self._taps, chunk_rows=self.chunk_rows, **kw)
        if lowrank:
            self.store_residuals([client], out["residual"])
        msg = frame_cohort_messages(CLIENT_UPDATE, self.cq, out, st.layout,
                                    enc_keys=[k_enc], version=st.t,
                                    basis_seed=bseed)[0]
        if self._taps:
            from repro.obs.taps import named_cohort_taps
            msg.meta["taps"] = named_cohort_taps(out["taps"][0])
        return msg, st.t

    def run_client_stream(self, batches, key, *,
                          chunk_rows=None) -> Tuple[list, int]:
        """Algorithm 2 with a memory-bounded uplink: one fused train
        dispatch produces the flat delta, then a host loop of per-chunk
        quantize-encode dispatches (``kernels.ops.qsgd_quantize_chunk``)
        streams the packed wire rows out ``chunk_rows`` rows at a time —
        the full packed message never materializes on a device, only one
        chunk of codes at any moment. The threefry dither is keyed by the
        GLOBAL wire-row index, so the streamed chunks reassemble to the
        fused ``run_client`` message bit for bit (pinned in
        tests/test_mesh2d.py). Returns ``(chunk messages, version)``; feed
        the messages to ``receive`` in any order — the buffer validates
        and reassembles the stream (``UpdateBuffer.add_encoded_chunks``).
        """
        from repro.kernels import ops as kops  # local import: kernels optional

        if self.cq.spec.kind != "qsgd":
            raise ValueError("streamed uploads are defined for qsgd client "
                             f"quantizers (got {self.cq.spec.kind!r})")
        c = int(chunk_rows if chunk_rows else (self.chunk_rows or 0))
        if c <= 0:
            raise ValueError("run_client_stream needs chunk_rows (argument "
                             "or QAFeL(chunk_rows=...))")
        k_train, k_enc = jax.random.split(key)
        st = self.state
        # identity-spec fused step = the SAME train math as run_client's
        # dispatch, returning the flat delta instead of encoding in-jit
        out = kops.cohort_train_encode_step(
            self.loss_fn, self.qcfg, make_quantizer("identity").spec,
            st.layout, st.hidden_flat, batches, k_train, k_enc, self._flag,
            b=1, mesh=self.mesh)
        delta = out["flat"][0]
        n, bits = st.n, self.cq.spec.bits
        rows = kops.rows_for(n)
        nch = -(-rows // c)
        pad = nch * c * kops.BUCKET - n
        if pad:  # zero tail: padded rows encode to zero codes, sliced off
            delta = jnp.concatenate([delta, jnp.zeros((pad,), delta.dtype)])
        chunks = []
        for i in range(nch):
            r0 = i * c
            p_c, n_c = kops.qsgd_quantize_chunk(
                delta[r0 * kops.BUCKET:(r0 + c) * kops.BUCKET], k_enc, r0,
                bits=bits)
            rc = min(c, rows - r0)  # true rows of the tail chunk
            chunks.append(packed_qsgd_chunk_payload(
                np.asarray(p_c[:rc]), np.asarray(n_c[:rc]), bits, n,
                st.layout, row0=r0, seq=i, last=(i == nch - 1)))
        msgs = frame_chunk_messages(CLIENT_UPDATE, self.cq, chunks, st.layout,
                                    version=st.t, stream=st.t)
        return msgs, st.t

    # -- checkpoint / resume ----------------------------------------------
    def save_checkpoint(self, path) -> None:
        """Serialize the flat ``ServerState`` + buffer occupancy + meters so
        a run can resume bit-identically (``repro.core.checkpoint``)."""
        from repro.core.checkpoint import save_checkpoint
        save_checkpoint(path, self)

    def load_checkpoint(self, path) -> "QAFeL":
        """Restore state saved by ``save_checkpoint`` into this instance
        (layout identity is verified against this model). Returns self."""
        from repro.core.checkpoint import load_checkpoint
        return load_checkpoint(path, self)

    # -- server side ------------------------------------------------------
    def receive(self, msg: Message, key, n_receivers: int = 1) -> Optional[Message]:
        """Algorithm 1 lines 5-16. Returns the broadcast message on a flush.

        The upload is NOT decoded here: its packed wire payload goes straight
        into the buffer, and the fused dequantize-accumulate runs inside the
        single-dispatch ``server_flush_step`` when the buffer flushes.
        ``n_receivers`` is the number of concurrently active clients the
        resulting broadcast fans out to (downlink byte accounting).
        """
        if (isinstance(msg.payload, dict)
                and msg.payload.get("format") == "packed_chunk"):
            return self._receive_chunk(msg, key, n_receivers)
        version = msg.meta["version"]
        if version > self.state.t:
            # clock-skew / replay guard: a client cannot have trained on a
            # model version the server has not produced yet; accepting it
            # would compute a negative staleness (and an amplifying weight)
            raise ValueError(
                f"message version {version} is ahead of the server clock "
                f"t={self.state.t} (clock skew or replay)")
        tau = self.state.t - version
        if self.staleness.would_drop(tau):
            # Assumption 3.4 as a drop policy: the upload is rejected before
            # it reaches the buffer; the uplink bytes were still spent.
            self.meter.record_dropped(msg)
            self.staleness.record_dropped(tau)
            if self.telemetry is not None:
                self.telemetry.emit("drop", step=self.state.t,
                                    client=msg.meta.get("client", -1),
                                    tau=tau, reason="stale")
            return None
        self.meter.record(msg)
        self.staleness.observe(tau)
        # host-side scalar of staleness_weight: a jnp call here would force a
        # device sync on every single upload
        w = (1.0 / math.sqrt(1.0 + tau)) if self.qcfg.staleness_scaling else 1.0
        if self.telemetry is not None:
            extra = ({"taps": msg.meta["taps"]} if "taps" in msg.meta else {})
            self.telemetry.emit("upload", step=self.state.t,
                                client=msg.meta.get("client", -1),
                                tau=tau, weight=w, **extra)
        payload = msg.payload
        if isinstance(payload, dict) and payload.get("format") == "packed":
            native = (payload["kind"] == self.cq.spec.kind
                      and payload.get("bits") in (None, self.cq.spec.bits))
            if native and payload["kind"] == "lowrank":
                # a lowrank tier with a different sketch group lives in a
                # different subspace — its message must decode eagerly
                native = payload.get("group") == self.cq.spec.group
            if native:
                with spans.span("qafel.buffer.add"):
                    self.buffer.add_encoded(payload, weight=w)
            else:
                # a bit-width-tier client uploaded through a different
                # quantizer: its packed payload is self-describing, so decode
                # eagerly — straight to the buffer's FLAT accumulator, no
                # tree view (the default-tier majority stays packed)
                self.buffer.add_decoded_flat(self.cq.decode_flat(payload),
                                             weight=w, layout=payload["layout"])
        else:  # legacy per-leaf message: decode eagerly
            self.buffer.add(decode_message(self.cq, msg), weight=w)
        if not self.buffer.full:
            return None
        return self._flush(key, n_receivers)

    def _receive_chunk(self, msg: Message, key,
                       n_receivers: int) -> Optional[Message]:
        """One streamed chunk of an upload (``run_client_stream``). The
        stream meters as ONE upload when it completes, with its summed chunk
        bytes (equal to the unstreamed message's wire total exactly —
        ``frame_chunk_messages``), so traffic summaries are identical to the
        fused uplink's; the staleness decision and the buffer insert also
        happen once, at completion, against the server clock at that time."""
        version = msg.meta["version"]
        if version > self.state.t:
            raise ValueError(
                f"message version {version} is ahead of the server clock "
                f"t={self.state.t} (clock skew or replay)")
        sid = (msg.meta.get("client", -1), msg.meta.get("stream", 0), version)
        pend = self._pending_chunks.setdefault(sid, [[], 0])
        pend[0].append(msg.payload)
        pend[1] += msg.wire_bytes
        if not msg.payload["last"]:
            return None
        chunks, stream_bytes = self._pending_chunks.pop(sid)
        tau = self.state.t - version
        if self.staleness.would_drop(tau):
            self.meter.uploads_dropped += 1
            self.meter.dropped_bytes += stream_bytes
            self.staleness.record_dropped(tau)
            if self.telemetry is not None:
                self.telemetry.emit("drop", step=self.state.t,
                                    client=msg.meta.get("client", -1),
                                    tau=tau, reason="stale")
            return None
        self.meter.record_stream(msg.payload, stream_bytes)
        self.staleness.observe(tau)
        w = (1.0 / math.sqrt(1.0 + tau)) if self.qcfg.staleness_scaling else 1.0
        if self.telemetry is not None:
            self.telemetry.emit("upload", step=self.state.t,
                                client=msg.meta.get("client", -1),
                                tau=tau, weight=w)
        self.buffer.add_encoded_chunks(chunks, weight=w)
        if not self.buffer.full:
            return None
        return self._flush(key, n_receivers)

    def _flush(self, key, n_receivers: int) -> Message:
        """Algorithm 1 lines 11-16 as one fused device dispatch.

        The broadcast carries q^t = Q_s(x^{t+1} - x-hat^t), and the server
        applies the *decoded wire bits themselves* — the exact increment
        every client decodes — which is what keeps all x-hat replicas
        bit-identical. Both the quantize-pack and that decode-apply happen
        inside the single jitted step.
        """
        with spans.span("qafel.flush", t=self.state.t):
            return self._flush_body(key, n_receivers)

    def _flush_body(self, key, n_receivers: int) -> Message:
        from repro.kernels import ops as kops  # local import: kernels are optional

        st = self.state
        # validate BEFORE drain(): drain resets the window, so failing after
        # it would silently discard the K buffered uploads
        if self.buffer.layout != st.layout:
            raise ValueError("buffered uploads do not match the server's "
                             "parameter layout")
        with spans.span("qafel.buffer.drain"):
            batch: FlushBatch = self.buffer.drain(normalize="capacity")
        tap_vec = None
        kind = self.sq.spec.kind
        if kind in ("qsgd", "identity"):
            sbits = self.sq.spec.bits if kind == "qsgd" else None
            # the (2,) key goes in as it comes (host numpy from the cohort
            # engine): the jitted step reshapes it, no eager device op here
            skey = key if kind == "qsgd" else None
            beta = self.qcfg.server_momentum if self.qcfg.server_momentum else None
            bits = batch.bits if batch.bits is not None else 0
            # lowrank upload window: the stacked wire pairs are RANK-length
            # (never row-padded to the state layout) and the flush needs the
            # static sketch group + the traced (K, 2) per-upload basis seeds
            lowrank_win = batch.kind == "lowrank" and batch.stack is not None
            lkw = ({"group": batch.group, "lseeds": batch.seeds}
                   if lowrank_win else {})
            if self.mesh is not None:
                # sharded substrate: pad the window's raw ingredients to the
                # state's segment-aligned layout (zero rows/elements are
                # numerically inert) and run the sharded single dispatch;
                # the payload is sliced back to the true wire rows, so the
                # broadcast bytes are identical to the single-device path.
                rows = kops.rows_for(batch.n)
                rows_pad = int(st.x_flat.shape[0]) // kops.BUCKET
                stack, norms, extra = batch.stack, batch.norms, batch.extra
                if stack is not None and rows_pad > rows and not lowrank_win:
                    xp = np if isinstance(stack, np.ndarray) else jnp
                    k_, _, lanes = stack.shape
                    stack = xp.concatenate(
                        [stack, xp.zeros((k_, rows_pad - rows, lanes),
                                         stack.dtype)], axis=1)
                    norms = xp.concatenate(
                        [norms, xp.zeros((k_, rows_pad - rows), norms.dtype)],
                        axis=1)
                if extra is not None and rows_pad * kops.BUCKET > batch.n:
                    extra = jnp.concatenate(
                        [jnp.asarray(extra, jnp.float32),
                         jnp.zeros((rows_pad * kops.BUCKET - batch.n,),
                                   jnp.float32)])
                with spans.span("qafel.flush.dispatch"):
                    _count_h2d(stack, norms, extra)
                    out = kops.server_flush_step_sharded(
                        st.x_flat, st.hidden_flat, st.momentum_flat,
                        stack, norms, batch.weights, extra, skey,
                        self._flag, bits=bits, sbits=sbits,
                        lr=self.qcfg.server_lr, beta=beta, mesh=self.mesh,
                        n=batch.n if (self._taps or lowrank_win) else None,
                        taps=self._taps, chunk_rows=self.chunk_rows, **lkw)
                x_new, h_new, m_new, payload = out[:4]
                if self._taps:
                    tap_vec = out[4]
                if kind == "qsgd":
                    payload = (payload[0][:rows], payload[1][:rows])
                else:
                    payload = (payload[0][:batch.n],)
            else:
                with spans.span("qafel.flush.dispatch"):
                    _count_h2d(batch.stack, batch.norms, batch.extra)
                    out = kops.server_flush_step(
                        st.x_flat, st.hidden_flat, st.momentum_flat,
                        batch.stack, batch.norms, batch.weights, batch.extra,
                        skey, self._flag,
                        bits=bits, sbits=sbits, n=batch.n,
                        lr=self.qcfg.server_lr, beta=beta, taps=self._taps,
                        **lkw)
                x_new, h_new, m_new, payload = out[:4]
                if self._taps:
                    tap_vec = out[4]
            with spans.span("qafel.flush.frame"):
                if kind == "qsgd":
                    enc = packed_qsgd_payload(payload[0], payload[1], sbits,
                                              batch.n, st.layout)
                else:
                    enc = packed_identity_payload(payload[0], batch.n,
                                                  st.layout)
                bmsg = frame_packed_message(HIDDEN_BROADCAST, self.sq, enc,
                                            t=st.t)
                self.meter.record(bmsg, n_receivers=n_receivers)
        else:
            # top_k / rand_k server quantizers have data-dependent wire
            # shapes (argsort / gather): a short flat-vector chain instead
            # of the single fused dispatch — still no pytree anywhere. Under
            # a mesh the chain runs on one device, over the true-n vectors
            # (its encode may be a Pallas kernel, which GSPMD cannot
            # partition), and the results are re-placed as segment vectors.
            delta = batch.reduce()
            beta = self.qcfg.server_momentum if self.qcfg.server_momentum else None
            x_cur, h_cur, m_cur = st.x_flat, st.hidden_flat, st.momentum_flat
            if self.mesh is not None:
                x_cur, h_cur, m_cur, delta = (
                    np.asarray(v)[:batch.n]
                    for v in (x_cur, h_cur, m_cur, delta))
            x_new, m_new = server_apply_flat(
                x_cur, m_cur, delta, lr=self.qcfg.server_lr, beta=beta)
            diff = x_new - h_cur
            # lowrank broadcasts ride the non-fused chain: encode_flat owns
            # the sketch projection (the payload is self-describing, so the
            # replicas decode from its seed)
            bmsg = encode_message_flat(HIDDEN_BROADCAST, self.sq, diff,
                                       st.layout, key,
                                       fast=self.sq.spec.kind != "lowrank",
                                       t=st.t)
            h_new = h_cur + self.sq.decode_flat(bmsg.payload)
            if self.mesh is not None:
                x_new = place_flat_on_mesh(x_new, self.mesh, batch.n)
                h_new = place_flat_on_mesh(h_new, self.mesh, batch.n)
                m_new = place_flat_on_mesh(m_new, self.mesh, batch.n)
            self.meter.record(bmsg, n_receivers=n_receivers)
        if self.telemetry is not None:
            extra = {}
            if tap_vec is not None:
                from repro.obs.taps import named_flush_taps
                extra["taps"] = named_flush_taps(tap_vec)
            self.telemetry.emit(
                "flush", step=st.t, window=self.qcfg.buffer_size,
                packed_k=0 if batch.stack is None else int(batch.stack.shape[0]),
                has_residual=batch.extra is not None, **extra)
            self.telemetry.emit("broadcast", step=st.t + 1,
                                n_receivers=n_receivers,
                                wire_kB=bmsg.wire_bytes / 1e3)
        # the staged window is rewritten only once this state is ready
        self.buffer.fence(x_new)
        self.state = ServerState(x_flat=x_new, hidden_flat=h_new,
                                 momentum_flat=m_new, layout=st.layout,
                                 t=st.t + 1, mesh=st.mesh)
        return bmsg

    # -- invariant checks / metrics ----------------------------------------
    def hidden_drift(self) -> float:
        """|| x - x-hat || / || x || — the quantization term of Lemma F.9.

        One jitted flat reduction; the float() conversion is the only device
        sync, and it happens only when this is explicitly called (metrics()
        skips it by default in hot loops). Under a mesh the vectors are
        sliced to the TRUE n and gathered first: a cross-segment psum (or a
        reduction over the padded length) has a different f32 reduction
        order than the single-device sum and drifts in the last ulp, and
        this metric is compared across runs — it must be sharding-invariant.
        """
        x, h = self.state.x_flat, self.state.hidden_flat
        if self.mesh is not None:
            n = self.state.n
            x = jnp.asarray(np.asarray(x)[:n])
            h = jnp.asarray(np.asarray(h)[:n])
        return float(_hidden_drift_ratio(x, h))

    def metrics(self, drift: bool = False) -> Dict[str, Any]:
        """The unified metrics surface (``repro.obs.metrics.collect``):
        the pre-telemetry ``TrafficMeter`` / ``StalenessMonitor`` /
        ``server_steps`` keys bit-for-bit, plus the tracer's deterministic
        tap series when telemetry is attached."""
        from repro.obs.metrics import collect
        return collect(self.meter, self.staleness, self.state.t,
                       tracer=self.telemetry,
                       drift=self.hidden_drift() if drift else None)
