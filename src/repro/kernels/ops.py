"""Public jitted wrappers around the Pallas kernels.

Handles backend dispatch (interpret=True off-TPU so the kernel bodies
execute in Python on CPU for correctness validation) and the two layouts a
message lives in:

* **wire layout** — what travels and is stored in the server buffer:
  ``rows_for(n) = ceil(n / 128)`` packed code rows + one fp32 bucket norm
  per row. Sized to the message, no tile padding (a 2048-coordinate message
  carries 16 rows, not a full kernel tile).
* **kernel tile layout** — what the Pallas grid needs: rows padded to a
  BLOCK_ROWS multiple. The padding (zero rows -> zero codes, numerically
  inert) is applied here at dispatch time and sliced off the results; it
  never reaches the wire or the buffer.

These wrappers are the packed wire path's only kernel entry points: a whole
pytree message is one flat vector, so ``qsgd_quantize`` is exactly one
dispatch per message (one padding tail, not one per leaf), and the server
buffer stacks the resulting (codes, norms) pairs verbatim for the single
fused ``buffer_aggregate`` pass at flush time. ``qsgd_quantize_batch``
quantizes a whole client cohort's (B, n) stack in one dispatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import buffer_agg as _agg
from repro.kernels import qsgd as _qsgd

TILE = _qsgd.BLOCK_ROWS * _qsgd.LANES  # elements per grid block
BUCKET = _qsgd.LANES  # one fp32 norm per 128-element row


def _interpret() -> bool:
    """Whether kernel calls take their interpret / XLA route: everywhere but
    a TPU. The Pallas route is a one-device program: callers whose operands
    are spread over a mesh call the kernels under ``shard_map``
    (``qsgd_dequantize_on_mesh``, the sharded flush), and GSPMD-partitioned
    graphs use the shared jnp block math directly."""
    return jax.default_backend() != "tpu"


def padded_len(n: int) -> int:
    """Length of the kernel-tile layout for an n-element message."""
    return ((n + TILE - 1) // TILE) * TILE


def rows_for(n: int) -> int:
    """Number of 128-lane rows (= bucket norms) a length-n message packs
    into on the wire."""
    return (n + BUCKET - 1) // BUCKET


def tile_rows_for(n: int) -> int:
    """Rows of the kernel-tile layout (wire rows padded to BLOCK_ROWS)."""
    return padded_len(n) // BUCKET


def _pad_rows(x2d: jnp.ndarray, tile_rows: int) -> jnp.ndarray:
    """Pad a (rows, ...) array with zero rows up to the kernel tile layout."""
    pad = tile_rows - x2d.shape[0]
    if pad:
        x2d = jnp.concatenate(
            [x2d, jnp.zeros((pad,) + x2d.shape[1:], x2d.dtype)])
    return x2d


@functools.partial(jax.jit, static_argnames=("bits",))
def qsgd_quantize(flat: jnp.ndarray, key, bits: int = 4):
    """Quantize a flat f32 vector.

    Returns (packed uint8 (rows, 128*bits//8), norms f32 (rows,)) in wire
    layout — one norm per 128-element bucket, rows = ceil(n / 128). Callers
    keep the true length n to slice after dequantize.
    """
    flat = flat.astype(jnp.float32)
    n = flat.shape[0]
    rows, tile_rows = rows_for(n), tile_rows_for(n)
    pad = rows * BUCKET - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    x2d = _pad_rows(flat.reshape(rows, BUCKET), tile_rows)
    # dither only for wire rows; padded tail rows are zeros -> zero codes
    # regardless of noise
    u2d = _pad_rows(jax.random.uniform(key, (rows, BUCKET), dtype=jnp.float32),
                    tile_rows)
    packed, norms = _qsgd.qsgd_quantize_pack(x2d, u2d, bits, interpret=_interpret())
    return packed[:rows], norms.reshape(-1)[:rows]


@functools.partial(jax.jit, static_argnames=("bits",))
def qsgd_quantize_batch(flat_batch: jnp.ndarray, keys, bits: int = 4):
    """Quantize a (B, n) stack of flat f32 messages in ONE kernel dispatch.

    ``keys`` is a (B, 2) stack of PRNG keys, one per message; their raw
    uint32 words seed the kernel's in-kernel counter-based dither
    (independent noise per client, no host-side threefry pass — see
    ``qsgd.qsgd_quantize_pack_batch``). The rounding noise therefore
    differs from ``qsgd_quantize``'s threefry uniforms message-for-message,
    but the wire format, unbiasedness and per-bucket error bound are
    identical. Returns (packed uint8 (B, rows, 128*bits//8), norms f32
    (B, rows)) in wire layout.
    """
    flat_batch = flat_batch.astype(jnp.float32)
    b, n = flat_batch.shape
    rows = rows_for(n)
    pad = rows * BUCKET - n
    if pad:
        flat_batch = jnp.concatenate(
            [flat_batch, jnp.zeros((b, pad), flat_batch.dtype)], axis=1)
    x3d = flat_batch.reshape(b, rows, BUCKET)
    seeds = jnp.asarray(keys).reshape(b, -1)[:, :2].astype(jnp.uint32)
    packed, norms = _qsgd.qsgd_quantize_pack_batch(x3d, seeds, bits,
                                                   interpret=_interpret())
    return packed, norms.reshape(b, rows)


# Trace counter for the streaming chunk encode, mirroring the fused-entry
# counters: the host-driven streaming client (``QAFeL`` with ``chunk_rows``)
# deliberately dispatches this once per chunk — it is NOT a fused single
# dispatch and is therefore NOT in KERNEL_ENTRY_POINTS — but it must compile
# once per chunk SHAPE (row_start is traced), not once per chunk.
ENCODE_CHUNK_TRACES = 0


@functools.partial(jax.jit, static_argnames=("bits", "threefry"))
def qsgd_quantize_chunk(flat_chunk: jnp.ndarray, key, row_start, *,
                        bits: int, threefry: bool = True):
    """Encode rows ``[row_start, row_start + rows_c)`` of a flat message —
    the streaming quantize-encode of the LLM-scale substrate: full packed
    codes never materialize on one device; each dispatch sees one
    fixed-size flat chunk and emits its wire rows.

    ``flat_chunk`` is ``(rows_c * 128,)`` f32 (the caller zero-pads the tail
    chunk's last row; zero elements encode to zero codes). ``row_start`` is
    TRACED — one compilation covers every chunk of a given shape.

    Bit-exactness with the whole-message entries, for any chunking:

    * ``threefry=True`` reproduces ``qsgd_quantize``'s b=1 wire convention:
      the dither rows are exact chunks of the whole message's
      ``jax.random.uniform(key, (rows, 128))`` field
      (``qsgd.threefry_uniform_rows`` applies jax's per-index threefry law).
    * ``threefry=False`` is the batched counter-hash convention keyed by
      the global element index (``row_start`` is the counter offset).

    Both laws are local to each element index, so the message's length
    never enters: one compilation serves every message with this chunk
    shape.

    Returns ``(packed uint8 (rows_c, 128*bits//8), norms f32 (rows_c,))``.
    """
    global ENCODE_CHUNK_TRACES
    ENCODE_CHUNK_TRACES += 1
    x2d = flat_chunk.astype(jnp.float32).reshape(-1, BUCKET)
    if threefry:
        u2d = _qsgd.threefry_uniform_rows(jnp.asarray(key), row_start,
                                          x2d.shape[0])
        packed, norms = _qsgd._quantize_pack_block(x2d, u2d, bits)
        return packed, norms.reshape(-1)
    seeds = jnp.asarray(key).reshape(1, -1)[:, :2].astype(jnp.uint32)
    p3, n3 = _qsgd._quantize_pack_batch_block(
        x2d[None], seeds[:, 0], seeds[:, 1],
        jnp.asarray(row_start).astype(jnp.uint32), bits)
    return p3[0], n3.reshape(-1)


@functools.partial(jax.jit, static_argnames=("bits", "n"))
def qsgd_dequantize(packed: jnp.ndarray, norms: jnp.ndarray, bits: int, n: int) -> jnp.ndarray:
    """Dequantize wire-layout packed codes back to a flat f32 vector of
    length n. (Kernel-tile padding, if the backend needs it, happens inside
    the kernel wrapper.)"""
    x2d = _qsgd.qsgd_unpack_dequantize(jnp.asarray(packed), jnp.asarray(norms),
                                       bits, interpret=_interpret())
    return x2d.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("bits", "n", "mesh"))
def qsgd_dequantize_on_mesh(packed, norms, bits: int, n: int, mesh):
    """``qsgd_dequantize`` for a payload spread over ``mesh`` (a sharded
    flush's broadcast): decoding is row-local, so each device decodes its
    own whole wire rows under ``shard_map``, where the Pallas route runs per
    device; GSPMD cannot partition a Mosaic kernel. Rows pad to a device
    multiple with zero rows (decoded to zeros, sliced off). Returns the
    flat (n,) vector, spread over the mesh like the rows."""
    from jax.sharding import PartitionSpec as P

    from repro.common.compat import shard_map as _shard_map

    axes = tuple(mesh.axis_names)
    packed = jnp.asarray(packed)
    norms = jnp.asarray(norms).astype(jnp.float32).reshape(-1)
    rows = packed.shape[0]
    rpad = (-rows) % mesh.size
    if rpad:
        packed = jnp.concatenate(
            [packed, jnp.zeros((rpad, packed.shape[1]), packed.dtype)])
        norms = jnp.concatenate([norms, jnp.zeros((rpad,), norms.dtype)])
    dec = _shard_map(
        lambda p, nm: _qsgd.qsgd_unpack_dequantize(p, nm, bits,
                                                   interpret=_interpret()),
        mesh=mesh, in_specs=(P(axes, None), P(axes)),
        out_specs=P(axes, None), check_vma=False)
    return dec(packed, norms).reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("bits", "n"))
def buffer_aggregate(packed_stack: jnp.ndarray, norms: jnp.ndarray,
                     weights: jnp.ndarray, bits: int, n: int) -> jnp.ndarray:
    """Fused weighted dequantized sum over the K buffered messages -> flat (n,).

    packed_stack: (K, rows, 128*bits//8) wire-layout codes
    norms:        (K, rows) per-message bucket norms."""
    out2d = _agg.buffer_aggregate(jnp.asarray(packed_stack),
                                  jnp.asarray(norms), weights, bits,
                                  interpret=_interpret())
    return out2d.reshape(-1)[:n]


def lowrank_window_delta(stack, norms, weights, lseeds, boundary, *,
                         bits: int, group: int, y_width: int, elem0,
                         n_out: int, n_true=None):
    """Weighted expansion of one lowrank flush window over a contiguous,
    group-aligned output slice: ``delta[j] = sum_k w_k * sigma_k(elem0 + j)
    * y_k[(elem0 + j) // group] / sqrt(group)``.

    ``stack`` / ``norms`` are the window's K stacked RANK-length wire pairs
    (``(K, rows_r, 128*bits//8)`` / ``(K, rows_r)``), ``lseeds`` the (K, 2)
    uint32 per-upload basis seeds (mixed-staleness windows span basis
    versions, so every upload carries its own), ``weights`` the normalized
    staleness weights. The whole d_r-space dequantize runs first (small),
    then ONE vectorized O(K * n_out) expansion pass over the slice.

    Segment-locality law: ``elem0`` is the slice's GLOBAL flat element
    offset (traced ok) — the Rademacher signs hash global element indices
    and the subspace coordinate is ``index // group``, so any row-aligned
    split of the output concatenates to the unsplit expansion bit for bit.
    ``y_width`` statically pads/slices the decoded subspace vectors so a
    segment-padded caller can address coordinates past the true rank (they
    decode to zero codes -> zero). ``n_true`` zeroes output elements at or
    beyond the true coordinate count (a sharded caller's segment padding
    must NOT receive expansion mass — the unsharded path slices instead).

    Each ``w_k * expansion_k`` product is pinned behind ``boundary``
    (``hard_boundary``) before the ascending-k accumulation, so the sharded
    and unsharded flush modules cannot FMA-contract the chain differently.
    """
    k_n, rows_r = stack.shape[0], stack.shape[1]
    stack = jnp.asarray(stack)
    norms3 = jnp.asarray(norms).astype(jnp.float32).reshape(k_n, rows_r, 1)

    def dec(p, nm):
        return _qsgd._unpack_dequantize_block(p, nm, bits).reshape(-1)

    yk = jax.vmap(dec)(stack, norms3)  # (K, rows_r * 128)
    w_dec = yk.shape[1]
    if y_width > w_dec:
        yk = jnp.concatenate(
            [yk, jnp.zeros((k_n, y_width - w_dec), yk.dtype)], axis=1)
    elif y_width < w_dec:
        yk = yk[:, :y_width]
    y0 = (jnp.asarray(elem0) // group).astype(jnp.int32)
    ys = jax.lax.dynamic_slice_in_dim(yk, y0, n_out // group, axis=1)
    wv = jnp.asarray(weights, jnp.float32)
    seeds = jnp.asarray(lseeds).reshape(k_n, 2).astype(jnp.uint32)
    acc = jnp.zeros((n_out,), jnp.float32)
    for i in range(k_n):
        xi = _qsgd.sketch_expand(ys[i][None], seeds[i], group, elem0)[0]
        acc = acc + boundary(wv[i] * xi)
    if n_true is not None:
        idx = jnp.asarray(elem0) + jnp.arange(n_out)
        acc = jnp.where(idx < n_true, acc, 0.0)
    return acc


# ---------------------------------------------------------------------------
# Fused server flush: ONE jitted, buffer-donated dispatch for the whole
# QAFeL server step (Algorithm 1 lines 11-16)
# ---------------------------------------------------------------------------

# Trace counter: incremented every time the fused step is (re)traced.
# tests/test_server_flush.py asserts the flush compiles ONCE for a fixed
# server configuration — i.e. the whole flush really is a single compiled
# dispatch, not a chain re-traced per call.
SERVER_FLUSH_TRACES = 0


def hard_boundary(flag, vals):
    """A reliable materialization boundary inside one jitted computation.

    Routes ``vals`` (one array or a tuple) through a ``lax.cond`` whose
    predicate is a runtime-True flag the caller passes in. Because the
    predicate is a traced value, XLA cannot fold, remove, or fuse across
    the conditional — the operands materialize at the branch boundary
    exactly as an eager dispatch boundary would materialize them.

    This is what keeps the fused ``server_flush_step`` bit-identical to the
    eager multi-dispatch reference: ``jax.lax.optimization_barrier`` is NOT
    sufficient — XLA:CPU duplicates cheap producers (broadcast-constant or
    short dequantize tails) past the barrier into consumer fusions where a
    multiply+add pair contracts into an FMA, changing bits vs the eager
    path. A conditional is semantics-bearing and cannot be bypassed. The
    False branch (never taken) returns zeros so no instruction is common to
    both branches, which defeats XLA's conditional code motion.
    """
    single = not isinstance(vals, tuple)
    operand = (vals,) if single else vals
    out = jax.lax.cond(flag,
                       lambda vs: vs,
                       lambda vs: jax.tree.map(jnp.zeros_like, vs),
                       operand)
    return out[0] if single else out


# ---------------------------------------------------------------------------
# Fused cohort train+encode: ONE jitted dispatch for the whole client-side
# pipeline (Algorithm 2 + upload quantize-pack) of a cohort tier-group
# ---------------------------------------------------------------------------

# Trace counter for the fused client step, mirroring SERVER_FLUSH_TRACES:
# tests drive multi-cohort runs and assert the step compiles ONCE per
# (quantizer spec, cohort size) — i.e. the whole client path really is a
# single compiled dispatch per cohort, with tier groups mask-padded to a
# static shape so membership churn never retraces.
COHORT_STEP_TRACES = 0


def _index_pad_members(b: int, b_pad: int, batches, k_train, k_enc,
                       residual=None):
    """Index-pad the member dim from b to b_pad by repeating member 0 (the
    padding's outputs are sliced off by the caller). ``residual`` (the
    lowrank error-feedback (b, d) stack) pads with the members."""
    k_train, k_enc = jnp.asarray(k_train), jnp.asarray(k_enc)
    if b_pad == b:
        return batches, k_train, k_enc, residual
    idx = jnp.concatenate([jnp.arange(b), jnp.zeros((b_pad - b,), jnp.int32)])

    def take(l):
        return jnp.take(l, idx, axis=0)

    return (jax.tree.map(take, batches), take(k_train), take(k_enc),
            None if residual is None else take(residual))


def _scan_member_chunks(call, b: int, mc: int, batches, k_train, k_enc,
                        residual=None):
    """Run the per-chunk client pipeline ``call(batches, k_train, k_enc)``
    (a ``client_update_flat`` closure at b=mc) over ``ceil(b / mc)``
    member-chunks inside ONE ``lax.scan`` — still a single dispatch, but
    each chunk's train+encode working set stays cache-resident instead of
    streaming the whole (b, d) stack through memory per pass. This is the
    d=98304 parity lever: per-member math is independent and the batched
    counter-hash dither keys only on (member seed, global element index),
    so the wire bits are identical to the whole-cohort vmap for any mc.
    b is index-padded to a chunk multiple (member-0 repeats, sliced off).
    ``residual`` (lowrank) chunks with the members and ``call`` receives it
    as a fourth argument."""
    nch = -(-b // mc)
    batches, k_train, k_enc, residual = _index_pad_members(
        b, nch * mc, batches, k_train, k_enc, residual)

    def resh(l):
        return l.reshape((nch, mc) + l.shape[1:])

    xs = (jax.tree.map(resh, batches), resh(k_train), resh(k_enc))
    if residual is not None:
        xs = xs + (resh(residual),)

    def body(_, x):
        return None, call(*x)

    _, ys = jax.lax.scan(body, None, xs)
    return {k: v.reshape((nch * mc,) + v.shape[2:])[:b]
            for k, v in ys.items()}


class _PaddedMemberStep:
    """Callable façade over the jitted sharded cohort step that index-pads
    the member dim EAGERLY (host-side) before dispatch. ``lower`` pads the
    same way, so flcheck's compiled-HLO pass sees the real executable.

    On the lowrank path the call carries two trailing args ``(residual,
    basis_seed)``; the (b, d) residual stack is member-leading and pads
    with the members, the (2,) basis seed rides through unchanged."""

    def __init__(self, inner, b: int, b_pad: int):
        self._inner, self._b, self._b_pad = inner, b, b_pad

    def _pad(self, batches, k_train, k_enc, rest):
        residual = rest[0] if rest else None
        batches, k_train, k_enc, residual = _index_pad_members(
            self._b, self._b_pad, batches, k_train, k_enc, residual)
        return (batches, k_train, k_enc), ((residual,) + rest[1:] if rest
                                           else rest)

    def __call__(self, hidden_flat, batches, k_train, k_enc, flag, *rest):
        (batches, k_train, k_enc), rest = self._pad(batches, k_train, k_enc,
                                                    rest)
        return self._inner(hidden_flat, batches, k_train, k_enc, flag, *rest)

    def lower(self, hidden_flat, batches, k_train, k_enc, flag, *rest):
        (batches, k_train, k_enc), rest = self._pad(batches, k_train, k_enc,
                                                    rest)
        return self._inner.lower(hidden_flat, batches, k_train, k_enc, flag,
                                 *rest)


@functools.lru_cache(maxsize=64)
def _cohort_step_fn(loss_fn, qcfg, spec, layout, b: int, mesh=None,
                    taps: bool = False, member_chunk=None, chunk_rows=None):
    """jit of the flat-in/packed-out client pipeline, cached by
    (loss_fn, qcfg, quantizer spec, layout, cohort size, mesh, taps,
    member_chunk, chunk_rows) so engine instances, benchmark sweeps and
    scenario tiers share compilations. Bounded: loss_fn closures can
    capture datasets.

    With a ("data",) ``mesh`` and b > 1 the cohort member dim is sharded
    via shard_map: each device trains its member slice of the tier-group
    from the REPLICATED flat x-hat and emits its slice of packed codes +
    bucket norms; the global (b, rows, ...) outputs come back in the same
    wire layout, bit-identical to the single-device path (per-member math
    is independent, and the batched counter-hash dither depends only on
    each member's seed and element index, never on batch position). b is
    index-padded up to a device multiple inside the jit (padding repeats
    member 0; its rows are sliced off before returning), covering cohorts
    that don't divide the device count. A 1-device mesh still runs the
    one-segment shard_map — the same convention as the sharded flush, and
    the fixed cost the ``shard/*_ndev1`` bench rows measure. b == 1 always
    takes the unsharded path: a single message cannot shard over members,
    and its threefry dither is the sequential engine's pinned wire
    contract.

    With a 2-D ("data","model") mesh the member dim still shards over
    "data" while each member's ENCODE shards its wire rows over "model":
    training is replicated along "model" (the honest tradeoff — the model
    axis buys packed-code memory, not training FLOPs), each model rank
    slices its whole-bucket-row segment of the flat delta and encodes it
    with the segment's GLOBAL row offset keying the counter-hash dither,
    so the model-concatenated codes are the single-device wire bits
    exactly. The one model-axis collective on this path is the x-hat
    all-gather GSPMD inserts at the dispatch boundary (the replicated
    in_spec); taps add a wire-sized uint8 all_gather (see
    ``client_update_flat``).

    ``member_chunk`` tiles the member dim over mc-sized lax.scan chunks
    inside the same dispatch (``_scan_member_chunks`` — the cache-locality
    lever); ``chunk_rows`` tiles each encode over fixed-size wire-row
    chunks (``quantizers.qsgd_encode_flat2d``). Both are bit-invisible.
    """
    from repro.core.qafel import client_update_flat  # lazy: kernels stay core-free

    mc = (int(member_chunk)
          if member_chunk is not None and b > member_chunk else None)

    if mesh is None or b == 1:
        gather = None
        if (taps or spec.kind == "lowrank") and mesh is not None:
            # the b=1 path takes a SHARDED hidden_flat from a mesh server;
            # GSPMD would keep the tap reductions — and the lowrank sketch
            # projection, whose g-element group sums straddle the d-axis
            # segment boundaries — partitioned along d, and their f32
            # grouping would drift from the meshless bits — pin the inputs
            # to replicated before reducing (the flush taps make the same
            # move)
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            replicated = NamedSharding(mesh, P())

            def gather(v):
                return jax.lax.with_sharding_constraint(v, replicated)

        if spec.kind == "lowrank":
            # lowrank signature: the error-feedback residual stack and the
            # round's (2,) basis seed are extra TRACED args (the seed is
            # round state — tracing it keeps one compilation per config)
            def step(hidden_flat, batches, k_train, k_enc, flag, residual,
                     basis_seed):
                global COHORT_STEP_TRACES
                COHORT_STEP_TRACES += 1
                if mc is None:
                    return client_update_flat(
                        loss_fn, qcfg, spec, layout, hidden_flat, batches,
                        k_train, k_enc, flag, b=b, taps=taps,
                        tap_gather=gather, chunk_rows=chunk_rows,
                        residual=residual, basis_seed=basis_seed)
                return _scan_member_chunks(
                    lambda bt, kt, ke, res: client_update_flat(
                        loss_fn, qcfg, spec, layout, hidden_flat, bt, kt, ke,
                        flag, b=mc, batched=True, taps=taps,
                        tap_gather=gather, chunk_rows=chunk_rows,
                        residual=res, basis_seed=basis_seed),
                    b, mc, batches, k_train, k_enc, residual)

            return jax.jit(step)

        def step(hidden_flat, batches, k_train, k_enc, flag):
            global COHORT_STEP_TRACES
            COHORT_STEP_TRACES += 1
            if mc is None:
                return client_update_flat(
                    loss_fn, qcfg, spec, layout, hidden_flat, batches,
                    k_train, k_enc, flag, b=b, taps=taps, tap_gather=gather,
                    chunk_rows=chunk_rows)
            return _scan_member_chunks(
                lambda bt, kt, ke: client_update_flat(
                    loss_fn, qcfg, spec, layout, hidden_flat, bt, kt, ke,
                    flag, b=mc, batched=True, taps=taps, tap_gather=gather,
                    chunk_rows=chunk_rows),
                b, mc, batches, k_train, k_enc)

        return jax.jit(step)

    from jax.sharding import PartitionSpec as P

    from repro.common.compat import shard_map as _shard_map
    from repro.sharding.rules import (FLAT_MODEL_AXIS, mesh_data_extent,
                                      mesh_model_extent)

    ndev = mesh_data_extent(mesh)
    nm = mesh_model_extent(mesh)
    b_pad = -(-b // ndev) * ndev
    b_loc = b_pad // ndev
    row_block = ((FLAT_MODEL_AXIS, nm)
                 if nm > 1 and spec.kind == "qsgd" else None)
    mc_loc = (int(member_chunk)
              if member_chunk is not None and b_loc > member_chunk else None)

    def member_slice(hidden_flat, batches, k_train, k_enc, flag,
                     residual=None, basis_seed=None):
        # batched=True even at b_loc == 1: every member's wire bits must be
        # the batched counter-hash convention of the whole-cohort dispatch
        def call(bt, kt, ke, bb, res=None):
            return client_update_flat(loss_fn, qcfg, spec, layout,
                                      hidden_flat, bt, kt, ke, flag, b=bb,
                                      batched=True, taps=taps,
                                      chunk_rows=chunk_rows,
                                      row_block=row_block,
                                      residual=res, basis_seed=basis_seed)

        if mc_loc is None:
            return call(batches, k_train, k_enc, b_loc, residual)
        if residual is None:
            return _scan_member_chunks(
                lambda bt, kt, ke: call(bt, kt, ke, mc_loc),
                b_loc, mc_loc, batches, k_train, k_enc)
        return _scan_member_chunks(
            lambda bt, kt, ke, res: call(bt, kt, ke, mc_loc, res),
            b_loc, mc_loc, batches, k_train, k_enc, residual)

    if spec.kind == "qsgd":
        if row_block is not None:
            # wire rows shard over "model"; members over "data"
            out_specs = {"norms": P("data", FLAT_MODEL_AXIS),
                         "packed": P("data", FLAT_MODEL_AXIS, None)}
        else:
            out_specs = {"norms": P("data", None),
                         "packed": P("data", None, None)}
    elif spec.kind == "lowrank":
        # the d_r-length subspace encode is tiny: each member's wire pair
        # and its (d,) error-feedback residual shard over "data" only and —
        # under a 2-D mesh — stay replicated along "model", exactly like
        # the identity kind's flat payload (the model axis buys qsgd
        # packed-code memory; a rank-length message doesn't need it)
        out_specs = {"norms": P("data", None),
                     "packed": P("data", None, None),
                     "residual": P("data", None)}
    else:
        out_specs = {"flat": P("data", None)}
    if taps:
        # per-member tap rows shard over members like every other output;
        # each member's reduction runs over its own full (d,) row, so the
        # values are independent of the member-dim sharding (under a 2-D
        # mesh they are replicated along "model" — every model rank
        # reconstructs the full wire bits before reducing)
        out_specs["taps"] = P("data", None)

    def lead_spec(leaf):
        return P(*(["data"] + [None] * (leaf.ndim - 1)))

    rows = -(-layout.total_size // BUCKET)

    def step(hidden_flat, batches, k_train, k_enc, flag, *rest):
        global COHORT_STEP_TRACES
        COHORT_STEP_TRACES += 1
        in_specs = (P(), jax.tree.map(lead_spec, batches),
                    lead_spec(k_train), lead_spec(k_enc), P())
        if rest:  # lowrank: (residual P("data"), basis_seed replicated)
            in_specs = in_specs + (P("data", None), P())
        sm = _shard_map(
            member_slice, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False)
        out = sm(hidden_flat, batches, k_train, k_enc, flag, *rest)
        out = {k: v[:b] for k, v in out.items()}
        if row_block is not None:
            # model-axis padding rounded rows up to an nm multiple; slice
            # the global outputs back to the true wire rows
            out["packed"] = out["packed"][:, :rows]
            out["norms"] = out["norms"][:, :rows]
        return out

    # the member index-padding runs EAGERLY, before the jit: feeding a
    # computed (padded) member dim into the 2-D shard_map from inside the
    # same jit miscompiles on XLA:CPU (GSPMD reshards the scan-carrying
    # train body's inputs wrong — members permute / go NaN), while jit
    # ARGUMENTS partition correctly on every mesh shape. One host-side
    # gather per call, member-dim-sized — noise next to the train step.
    return _PaddedMemberStep(jax.jit(step), b, b_pad)


def cohort_train_encode_step(loss_fn, qcfg, spec, layout, hidden_flat,
                             batches, k_train, k_enc, flag, *, b: int,
                             mesh=None, taps: bool = False,
                             member_chunk=None, chunk_rows=None,
                             residual=None, basis_seed=None):
    """The entire client pipeline of one cohort tier-group as ONE jitted
    dispatch: unflatten the device-resident flat x-hat *inside* the jit, run
    the (vmapped) local-SGD scan, flatten the delta stack to (b, d), and
    quantize-pack it in the same computation.

    ``batches`` / ``k_train`` / ``k_enc`` are stacked on a leading b axis
    for b > 1 and unstacked for b == 1 (the sequential engine's shape —
    ``QAFeL.run_client`` calls this with b=1, so both engines share one
    compiled client path). ``flag`` is the runtime-True predicate behind the
    ``hard_boundary`` materialization points that pin bit-exactness with the
    pre-fusion multi-dispatch reference. ``mesh`` (a ("data",) sim mesh)
    shards the member dim b via shard_map — same wire layout, bit-identical
    outputs; see ``_cohort_step_fn``.

    Returns ``{"packed": (b, rows, 128*bits//8), "norms": (b, rows)}`` for
    qsgd, ``{"flat": (b, d)}`` otherwise (identity's flat rows ARE the wire
    payload; sparse kinds are encoded by the host from the flat rows).
    ``taps=True`` adds a ``"taps"`` entry — the (b, len(COHORT_TAP_NAMES))
    per-member in-dispatch metric rows — to the SAME dispatch.

    ``member_chunk`` / ``chunk_rows`` enable the LLM-scale chunked modes
    (member-chunked lax.scan / row-chunked streaming encode) — both
    bit-invisible; see ``_cohort_step_fn``. With a 2-D ("data","model")
    mesh the packed wire rows additionally shard over "model".

    A lowrank ``spec`` additionally takes the (b, d) error-feedback
    ``residual`` stack and the round's (2,) uint32 ``basis_seed`` (both
    TRACED — the seed is round state, tracing it keeps one compilation per
    config) and returns ``{"packed", "norms", "residual"}``: the rank-length
    wire pair plus each member's NEW residual (what the quantized subspace
    message failed to carry), which the caller stores back into client
    state. See ``core.qafel.client_update_flat``.
    """
    fn = _cohort_step_fn(loss_fn, qcfg, spec, layout, b, mesh, taps,
                         member_chunk, chunk_rows)
    rest = ()
    if spec.kind == "lowrank":
        if residual is None or basis_seed is None:
            raise ValueError("a lowrank cohort step needs the (b, d) "
                             "error-feedback residual stack and the round's "
                             "(2,) basis seed")
        rest = (residual, basis_seed)
    return fn(hidden_flat, batches, k_train, k_enc, flag, *rest)


@functools.partial(jax.jit,
                   static_argnames=("bits", "sbits", "n", "lr", "beta",
                                    "taps", "group"),
                   donate_argnums=(0, 1, 2))
def server_flush_step(x_flat, hidden_flat, momentum_flat, stack, norms,
                      weights, extra, key, flag, *,
                      bits: int, sbits, n: int, lr: float, beta,
                      taps: bool = False, group=None, lseeds=None):
    """The entire QAFeL buffer flush as ONE jitted, buffer-donated dispatch.

    Chains, without leaving the device or materializing any pytree:

      1. fused dequantize-accumulate of the K packed uploads (+ pre-scaled
         residual ``extra`` from tiered/sparse/identity arrivals),
      2. FedBuff server momentum + server update (``aggregate_update``),
      3. broadcast diff ``x^{t+1} - x-hat^t`` and its quantize-pack through
         the batched in-kernel-dither entry (``sbits``-bit qsgd) — or the
         raw diff itself when ``sbits is None`` (identity server quantizer),
      4. hidden-state apply of the *decoded broadcast bits* — the exact
         increment every client replica applies.

    ``x_flat`` / ``hidden_flat`` / ``momentum_flat`` are donated: the server
    state is updated in place on device. ``stack`` may be None (no packed
    qsgd uploads this window), ``beta`` None (no server momentum), ``key``
    (the broadcast's (2,) or (1, 2) uint32 key, reshaped in the jit) None
    (identity broadcast). ``weights``, ``key`` and the stacks may be host
    numpy arrays: they cross to the device with this dispatch, which is
    then the flush's only device op. ``flag`` is a runtime-True bool array
    backing the ``hard_boundary`` materialization points that pin
    bit-exactness with the eager multi-dispatch reference (and with the
    client replicas, which decode the broadcast bits in their own
    dispatch).

    Returns ``(x_new, hidden_new, momentum_new, (payload...))`` where the
    payload is ``(packed, norms)`` for a qsgd broadcast or ``(diff,)`` for
    identity. ``taps=True`` appends the in-dispatch metric tap vector
    (``repro.obs.taps.FLUSH_TAP_NAMES`` layout) as a fifth element — one
    extra f32 output of the SAME dispatch, never a new kernel entry; the
    tap math consumes only hard-boundary-pinned values, so the state/
    payload outputs stay bit-identical to a ``taps=False`` flush.

    A lowrank upload window passes the static sketch ``group`` plus the
    traced (K, 2) per-upload basis seeds ``lseeds``: ``stack`` / ``norms``
    are then the K RANK-length subspace wire pairs, which are dequantized
    in d_r space and expanded ONCE (``lowrank_window_delta``) inside this
    same dispatch; the expanded weighted delta rides the ``extra`` lane
    into the identical server-update / broadcast chain.
    """
    global SERVER_FLUSH_TRACES
    SERVER_FLUSH_TRACES += 1
    boundary = functools.partial(hard_boundary, flag)
    if group is not None:
        d_pad = rows_for(n) * BUCKET
        ld = lowrank_window_delta(
            stack, norms, weights, lseeds, boundary, bits=bits, group=group,
            y_width=d_pad // group, elem0=0, n_out=d_pad)[:n]
        extra = ld if extra is None else extra + ld
        stack = norms = None
    agg = _agg.aggregate_update(
        x_flat, momentum_flat, stack, norms, weights, extra,
        bits=bits, n=n, lr=lr, beta=beta, boundary=boundary,
        interpret=_interpret(), with_delta=taps)
    m_new, x_new = agg[0], agg[1]
    diff = boundary(x_new - hidden_flat)
    if sbits is None:  # identity server quantizer: the diff IS the wire payload
        h_new = hidden_flat + diff
        q, payload = diff, (diff,)
    else:
        bp3, bn3 = qsgd_quantize_batch(diff[None], key, sbits)
        bpacked, bnorms = boundary((bp3[0], bn3[0]))
        q = boundary(qsgd_dequantize(bpacked, bnorms, sbits, n))
        h_new = hidden_flat + q
        payload = (bpacked, bnorms)
    if not taps:
        return x_new, h_new, m_new, payload
    from repro.obs.taps import flush_tap_vector  # lazy: kernels stay obs-free
    tap_vec = flush_tap_vector(boundary, x_flat, x_new, agg[2], diff, q,
                               weights)
    return x_new, h_new, m_new, payload, tap_vec


@functools.partial(jax.jit,
                   static_argnames=("bits", "sbits", "lr", "beta", "mesh",
                                    "n", "taps", "chunk_rows", "group"),
                   donate_argnums=(0, 1, 2))
def server_flush_step_sharded(x_flat, hidden_flat, momentum_flat, stack, norms,
                              weights, extra, key, flag, *,
                              bits: int, sbits, lr: float, beta, mesh,
                              n=None, taps: bool = False, chunk_rows=None,
                              group=None, lseeds=None):
    """``server_flush_step`` on a flat state sharded over a ("data",) or
    2-D ("data","model") mesh.

    Same chain, one shard_map: every device owns one CONTIGUOUS segment of
    the flat vectors (``sharding.rules.flat_vector_spec`` — under a 2-D
    mesh the segments enumerate the flat axes data-major) and the matching
    row segment of the K-upload code/norm stacks, so the K-upload buffer is
    sharded along d rather than replicated. All state arrays are
    segment-aligned-padded to ``sharding.rules.flat_padded_len`` over
    ``sharding.rules.mesh_flat_extent`` segments (bucket rows padded to a
    segment-count multiple, zero tails — the caller pads the
    stack/norms/extra the same way), so:

    * the fused dequantize-accumulate, momentum and server update are
      segment-local elementwise math — bit-identical per element to the
      single-device dispatch;
    * the broadcast encode's bucket-norm math only ever sees whole
      128-element rows (segments are row-aligned — the BUCKET alignment
      rule), and its counter-hash dither is keyed by the GLOBAL element
      index via a per-segment row offset
      (``sharding.rules.flat_segment_index * local_rows``), so the emitted
      codes are the single-device wire bits exactly on every mesh shape;
    * the zero tails stay zero through every step (zero codes -> zero
      delta -> zero diff -> zero broadcast rows), and the caller slices
      the payload back to the true ``rows_for(n)`` wire rows — zero
      wire-format change.

    No model-axis collective exists on this path: every step is
    segment-local, and GSPMD only moves data if the CALLER hands in arrays
    laid out differently from the flat specs (taps excepted, below).

    ``chunk_rows`` additionally tiles the whole per-segment chain —
    dequant-accumulate, momentum/server update, broadcast encode AND the
    hidden apply of the decoded bits — over fixed-size row chunks inside
    one ``lax.scan``, so the f32 transients (dequantized sums, diff,
    decoded broadcast) never materialize beyond one chunk per device.
    Per-chunk math is the same per-element chain (the ascending-k
    accumulation order is per element, the dither keys on global indices),
    so chunking is bit-invisible; the tail chunk is zero-row-padded and
    sliced off.

    Donation keeps the sharded state update in place per device. ``stack``
    may be None (no packed qsgd uploads this window), ``beta`` None (no
    momentum), ``key`` (the (2,) or (1, 2) broadcast key, reshaped inside)
    None (identity broadcast). Returns the same
    ``(x_new, hidden_new, momentum_new, (payload...))`` contract with
    padded-length payload arrays.

    ``taps=True`` (requires the static TRUE length ``n``) appends the
    in-dispatch metric tap vector as a fifth element, sharding-invariant by
    construction: the per-segment delta/diff/decoded-broadcast vectors come
    back as extra sharded outputs of the SAME shard_map, are gathered to a
    replicated layout inside the same jit, sliced to the true ``n`` (a
    reduction over the zero-padded length has a different f32 tree-reduce
    grouping), and fed to the ONE shared ``flush_tap_vector`` — so every
    mesh size (model axis included) reduces the exact shapes the
    single-device dispatch reduces. The gather-to-replicated is the one
    collective taps add.

    A lowrank upload window (static ``group`` + traced (K, 2) ``lseeds``)
    keeps the RANK-length ``stack`` / ``norms`` REPLICATED instead of
    d-sharded — the subspace stack is d/group-sized, so replication is what
    makes the expansion segment-local (no cross-segment gather): every
    device dequantizes the full d_r stack (small) and expands ONLY its
    element segment via ``lowrank_window_delta``, whose counter-hash signs
    key on global element indices. The expanded per-segment delta rides the
    ``extra`` lane, so the whole-segment and chunked chains below are
    byte-for-byte the non-lowrank code. Requires the static true ``n`` (the
    segment padding past n must not receive expansion mass).
    """
    global SERVER_FLUSH_TRACES
    SERVER_FLUSH_TRACES += 1
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.common.compat import shard_map as _shard_map
    from repro.sharding.rules import (flat_axes, flat_norms_spec,
                                      flat_segment_index, flat_stack_spec,
                                      flat_vector_spec, mesh_flat_extent)

    if taps and n is None:
        raise ValueError("server_flush_step_sharded(taps=True) requires the "
                         "static true length n")
    if group is not None and n is None:
        raise ValueError("a lowrank sharded flush requires the static true "
                         "length n (segment padding must not be expanded)")
    nseg = mesh_flat_extent(mesh)
    # static host int: resolved OUTSIDE the jitted body (chunking is a
    # dispatch shape, never a traced value)
    chunk_c = None if chunk_rows is None else int(chunk_rows)

    def encode_decode(boundary, diff, seeds, row_off, rows_c):
        """Broadcast quantize-pack + decode of one row-aligned diff block
        (the whole segment, or one chunk of it) at global row ``row_off``."""
        bp, bn = _qsgd._quantize_pack_batch_block(
            diff.reshape(1, rows_c, BUCKET), seeds[:, 0], seeds[:, 1],
            row_off, sbits)
        bpacked, bnorms = boundary((bp[0], bn.reshape(rows_c)))
        q = boundary(_qsgd._unpack_dequantize_block(
            bpacked, bnorms.reshape(rows_c, 1), sbits).reshape(-1))
        return bpacked, bnorms, q

    def seg_body(x_l, h_l, m_l, stack_l, norms_l, w, extra_l, key_l, flag_l,
                 lseeds_l):
        boundary = functools.partial(hard_boundary, flag_l)
        n_l = x_l.shape[0]
        rows_l = n_l // BUCKET
        seg_row0 = flat_segment_index(mesh) * rows_l
        seeds = (None if sbits is None else
                 jnp.asarray(key_l).reshape(1, -1)[:, :2].astype(jnp.uint32))
        if group is not None:
            # lowrank window: expand the replicated subspace stack over this
            # segment's element range only, then hand the delta to the
            # untouched extra-lane chain (whole-segment or chunked alike)
            ld = lowrank_window_delta(
                stack_l, norms_l, w, lseeds_l, boundary, bits=bits,
                group=group, y_width=(n_l * nseg) // group,
                elem0=seg_row0 * BUCKET, n_out=n_l, n_true=n)
            extra_l = ld if extra_l is None else extra_l + ld
            stack_l = norms_l = None
        if chunk_c is None or chunk_c >= rows_l:
            agg = _agg.aggregate_update(
                x_l, m_l, stack_l, norms_l, w, extra_l,
                bits=bits, n=n_l, lr=lr, beta=beta, boundary=boundary,
                interpret=_interpret(), with_delta=taps)
            m_new, x_new = agg[0], agg[1]
            diff = boundary(x_new - h_l)
            if sbits is None:  # identity server quantizer
                q, h_new, payload = diff, h_l + diff, (diff,)
            else:
                bpacked, bnorms, q = encode_decode(
                    boundary, diff, seeds, seg_row0.astype(jnp.uint32),
                    rows_l)
                h_new, payload = h_l + q, (bpacked, bnorms)
            if not taps:
                return x_new, h_new, m_new, payload
            return x_new, h_new, m_new, payload, (agg[2], diff, q)

        # chunked streaming mode: one lax.scan tiles the entire chain over
        # c-row chunks; only chunk-sized f32 transients ever exist
        c = chunk_c
        nch = -(-rows_l // c)
        rpad = nch * c - rows_l
        cb = c * BUCKET

        def padv(v):  # (n_l,) f32 vector -> (nch, cb) chunk rows
            if rpad:
                v = jnp.concatenate([v, jnp.zeros((rpad * BUCKET,), v.dtype)])
            return v.reshape(nch, cb)

        xs = {"x": padv(x_l), "h": padv(h_l), "m": padv(m_l),
              "i": jnp.arange(nch, dtype=jnp.uint32)}
        if stack_l is not None:
            st, nr = stack_l, norms_l
            if rpad:
                k_ = st.shape[0]
                st = jnp.concatenate(
                    [st, jnp.zeros((k_, rpad, st.shape[2]), st.dtype)], axis=1)
                nr = jnp.concatenate(
                    [nr, jnp.zeros((k_, rpad), nr.dtype)], axis=1)
            xs["stack"] = st.reshape(st.shape[0], nch, c,
                                     st.shape[2]).transpose(1, 0, 2, 3)
            xs["norms"] = nr.reshape(nr.shape[0], nch, c).transpose(1, 0, 2)
        if extra_l is not None:
            xs["extra"] = padv(extra_l)

        def chunk_body(_, ch):
            agg = _agg.aggregate_update(
                ch["x"], ch["m"], ch.get("stack"), ch.get("norms"), w,
                ch.get("extra"), bits=bits, n=cb, lr=lr, beta=beta,
                boundary=boundary, interpret=_interpret(), with_delta=taps)
            m_new, x_new = agg[0], agg[1]
            diff = boundary(x_new - ch["h"])
            if sbits is None:
                q, h_new, payload = diff, ch["h"] + diff, (diff,)
            else:
                row_off = (seg_row0.astype(jnp.uint32)
                           + ch["i"] * jnp.uint32(c))
                bpacked, bnorms, q = encode_decode(boundary, diff, seeds,
                                                   row_off, c)
                h_new, payload = ch["h"] + q, (bpacked, bnorms)
            ys = (x_new, h_new, m_new, payload)
            if taps:
                ys = ys + ((agg[2], diff, q),)
            return None, ys

        _, ys = jax.lax.scan(chunk_body, None, xs)

        def unchunk(v):  # (nch, cb) -> (n_l,)
            return v.reshape(-1)[:n_l]

        x_new, h_new, m_new = unchunk(ys[0]), unchunk(ys[1]), unchunk(ys[2])
        if sbits is None:
            payload = (unchunk(ys[3][0]),)
        else:
            payload = (ys[3][0].reshape(nch * c, -1)[:rows_l],
                       ys[3][1].reshape(-1)[:rows_l])
        if not taps:
            return x_new, h_new, m_new, payload
        delta, diff, q = (unchunk(t) for t in ys[4])
        return x_new, h_new, m_new, payload, (delta, diff, q)

    ax = flat_axes(mesh)
    ax = ax[0] if len(ax) == 1 else ax
    vec, rep = flat_vector_spec(mesh), P()
    payload_specs = (vec,) if sbits is None else (P(ax, None), vec)
    out_specs = (vec, vec, vec, payload_specs)
    if taps:
        out_specs = out_specs + ((vec, vec, vec),)
    # lowrank stacks are rank-length and REPLICATED (the expansion is what
    # is segment-local); qsgd stacks shard their code rows along d
    stack_spec = rep if group is not None else flat_stack_spec(mesh)
    norms_spec = rep if group is not None else flat_norms_spec(mesh)
    sm = _shard_map(
        seg_body, mesh=mesh,
        in_specs=(vec, vec, vec,
                  None if stack is None else stack_spec,
                  None if norms is None else norms_spec,
                  None if weights is None else rep,
                  None if extra is None else vec,
                  None if key is None else rep, rep,
                  None if lseeds is None else rep),
        out_specs=out_specs, check_vma=False)
    out = sm(x_flat, hidden_flat, momentum_flat, stack, norms, weights,
             extra, key, flag, lseeds)
    if not taps:
        return out
    x_new, h_new, m_new, payload, (delta, diff, q) = out
    from repro.obs.taps import flush_tap_vector  # lazy: kernels stay obs-free
    replicated = NamedSharding(mesh, P())

    def gather(v):
        return jax.lax.with_sharding_constraint(v, replicated)[:n]

    boundary = functools.partial(hard_boundary, flag)
    tap_vec = flush_tap_vector(boundary, gather(x_flat), gather(x_new),
                               gather(delta), gather(diff), gather(q),
                               weights)
    return x_new, h_new, m_new, payload, tap_vec


# ---------------------------------------------------------------------------
# Fused population lifecycle step
# ---------------------------------------------------------------------------

POPULATION_ADVANCE_TRACES = 0


@functools.lru_cache(maxsize=32)
def _population_advance_fn(scenario, capacity: int, buckets: int,
                           bucket_width: int, admit: int, deliver: int,
                           queue_cap: int, host_draws: bool):
    """Compiled macro-step of the device-resident population engine.

    Cached per (scenario, shape) so every engine instance with the same
    statics shares ONE executable and the warm path never retraces. The
    population-state dict (arg 0) is donated: each step rewrites the
    lifecycle arrays in place. The out dict is packed in-kernel into two
    flat arrays (``population.pack_step_out``) so the host's per-step sync
    is exactly two transfers, not one per leaf — read it through
    ``population.PopStepOut``.
    """
    from repro.kernels import population as _pop
    body = _pop.make_advance_body(scenario, capacity, buckets, bucket_width,
                                  admit, deliver, queue_cap, host_draws)
    if host_draws:
        def step(pop, seeds, version, draws):
            global POPULATION_ADVANCE_TRACES
            POPULATION_ADVANCE_TRACES += 1
            new_pop, out = body(pop, seeds, version, draws)
            return new_pop, _pop.pack_step_out(out, admit, deliver)
    else:
        def step(pop, seeds, version):
            global POPULATION_ADVANCE_TRACES
            POPULATION_ADVANCE_TRACES += 1
            new_pop, out = body(pop, seeds, version)
            return new_pop, _pop.pack_step_out(out, admit, deliver)
    step.__name__ = "population_advance_step"
    return jax.jit(step, donate_argnums=(0,))


def population_advance(pop, seeds, version, draws=None, *, scenario,
                       capacity: int, buckets: int, bucket_width: int,
                       admit: int, deliver: int, queue_cap: int):
    """Advance the device-resident population by one macro step.

    ONE dispatch that either admits a cohort of ``admit`` clients (drawing
    their interarrivals / latencies / dropouts / tiers in-kernel from the
    counter-hash law, or consuming the host-fed ``draws`` dict
    ``{"inter", "dur", "drop", "tier"}`` of ``(admit,)`` arrays) or pops up
    to ``deliver`` completed deadlines in completion order. ``pop`` (from
    ``population.init_population``) is DONATED — rebind it to the first
    output. ``version`` is the current server model version (traced int,
    staleness = version - slot_version). Returns ``(new_pop, out)`` where
    ``out`` is the PACKED step output — two flat arrays (``{"f32", "i32"}``)
    carrying the admitted cohort / delivered batch plus population
    counters; sync with one ``jax.device_get`` (exactly two transfers) and
    read named fields through ``population.PopStepOut``.
    """
    jitted = _population_advance_fn(scenario, capacity, buckets, bucket_width,
                                    admit, deliver, queue_cap,
                                    draws is not None)
    if draws is None:
        return jitted(pop, seeds, version)
    return jitted(pop, seeds, version, draws)


# ---------------------------------------------------------------------------
# Compiled contracts: the invariants flcheck machine-checks per entry
# ---------------------------------------------------------------------------

# The base (non-fused) kernel entry points. On the fused paths these must
# NEVER be python-dispatched — the whole flush / cohort step is one call
# into one compiled executable. analysis_static.trace_guard patches exactly
# this list to enforce it.
KERNEL_ENTRY_POINTS = ("qsgd_quantize", "qsgd_quantize_batch",
                       "qsgd_dequantize", "buffer_aggregate")


def _flush_boundaries(*, sbits, beta, taps: bool = False, group=None,
                      lowrank_k: int = 0, **_) -> int:
    """hard_boundary call sites traced into one flush dispatch:
    the server-update products (lr*m always, beta*m with momentum — see
    ``core.qafel.server_apply_flat``), the broadcast diff, and for a qsgd
    broadcast the packed wire pair + the decoded hidden increment. Metric
    taps add exactly one more: the squares feeding the tap reductions are
    materialized behind a single shared boundary
    (``obs.taps._materialized_sq_sums``). A lowrank window (``group``)
    adds one per buffered upload: each ``w_k * expansion_k`` product is
    pinned before the ascending-k accumulation
    (``lowrank_window_delta``)."""
    return (2 + (1 if beta is not None else 0)
            + (2 if sbits is not None else 0) + (1 if taps else 0)
            + (lowrank_k if group is not None else 0))


def _cohort_boundaries(*, taps: bool = False, lowrank: bool = False,
                       **_) -> int:
    """One boundary on the client path: the flat delta stack between the
    local-SGD scan and the encode's norm math (``client_update_flat``).
    The in-jit unflatten needs none — slices are exact data movement.
    Metric taps add one: the shared squares boundary of the per-member tap
    reductions. A lowrank spec adds one: the residual-corrected stack and
    its sketch projection are pinned together (one cond for the pair)
    before the subspace encode's norm math."""
    return 1 + (1 if taps else 0) + (1 if lowrank else 0)


# Declarative contracts over the fused entries, consumed by
# ``repro.analysis_static.contracts`` (the compiled-HLO pass):
#
# * ``donate``      — positional indices that MUST establish input->output
#   aliasing in the compiled module (the in-place state update). An entry
#   with ``donate=()`` must establish NONE: the cohort step's hidden_flat
#   is read again by every later tier-group in the same window, so aliasing
#   it would corrupt the cohort path.
# * ``unused_without_momentum`` — donated args pruned from the compiled
#   module when ``beta is None`` (jit's keep_unused=False drops them, and a
#   pruned param cannot alias).
# * ``min_hard_boundaries(**cfg)`` — lower bound on ``conditional`` ops the
#   compiled module must retain: each ``hard_boundary`` is one lax.cond,
#   and a vanished conditional means XLA is free to FMA-contract across
#   what used to be an eager dispatch boundary (bit-exactness dies).
# * ``trace_counter`` — the module global counting (re)traces of the entry.
CONTRACTS = {
    "server_flush_step": {
        "donate": (0, 1, 2),
        "donated_args": ("x_flat", "hidden_flat", "momentum_flat"),
        "unused_without_momentum": (2,),
        "min_hard_boundaries": _flush_boundaries,
        "trace_counter": "SERVER_FLUSH_TRACES",
    },
    "server_flush_step_sharded": {
        "donate": (0, 1, 2),
        "donated_args": ("x_flat", "hidden_flat", "momentum_flat"),
        "unused_without_momentum": (2,),
        "min_hard_boundaries": _flush_boundaries,
        "trace_counter": "SERVER_FLUSH_TRACES",
    },
    "cohort_train_encode_step": {
        "donate": (),
        "donated_args": (),
        "unused_without_momentum": (),
        "min_hard_boundaries": _cohort_boundaries,
        "trace_counter": "COHORT_STEP_TRACES",
    },
    # The streaming chunk encode is DELIBERATELY one dispatch per chunk
    # (the host stages each chunk's wire bytes off-device) — so it is not
    # in KERNEL_ENTRY_POINTS and needs no hard boundary (nothing fuses
    # across its dispatch edge by construction). Its contract is the
    # aliasing-free single-compilation property: row_start is traced, so
    # one trace covers every chunk of a shape.
    "qsgd_quantize_chunk": {
        "donate": (),
        "donated_args": (),
        "unused_without_momentum": (),
        "min_hard_boundaries": lambda **_: 0,
        "trace_counter": "ENCODE_CHUNK_TRACES",
    },
    # The population macro step donates its whole state pytree (arg 0 =
    # every lifecycle array): the wheel, state codes, free stack and
    # counters are rewritten in place each step. It has no eager reference
    # path and no flag argument, so — like qsgd_quantize_chunk — it needs
    # no hard boundary; its contract is pytree donation aliasing plus the
    # single-dispatch / zero-retrace-across-macro-steps property checked by
    # ``contracts._check_population``.
    "population_advance": {
        "donate": (0,),
        "donated_args": ("pop",),
        "unused_without_momentum": (),
        "min_hard_boundaries": lambda **_: 0,
        "trace_counter": "POPULATION_ADVANCE_TRACES",
    },
}
