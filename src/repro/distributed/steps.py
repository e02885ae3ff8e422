"""Device-side programs: the QAFeL round, prefill and decode steps.

``qafel_round`` is the program lowered for the ``train_*`` input shapes: the
compute of one buffer flush (Algorithm 1 lines 5-16) on the production mesh.

* The K buffered clients are simulated **in time** (a ``lax.scan`` over K),
  each doing P local SGD steps from the shared hidden state with its own
  batch shard — exactly the paper's own FLSim methodology, on TPU. Client
  *asynchrony* (staleness, arrival order) is host-level control flow across
  rounds (repro.sim); per-client staleness weights enter the round as an
  input vector.
* The round runs on the SHARED flat substrate — the same entries the host
  simulators and the cohort engine compile: each in-graph client is one
  ``repro.core.qafel.client_update_flat`` call (flat x-hat in, REAL packed
  wire codes out), the accumulated delta is the dequantized wire bits, the
  server update is ``server_apply_flat`` on flat vectors, and the broadcast
  is ``qsgd_encode_flat2d`` + decode of its own bits — there is no private
  tree-based quantize/aggregate math here anymore.
* The full-precision model x and the shared x-hat enter/leave as trees (the
  launcher's sharded state contract); flatten/unflatten happens in-graph at
  the round boundary. Known tradeoff of the unification: the in-graph
  flatten concatenates leaves into one (d,) vector, so under a
  model-parallel GSPMD mesh the round's flat segment is not leaf-sharded
  the way the old tree scan was — fine for the host/reduced scales this
  round executes at (the pod-quantized variant below stays leafwise and
  sharding-preserving); a segment-sharded application of the flat entries
  (the server_flush_step_sharded layout) is the path to recover it.

The batch layout is (K, P, local_batch, ...): global_batch = K * P * local.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.compat import shard_map
from repro.common.tree import tree_axpy, tree_scale, tree_sub, tree_zeros_like
from repro.core.hidden_state import hidden_apply
from repro.core.qafel import (QAFeLConfig, client_update_flat, local_sgd_scan,
                              server_apply, server_apply_flat)
from repro.core.quantizers import (flatten_tree, lowrank_expand_flat2d,
                                   make_quantizer, qsgd_decode_flat,
                                   qsgd_encode_flat2d, qsgd_pack_lastdim,
                                   qsgd_unpack_lastdim)
from repro.models import transformer as T
from repro.models.config import ModelConfig


class RoundState(NamedTuple):
    x: Any  # full-precision server model
    hidden: Any  # shared hidden state x-hat
    momentum: Any
    t: jnp.ndarray  # server step


def init_round_state(cfg: ModelConfig, key) -> RoundState:
    params = T.init_params(cfg, key)
    return RoundState(x=params,
                      hidden=jax.tree.map(lambda a: a.copy(), params),
                      momentum=tree_zeros_like(params),
                      t=jnp.zeros((), jnp.int32))


def abstract_round_state(cfg: ModelConfig) -> RoundState:
    return jax.eval_shape(lambda: init_round_state(cfg, jax.random.PRNGKey(0)))


def make_qafel_round(cfg: ModelConfig, qcfg: QAFeLConfig, *,
                     remat: bool = True,
                     window_override: Optional[int] = None,
                     pod_quantized: bool = False, mesh=None,
                     podq_bits: int = 4, taps: bool = False,
                     chunk_rows: Optional[int] = None) -> Callable:
    """Build the jittable round function for a decoder architecture.

    ``chunk_rows`` streams both wire encodes (the per-client upload and the
    hidden-state broadcast) through fixed-size bucket-row chunks — the
    LLM-scale memory lever: full packed code buffers never materialize at
    once. The counter-hash / threefry dither is keyed by global element
    index, so any chunk size produces bit-identical codes (``None`` = one
    unchunked encode, the small-model default).

    ``taps=True`` adds the flush metric tap vector
    (``repro.obs.taps.FLUSH_TAP_NAMES`` layout) to the round's metrics dict
    under ``"taps"`` — the same in-dispatch scalars the host flush emits,
    computed in the same round dispatch (baseline round only; the
    pod-quantized variant keeps its leafwise metrics).

    pod_quantized=True (requires a mesh with a "pod" axis): hierarchical
    QAFeL — the K buffered clients are partitioned across pods; each pod
    aggregates its clients' (per-client Q_c-quantized) deltas in full
    precision over the cheap intra-pod ICI, then the pod-level partial sums
    cross the scarce pod interconnect as REAL packed qsgd codes (uint8 +
    per-bucket norms) via all_gather — the paper's upload compression
    applied to the one link where bytes actually hurt. The server update +
    hidden-state update then run replicated per pod on identical data.
    """
    cq = make_quantizer(qcfg.client_quantizer)
    sq = make_quantizer(qcfg.server_quantizer)
    if pod_quantized:
        return _make_podq_round(cfg, qcfg, cq, sq, remat=remat,
                                window_override=window_override, mesh=mesh,
                                bits=podq_bits)

    def loss(params, batch, key):
        del key
        l, _ = T.loss_fn(cfg, params, batch, remat=remat,
                         window_override=window_override)
        return l

    def decode_client_flat(out: dict, k_enc, d: int, seeds=None):
        """The flat delta the server accumulates: the client's own decoded
        wire bits (real packed codes for qsgd, raw rows for identity, exact
        sparse reconstruction for top_k/rand_k, dequantize-then-expand for
        lowrank — ``seeds`` is the round's sketch-basis seed pair). The
        round is one GSPMD program, so decoding is the kernels' block math
        in-graph (``qsgd_decode_flat``), like the encode."""
        if cq.spec.kind == "qsgd":
            return qsgd_decode_flat(out["packed"][0], out["norms"][0],
                                    cq.spec.bits, d)
        if cq.spec.kind == "lowrank":
            r = cq.spec.rank(d)
            y = qsgd_decode_flat(out["packed"][0], out["norms"][0],
                                 cq.spec.bits, r)
            return lowrank_expand_flat2d(y[None], seeds, cq.spec.group, d)[0]
        if cq.spec.kind == "identity":
            return out["flat"][0]
        return cq.qdq_flat(out["flat"][0], k_enc)

    def round_fn(state: RoundState, batch, weights, key):
        """batch leaves: (K, P, b, ...); weights: (K,) staleness weights."""
        from repro.kernels import ops as kops  # lazy: kernels stay optional

        k_clients, k_server = jax.random.split(key)
        hidden_flat, layout = flatten_tree(state.hidden)
        x_flat, _ = flatten_tree(state.x)
        m_flat, _ = flatten_tree(state.momentum)
        d = layout.total_size
        # hard_boundary's predicate must be a TRACED runtime value (a
        # constant lets XLA fold the cond and fuse across the boundary);
        # derive an always-True flag from a round input, like the host
        # path's self._flag jit argument
        flag = state.t >= jnp.int32(0)
        # lowrank: in-graph clients are fresh each round (no persistent
        # error-feedback state in this reduced round), so the residual is
        # zero and the basis seed rotates with the server step
        lseeds = None
        if cq.spec.kind == "lowrank":
            from repro.kernels import qsgd as _kq
            lseeds = _kq.basis_seeds(0, state.t)

        def client_body(carry, inp):
            buf, loss_sum = carry
            batches_kp, w_k, key_k = inp

            # the SAME fused client pipeline the host engines compile
            # (client_update_flat = shared local_sgd_scan + in-graph flatten
            # + wire encode), at b=1 with the threefry wire dither
            k_train, k_enc = jax.random.split(key_k)
            lkw = ({} if lseeds is None else
                   {"residual": jnp.zeros((1, d), jnp.float32),
                    "basis_seed": lseeds})
            out, losses = client_update_flat(
                loss, qcfg, cq.spec, layout, hidden_flat, batches_kp,
                k_train, k_enc, flag, b=1, with_loss=True,
                chunk_rows=chunk_rows, **lkw)
            buf = buf + w_k * decode_client_flat(out, k_enc, d, seeds=lseeds)
            return (buf, loss_sum + losses.mean()), None

        ckeys = jax.random.split(k_clients, qcfg.buffer_size)
        (buf, loss_sum), _ = jax.lax.scan(
            client_body,
            (jnp.zeros((d,), jnp.float32), jnp.zeros((), jnp.float32)),
            (batch, weights, ckeys))

        delta_bar = buf * (1.0 / qcfg.buffer_size)
        beta = qcfg.server_momentum if qcfg.server_momentum else None
        x_new, m_new = server_apply_flat(x_flat, m_flat, delta_bar,
                                         lr=qcfg.server_lr, beta=beta)
        # Hidden-state update: q = Q_s(x^{t+1} - x-hat) through the shared
        # flat wire encode; both sides apply the decoded bits.
        diff = x_new - hidden_flat
        if sq.spec.kind == "qsgd":
            bp, bn = qsgd_encode_flat2d(diff[None], k_server, sq.spec.bits,
                                        threefry=True, chunk_rows=chunk_rows)
            q = qsgd_decode_flat(bp[0], bn[0], sq.spec.bits, d)
        elif sq.spec.kind == "identity":
            q = diff
        else:
            q = sq.qdq_flat(diff, k_server)
        hidden_new = hidden_flat + q
        new_state = RoundState(x=layout.unflatten(x_new),
                               hidden=layout.unflatten(hidden_new),
                               momentum=layout.unflatten(m_new),
                               t=state.t + 1)
        metrics = {"loss": loss_sum / qcfg.buffer_size}
        if taps:
            from repro.obs.taps import flush_tap_vector
            boundary = functools.partial(kops.hard_boundary, flag)
            metrics["taps"] = flush_tap_vector(
                boundary, x_flat, x_new, delta_bar, diff, q, weights)
        return new_state, metrics

    return round_fn


def _make_podq_round(cfg: ModelConfig, qcfg: QAFeLConfig, cq, sq, *,
                     remat: bool, window_override: Optional[int], mesh,
                     bits: int) -> Callable:
    """Hierarchical quantized round (see make_qafel_round docstring).

    Batch layout: (K, P, b, ...) with the K (client) dim sharded over "pod"
    and b over "data". Returns the same (state, metrics) contract as the
    baseline round.
    """
    assert mesh is not None and "pod" in mesh.axis_names
    from jax.sharding import PartitionSpec as P
    from repro.kernels import ops as kops

    n_pods = int(mesh.shape["pod"])
    assert qcfg.buffer_size % n_pods == 0
    kpp = qcfg.buffer_size // n_pods

    def loss(params, batch, key):
        del key
        l, _ = T.loss_fn(cfg, params, batch, remat=remat,
                         window_override=window_override)
        return l

    BUCKET = 128
    per_byte = 8 // bits

    def xchg_leaf(leaf, key):
        """Cross-pod exchange of one pod-partial tensor as packed codes.

        Sharding-preserving: quantization is elementwise and packing stays
        within the (possibly TP-sharded) last dim, so no reshape ever crosses
        a sharded axis and the auto ("data"/"model") layout is untouched —
        only the all_gather crosses pods, carrying uint8 codes + fp32 bucket
        norms (~bits/8 + 32/BUCKET bytes per param vs 2-4 raw). The pack /
        unpack math is the shared last-dim wire math in
        ``repro.core.quantizers`` (``qsgd_pack_lastdim``/``_unpack_``), not
        private to this module. Tiny 1D leaves go raw (savings negligible,
        padding awkward)."""
        if leaf.ndim < 2 or leaf.shape[-1] % (BUCKET * per_byte):
            g = jax.lax.all_gather(leaf.astype(jnp.float32), "pod")
            return jnp.sum(g, axis=0).astype(leaf.dtype)
        packed, norms = qsgd_pack_lastdim(leaf, key, bits, bucket=BUCKET)

        pk = jax.lax.all_gather(packed, "pod")  # uint8 across the pod link
        nm = jax.lax.all_gather(norms, "pod")

        vals = qsgd_unpack_lastdim(pk, nm, bits, bucket=BUCKET)
        tot = jnp.sum(vals, axis=0).reshape(leaf.shape)
        return tot.astype(leaf.dtype)

    def pod_body(x, hidden, momentum, t, batch, weights, key_data):
        # manual over "pod": batch (kpp, P, b, ...) per pod; weights (kpp,).
        pod = jax.lax.axis_index("pod")
        base_key = jax.random.wrap_key_data(key_data)
        pod_key = jax.random.fold_in(base_key, pod)  # pod-varying client keys
        k_server = jax.random.fold_in(base_key, 10_007)  # pod-INvariant

        def client_body(carry, inp):
            buf, loss_sum = carry
            batches_kp, w_k, key_k = inp

            pkeys = jax.random.split(key_k, qcfg.local_steps + 1)
            y_final, losses = local_sgd_scan(
                loss, qcfg.client_lr, hidden, batches_kp, pkeys[:-1],
                with_loss=True)
            delta = tree_sub(y_final, hidden)
            delta_q = cq.qdq(delta, pkeys[-1])  # per-client Q_c (Algorithm 2)
            buf = tree_axpy(w_k, delta_q, buf)
            return (buf, loss_sum + losses.mean()), None

        ckeys = jax.random.split(pod_key, kpp)
        (buf_pod, loss_pod), _ = jax.lax.scan(
            client_body, (tree_zeros_like(x), jnp.zeros((), jnp.float32)),
            (batch, weights, ckeys))

        # cross-pod: pod partial-sums travel as packed 4-bit codes
        leaves, treedef = jax.tree.flatten(buf_pod)
        xkeys = jax.random.split(k_server, len(leaves) + 1)
        buf_tot = jax.tree.unflatten(
            treedef, [xchg_leaf(l, k) for l, k in zip(leaves, xkeys[:-1])])

        delta_bar = tree_scale(buf_tot, 1.0 / qcfg.buffer_size)
        x_new, m_new = server_apply(qcfg, x, momentum, delta_bar)
        q = sq.qdq(tree_sub(x_new, hidden), xkeys[-1])
        hidden_new = hidden_apply(hidden, q)
        loss_mean = jax.lax.pmean(loss_pod, "pod") / kpp
        return x_new, hidden_new, m_new, t + 1, {"loss": loss_mean}

    rep = P()

    def batch_spec(leaf):
        return P(*(["pod"] + [None] * (leaf.ndim - 1)))

    def round_fn(state: RoundState, batch, weights, key):
        key_data = jax.random.key_data(key)
        b_specs = jax.tree.map(lambda l: batch_spec(l), batch)
        sm = shard_map(
            pod_body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: rep, state.x),
                      jax.tree.map(lambda _: rep, state.hidden),
                      jax.tree.map(lambda _: rep, state.momentum),
                      rep, b_specs, P("pod"), rep),
            out_specs=(jax.tree.map(lambda _: rep, state.x),
                       jax.tree.map(lambda _: rep, state.hidden),
                       jax.tree.map(lambda _: rep, state.momentum),
                       rep, {"loss": rep}),
            axis_names={"pod"}, check_vma=False)
        x_new, hidden_new, m_new, t_new, metrics = sm(
            state.x, state.hidden, state.momentum, state.t, batch, weights,
            key_data)
        return RoundState(x=x_new, hidden=hidden_new, momentum=m_new,
                          t=t_new), metrics

    return round_fn


def make_prefill_step(cfg: ModelConfig, *, max_len: Optional[int] = None,
                      window_override: Optional[int] = None) -> Callable:
    def prefill_step(params, inputs):
        return T.prefill(cfg, params, inputs, max_len=max_len,
                         window_override=window_override)
    return prefill_step


def make_decode_step(cfg: ModelConfig, *,
                     window_override: Optional[int] = None) -> Callable:
    def decode_step(params, cache, inputs, pos):
        return T.decode_step(cfg, params, cache, inputs, pos,
                             window_override=window_override)
    return decode_step
