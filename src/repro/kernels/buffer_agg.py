"""Fused dequantize + weighted-accumulate of the QAFeL server buffer.

Algorithm 1 (QAFeL-server) lines 11-12 dequantize K buffered client messages
and fold them into the model update. Done naively that is K separate
dequantize passes plus K adds — (2K+1) HBM round-trips over a model-sized
tensor. This kernel fuses the whole reduction: for each (BLOCK_ROWS, 128)
tile of the model it streams the K packed code blocks (+ per-row bucket
norms) through VMEM, dequantizes each in registers, and accumulates

    out = sum_k  w_k * dequant(packed_k, norms_k)

in one pass (w_k carries both the 1/K mean and FedBuff's staleness
down-weighting 1/sqrt(1+tau_k)). One HBM read of K * bits/32 of the f32
footprint + one write — the minimum traffic the server step can do.

Off-TPU the pallas interpreter's per-cell block copies dominate the
memory-bound body, so ``buffer_aggregate`` routes the SAME reduction as one
XLA-fused computation (identical fori_loop accumulation order — bit-exact
vs the interpreted kernel; ``force_pallas=True`` pins it in tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.qsgd import BLOCK_ROWS, LANES, _decode_codes


def _weighted_dequant_sum(w, p, n3, *, bits: int, k: int, rows: int):
    """Shared reduction body: sum_k w[k,0] * dequant(p[k], n3[k]) -> (rows,
    128) f32. ``w``/``p``/``n3`` may be arrays or pallas refs (indexed per
    k); accumulation is an ascending-k fori_loop on both routes."""
    s = (1 << (bits - 1)) - 1

    def body(i, acc):
        sign, mag = _decode_codes(p[i], bits)  # p[i]: (rows, LANES/per_byte)
        scale = w[i, 0] * n3[i] / float(s)  # (rows, 1): weight * norms / s
        return acc + sign * mag * scale

    return jax.lax.fori_loop(0, k, body,
                             jnp.zeros((rows, LANES), jnp.float32))


def _buffer_agg_kernel(w_ref, p_ref, n_ref, out_ref, *, bits: int, k: int):
    """w (K, 1); p (K, R, 128/per_byte) uint8; n (K, R, 1) -> out f32 (R, 128)."""
    out_ref[...] = _weighted_dequant_sum(w_ref, p_ref, n_ref, bits=bits, k=k,
                                         rows=p_ref.shape[1])


@functools.partial(jax.jit, static_argnames=("bits", "interpret", "force_pallas"))
def buffer_aggregate(packed_stack: jnp.ndarray, norms: jnp.ndarray,
                     weights: jnp.ndarray, bits: int,
                     interpret: bool = True,
                     force_pallas: bool = False) -> jnp.ndarray:
    """Fused weighted dequantized sum of K packed messages.

    packed_stack: (K, rows, 128*bits//8) uint8 wire-layout codes
    norms:        (K, rows) f32 per-row bucket norms
    weights:      (K,) f32 aggregation weights (mean + staleness scaling)
    returns:      (rows, 128) f32 == sum_k weights[k] * dequant(msg_k)

    The pallas route needs rows padded to a BLOCK_ROWS multiple (done here,
    zero rows are numerically inert and sliced off); the fused off-TPU
    route takes wire rows as they come.
    """
    k, rows, in_lanes = packed_stack.shape
    per_byte = 8 // bits
    assert in_lanes == LANES // per_byte, packed_stack.shape
    w = weights.reshape(k, 1).astype(jnp.float32)
    n3 = norms.reshape(k, rows, 1).astype(jnp.float32)
    if interpret and not force_pallas:
        return _weighted_dequant_sum(w, packed_stack, n3, bits=bits, k=k,
                                     rows=rows)
    rpad = (-rows) % BLOCK_ROWS
    if rpad:
        packed_stack = jnp.concatenate(
            [packed_stack, jnp.zeros((k, rpad, in_lanes), jnp.uint8)], axis=1)
        n3 = jnp.concatenate(
            [n3, jnp.zeros((k, rpad, 1), jnp.float32)], axis=1)
    grid = ((rows + rpad) // BLOCK_ROWS,)
    out = pl.pallas_call(
        functools.partial(_buffer_agg_kernel, bits=bits, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((k, BLOCK_ROWS, in_lanes), lambda i: (0, i, 0)),
            pl.BlockSpec((k, BLOCK_ROWS, 1), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + rpad, LANES), jnp.float32),
        interpret=interpret,
    )(w, packed_stack, n3)
    return out[:rows]


# ---------------------------------------------------------------------------
# Fused aggregate -> server update (the first half of the one-dispatch flush)
# ---------------------------------------------------------------------------


def aggregate_update(x_flat, m_flat, stack, norms, weights, extra, *,
                     bits, n: int, lr, beta, boundary=None,
                     interpret: bool = True, with_delta: bool = False):
    """Chain the buffer aggregation into the FedBuff server update without
    leaving the device: Delta-bar = sum_k w_k dequant(msg_k) (+ pre-scaled
    residual), m <- beta m + Delta-bar, x <- x + eta_g m.

    Designed to be traced *inside* the single jitted ``server_flush_step``
    (``repro.kernels.ops``). The server update itself is the shared
    ``repro.core.qafel.server_apply_flat``; ``boundary`` (see
    ``ops.hard_boundary``) pins the intermediate scalar products so XLA
    cannot FMA-contract them and drift bit-wise from the eager reference.

    Returns ``(m_new, x_new)``, or ``(m_new, x_new, delta)`` with
    ``with_delta=True`` — the aggregated Delta-bar is what the flush's
    in-dispatch metric taps reduce over, and recovering it from the
    momentum recurrence would not be f32-exact.
    """
    from repro.core.qafel import server_apply_flat  # lazy: kernels stay core-free

    if stack is not None:
        delta = buffer_aggregate(jnp.asarray(stack), jnp.asarray(norms),
                                 weights, bits,
                                 interpret=interpret).reshape(-1)[:n]
        if extra is not None:
            delta = extra + delta
    else:
        delta = extra
    x_new, m_new = server_apply_flat(x_flat, m_flat, delta,
                                     lr=lr, beta=beta, boundary=boundary)
    if with_delta:
        return m_new, x_new, delta
    return m_new, x_new
