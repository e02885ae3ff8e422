"""Pure-jnp oracles for the Pallas kernels (bit-exact reference semantics).

Every kernel in this package must produce *identical* outputs to its oracle
given the same inputs (quantization randomness enters only through the
explicit uniform array, so both paths are deterministic). The test suite
sweeps shapes/dtypes/bits and asserts exact equality on codes and allclose
on dequantized floats.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.qsgd import LANES


def quantize_pack(x2d: jnp.ndarray, u2d: jnp.ndarray, bits: int):
    """Oracle for qsgd.qsgd_quantize_pack: returns (packed, norms (rows, 1))."""
    s = (1 << (bits - 1)) - 1
    per_byte = 8 // bits
    x = x2d.astype(jnp.float32)
    # the kernel's summation order (lane l + lane l+w, w = 64 ... 1): an
    # order left to the compiler differs by an ulp between routes
    sq = x * x
    while sq.shape[1] > 1:
        half = sq.shape[1] // 2
        sq = sq[:, :half] + sq[:, half:]
    norm = jnp.sqrt(sq)
    inv = jnp.where(norm > 0.0, s / jnp.maximum(norm, 1e-30), 0.0)
    level = jnp.abs(x) * inv
    low = jnp.floor(level)
    xi = low + (u2d < (level - low)).astype(jnp.float32)
    xi = jnp.minimum(xi, float(s)).astype(jnp.uint32)
    code = ((x < 0.0).astype(jnp.uint32) << (bits - 1)) | xi
    r = code.shape[0]
    grouped = code.reshape(r, LANES // per_byte, per_byte)
    shifts = (jnp.arange(per_byte, dtype=jnp.uint32) * bits).reshape(1, 1, per_byte)
    return jnp.sum(grouped << shifts, axis=-1).astype(jnp.uint8), norm


def unpack_dequantize(packed: jnp.ndarray, norms: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Oracle for qsgd.qsgd_unpack_dequantize."""
    s = (1 << (bits - 1)) - 1
    per_byte = 8 // bits
    code_mask = jnp.uint32((1 << bits) - 1)
    p = packed.astype(jnp.uint32)
    r = p.shape[0]
    shifts = (jnp.arange(per_byte, dtype=jnp.uint32) * bits).reshape(1, 1, per_byte)
    codes = ((p[:, :, None] >> shifts) & code_mask).reshape(r, LANES)
    mag = (codes & jnp.uint32(s)).astype(jnp.float32)
    sign = 1.0 - 2.0 * ((codes >> (bits - 1)) & 1).astype(jnp.float32)
    return sign * mag * (norms.reshape(r, 1).astype(jnp.float32) / float(s))


def buffer_aggregate(packed_stack: jnp.ndarray, norms: jnp.ndarray,
                     weights: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Oracle for buffer_agg.buffer_aggregate. norms: (K, rows)."""
    out = jnp.zeros((packed_stack.shape[1], LANES), jnp.float32)
    for i in range(packed_stack.shape[0]):
        out = out + weights[i].astype(jnp.float32) * unpack_dequantize(
            packed_stack[i], norms[i], bits)
    return out
