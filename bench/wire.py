"""Plain reference of QAFeL's qsgd wire format, written from its definition.

A message of n f32 values is cut into rows of 128 (the last row zero
padded). Each row carries its L2 norm and one code per value: the sign bit
above ``bits - 1`` magnitude bits holding the level ``xi = floor(l) +
[u < l - floor(l)]`` of ``l = |x| s / norm``, ``s = 2**(bits - 1) - 1``,
where ``u`` is the dither of the value's global index
``i = row * 128 + lane``:

    h = fmix32(i * 0x9E3779B9 + seed0); h = fmix32(h ^ seed1)
    u = (h >> 8) / 2**24

(``fmix32`` is murmur3's 32-bit finalizer). Codes are packed little-endian,
``8 // bits`` to a byte. A decoded value is ``sign * xi * norm / s``.

Everything here is straightforward ``jax.numpy``; ``dtype`` is the
precision the arithmetic runs in (float32, or bfloat16 for the control).
Nothing is imported from the system under test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128


def rows_for(n: int) -> int:
    return -(-n // LANES)


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def dither(seed0, seed1, idx):
    """The dither law: uint32 seeds and global element indices -> [0, 1)."""
    h = _fmix32(idx * jnp.uint32(0x9E3779B9) + seed0)
    h = _fmix32(h ^ seed1)
    return (h >> 8).astype(jnp.float32) / jnp.float32(1 << 24)


@functools.partial(jax.jit, static_argnames=("bits", "dtype"))
def encode_rows(x2d, seed, row0, *, bits: int, dtype=jnp.float32):
    """(R, 128) values whose first row is global row ``row0`` -> (codes
    int32 (R, 128) with the sign bit on top, norms f32 (R,)). ``seed`` is
    the message's (2,) uint32 dither seed."""
    s = (1 << (bits - 1)) - 1
    x = x2d.astype(dtype)
    norm = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    rows = x.shape[0]
    idx = ((jnp.arange(rows, dtype=jnp.uint32)[:, None]
            + jnp.asarray(row0).astype(jnp.uint32)) * jnp.uint32(LANES)
           + jnp.arange(LANES, dtype=jnp.uint32)[None, :])
    seed = jnp.asarray(seed).astype(jnp.uint32)
    u = dither(seed[0], seed[1], idx).astype(dtype)
    level = jnp.where(norm > 0, jnp.abs(x) * (s / jnp.where(norm > 0, norm, 1)),
                      0)
    low = jnp.floor(level)
    xi = jnp.minimum(low + (u < level - low), s).astype(jnp.int32)
    code = xi + jnp.where(x < 0, 1 << (bits - 1), 0)
    return code, norm[:, 0].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "dtype"))
def decode_rows(codes, norms, *, bits: int, dtype=jnp.float32):
    """Codes (R, 128) and norms (R,) -> values (R, 128) in ``dtype``."""
    s = (1 << (bits - 1)) - 1
    mag = (codes & s).astype(dtype)
    sign = jnp.where((codes >> (bits - 1)) & 1, -1, 1).astype(dtype)
    return sign * mag * (norms.astype(dtype)[:, None] / s)


def pack(codes: np.ndarray, bits: int) -> np.ndarray:
    """Codes (R, 128) -> packed uint8 (R, 128 * bits // 8), little-endian
    within each byte."""
    per_byte = 8 // bits
    c = np.asarray(codes, np.int32).reshape(codes.shape[0], -1, per_byte)
    shifts = bits * np.arange(per_byte)
    return (c << shifts).sum(axis=2).astype(np.uint8)
