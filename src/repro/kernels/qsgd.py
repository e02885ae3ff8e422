"""Pallas kernels for stochastic n-bit qsgd quantization with bit-packing.

Wire format (per flat message of n elements, padded to LANE*SUBLANE tiles):

* one fp32 L2 norm per 128-element bucket (= one VMEM lane row; bucketing is
  both Alistarh et al.'s practical QSGD and the reason the hidden-state loop
  contracts — see core/quantizers.py),
* one n-bit code per element: 1 sign bit (MSB of the code) + (bits-1)
  magnitude bits holding the stochastically rounded level xi in [0, s],
  s = 2**(bits-1) - 1,
* codes packed little-endian into uint8 lanes, ``8 // bits`` codes per byte
  (bits must divide 8: 2, 4 or 8).

Layout: the flat vector is reshaped to (rows, 128) and tiled with
BlockSpec((BLOCK_ROWS, 128)) so each grid step streams one VMEM-resident
block: read x + uniform noise, emit packed codes + carry per-row norms.
Arithmetic intensity is O(1) so the kernel is HBM-bandwidth-bound by
design — the point is to touch each element exactly once.

Packing ``8 // bits`` lanes into one byte is a lane-regrouping, which
Mosaic cannot express as a reshape; both directions are therefore one small
matmul against a 0/power-of-two placement matrix (``_pack_codes`` /
``_unpack_codes``). Every operand is an integer below 256, exact in bf16,
and the sums accumulate in f32, so the packed bytes are the little-endian
layout above bit for bit. Unsigned<->float conversions go through int32,
the one integer type Mosaic converts to and from f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128  # bucket size: one norm per 128-element row
BLOCK_ROWS = 256  # (256, 128) fp32 block = 128 KiB in VMEM

# ---------------------------------------------------------------------------
# Quantize + pack
# ---------------------------------------------------------------------------


def _pack_codes(code, bits: int):
    """f32 integer codes (R, 128) in [0, 2**bits) -> packed uint8
    (R, 128/per_byte): lane ``l`` lands in byte ``l // per_byte`` at bit
    ``bits * (l % per_byte)``. One matmul against the placement matrix
    ``P[l, j] = 2**(bits * (l % per_byte)) * [l // per_byte == j]``, built
    from iotas because Pallas refuses captured constants."""
    per_byte = 8 // bits
    if per_byte > 1:
        log_pb = per_byte.bit_length() - 1
        shape = (LANES, LANES // per_byte)
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        weight = jnp.left_shift(1, bits * (lane & (per_byte - 1)))
        place = jnp.where((lane >> log_pb) == col, weight, 0)
        code = jnp.dot(code.astype(jnp.bfloat16), place.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return code.astype(jnp.int32).astype(jnp.uint8)


def _unpack_codes(p, bits: int):
    """packed uint8 (R, 128/per_byte) -> int32 codes (R, 128), the inverse
    of ``_pack_codes``: one matmul against the 0/1 expansion matrix copies
    byte ``l // per_byte`` into lane ``l``, then a per-lane shift and mask
    pick the lane's code."""
    per_byte = 8 // bits
    byte = p.astype(jnp.int32)
    if per_byte == 1:
        return byte
    log_pb = per_byte.bit_length() - 1
    shape = (LANES // per_byte, LANES)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    expand = ((lane >> log_pb) == row).astype(jnp.bfloat16)
    byte = jnp.dot(byte.astype(jnp.bfloat16), expand,
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    shift = bits * (jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
                    & (per_byte - 1))
    return (byte >> shift) & ((1 << bits) - 1)


def _decode_codes(p, bits: int):
    """packed uint8 (R, 128/per_byte) -> (sign, magnitude) f32 (R, 128)."""
    s = (1 << (bits - 1)) - 1
    codes = _unpack_codes(p, bits)
    mag = (codes & s).astype(jnp.float32)
    sign = 1.0 - 2.0 * ((codes >> (bits - 1)) & 1).astype(jnp.float32)
    return sign, mag


def tree_sum(v):
    """Sum over the last axis (a power-of-two width), keeping it as size 1,
    in one fixed order: a halving tree (element ``l`` + element ``l + w``
    for w = width/2, ..., 1). ``jnp.sum`` leaves the order to the compiler,
    which picks it per shape and backend (Mosaic, XLA on a block, XLA on a
    whole message), and a different order moves the sum by an ulp; adds of
    slices are never reassociated, so every route agrees bit for bit."""
    w = v.shape[-1]
    assert w & (w - 1) == 0, w
    while w > 1:
        w //= 2
        v = v[..., :w] + v[..., w:]
    return v


def _quantize_pack_block(x, u, bits: int):
    """Shared block math: f32 (R, 128) + uniforms -> (packed uint8
    (R, 128/per_byte), norms f32 (R, 1)). Used by both the single-message
    and the batched kernel so the two are bit-identical per row."""
    s = (1 << (bits - 1)) - 1
    x = x.astype(jnp.float32)
    norm = jnp.sqrt(tree_sum(x * x))  # (R, 1)
    inv = jnp.where(norm > 0.0, s / jnp.maximum(norm, 1e-30), 0.0)

    level = jnp.abs(x) * inv
    low = jnp.floor(level)
    xi = low + (u < (level - low)).astype(jnp.float32)  # stochastic rounding
    xi = jnp.minimum(xi, float(s))
    # n-bit code: the sign bit (MSB) above the magnitude, as an exact f32
    code = xi + jnp.where(x < 0.0, float(1 << (bits - 1)), 0.0)
    return _pack_codes(code, bits), norm


def _quantize_pack_kernel(x_ref, u_ref, out_ref, norm_ref, *, bits: int):
    """One block: f32 (R, 128) + uniforms -> packed uint8 (R, 128/per_byte)
    plus per-row norms (R, 1)."""
    packed, norm = _quantize_pack_block(x_ref[...], u_ref[...], bits)
    out_ref[...] = packed
    norm_ref[...] = norm


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def qsgd_quantize_pack(x2d: jnp.ndarray, u2d: jnp.ndarray,
                       bits: int, interpret: bool = True):
    """Quantize+pack a (rows, 128) f32 array; rows % BLOCK_ROWS == 0.

    Returns (packed uint8 (rows, 128*bits//8), norms f32 (rows, 1)).
    """
    rows = x2d.shape[0]
    assert x2d.shape[1] == LANES and rows % BLOCK_ROWS == 0, x2d.shape
    assert 8 % bits == 0, bits
    per_byte = 8 // bits
    out_lanes = LANES // per_byte
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        functools.partial(_quantize_pack_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_ROWS, out_lanes), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, out_lanes), jnp.uint8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2d, u2d)


# ---------------------------------------------------------------------------
# Batched quantize + pack (one dispatch for a whole client cohort)
# ---------------------------------------------------------------------------


# messages per grid cell of the batched kernel: an (8, 256, 128) f32 block is
# 1 MiB in VMEM (x + outputs ~ 1.3 MiB, well under budget), and 8x fewer
# grid steps than one-message-per-cell.
BATCH_TILE = 8


def _hash_uniform(seed0, seed1, idx):
    """Counter-based dither: uint32 (seed0, seed1, element index) -> f32 in
    [0, 1). Two murmur3-style finalizer rounds (xorshift-multiply avalanche)
    keyed by the per-message seed — the in-kernel analogue of
    ``pltpu.prng_random_bits``, so the batched kernel needs no host-generated
    uniforms (no threefry precompute, half the HBM reads). Plain uint32
    jnp arithmetic: identical on the pallas and fused-XLA routes.
    """
    def fmix32(x):
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0xC2B2AE35)
        x = x ^ (x >> 16)
        return x

    x = fmix32(idx * jnp.uint32(0x9E3779B9) + seed0)
    x = fmix32(x ^ seed1)
    # top 24 bits -> [0, 1): exactly representable in f32
    return ((x >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def _quantize_pack_batch_block(x, seed0, seed1, row_offset, bits: int):
    """Shared batched block math: f32 (BT, R, 128) + per-message seeds (BT,)
    -> (packed uint8 (BT, R, 128/per_byte), norms (BT, R, 1)). Dither is
    generated in-kernel from the global element index, so a message's codes
    do not depend on how the batch is tiled."""
    bt, r, lanes = x.shape
    lane = jax.lax.broadcasted_iota(jnp.uint32, (bt, r, lanes), 2)
    row = jax.lax.broadcasted_iota(jnp.uint32, (bt, r, lanes), 1)
    idx = (row + jnp.uint32(row_offset)) * jnp.uint32(lanes) + lane
    u = _hash_uniform(seed0.reshape(bt, 1, 1).astype(jnp.uint32),
                      seed1.reshape(bt, 1, 1).astype(jnp.uint32), idx)
    packed, norm = _quantize_pack_block(x.reshape(bt * r, lanes),
                                        u.reshape(bt * r, lanes), bits)
    return packed.reshape(bt, r, -1), norm.reshape(bt, r, 1)


def _quantize_pack_batch_kernel(x_ref, seed_ref, out_ref, norm_ref, *, bits: int):
    """One (message-tile, row-block) grid cell; seed_ref is (BT, 2) uint32."""
    row_offset = pl.program_id(1) * BLOCK_ROWS
    packed, norm = _quantize_pack_batch_block(
        x_ref[...], seed_ref[:, 0], seed_ref[:, 1], row_offset, bits)
    out_ref[...] = packed
    norm_ref[...] = norm


@functools.partial(jax.jit, static_argnames=("bits", "interpret", "force_pallas"))
def qsgd_quantize_pack_batch(x3d: jnp.ndarray, seeds: jnp.ndarray,
                             bits: int, interpret: bool = True,
                             force_pallas: bool = False):
    """Quantize+pack a (B, rows, 128) stack of messages in ONE dispatch.

    ``seeds`` is (B, 2) uint32 — one dither seed pair per message; the
    stochastic-rounding noise is generated *in-kernel* by a counter-based
    hash (``_hash_uniform``), so unlike the single-message kernel there is
    no host-side threefry pass and no uniforms input (half the HBM reads).

    On TPU (``interpret=False``) this is one pallas launch with grid
    (B / BATCH_TILE, rows / BLOCK_ROWS), each cell streaming a BATCH_TILE-
    message tile through VMEM. Off-TPU the interpreter's per-cell block
    copies dominate, so the batched entry routes the SAME block math as one
    XLA-fused computation over the whole stack — bit-identical to the
    pallas route by construction (``force_pallas=True`` exercises the
    interpreted pallas path; a test pins the equality). Returns (packed
    uint8 (B, rows, 128*bits//8), norms f32 (B, rows, 1)).
    """
    b, rows, lanes = x3d.shape
    assert lanes == LANES, x3d.shape
    assert seeds.shape == (b, 2), seeds.shape
    assert 8 % bits == 0, bits
    per_byte = 8 // bits
    out_lanes = LANES // per_byte
    if interpret and not force_pallas:
        packed, norm = _quantize_pack_batch_block(
            x3d, seeds[:, 0], seeds[:, 1], 0, bits)
        return packed, norm
    # pad to full kernel tiles: batch to a BATCH_TILE multiple with zero
    # messages, rows to a BLOCK_ROWS multiple with zero rows (zero codes,
    # numerically inert; sliced off below)
    rpad = (-rows) % BLOCK_ROWS
    if rpad:
        x3d = jnp.concatenate(
            [x3d, jnp.zeros((b, rpad, lanes), x3d.dtype)], axis=1)
    bpad = (-b) % BATCH_TILE
    if bpad:
        x3d = jnp.concatenate(
            [x3d, jnp.zeros((bpad, rows + rpad, lanes), x3d.dtype)])
        seeds = jnp.concatenate(
            [seeds, jnp.zeros((bpad, 2), seeds.dtype)])
    grid = ((b + bpad) // BATCH_TILE, (rows + rpad) // BLOCK_ROWS)
    packed, norms = pl.pallas_call(
        functools.partial(_quantize_pack_batch_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BATCH_TILE, BLOCK_ROWS, LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((BATCH_TILE, 2), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BATCH_TILE, BLOCK_ROWS, out_lanes),
                         lambda i, j: (i, j, 0)),
            pl.BlockSpec((BATCH_TILE, BLOCK_ROWS, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b + bpad, rows + rpad, out_lanes), jnp.uint8),
            jax.ShapeDtypeStruct((b + bpad, rows + rpad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x3d, seeds)
    return packed[:b, :rows], norms[:b, :rows]


# ---------------------------------------------------------------------------
# Low-rank sketch basis (counter-hash Rademacher signs)
# ---------------------------------------------------------------------------

# a distinct salt channel so basis signs never correlate with the dither
# stream (_hash_uniform) even under equal seeds
_SIGN_SALT = 0xB5297A4D


def _fmix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def sketch_signs(seed0, seed1, idx):
    """Rademacher ±1 f32 basis signs keyed on the GLOBAL element index —
    the same counter-hash law as ``_hash_uniform`` (salted), so any tiling
    / chunking / segment split of the expand is bit-invisible and the basis
    itself never ships on the wire: both sides rebuild it from (seed, idx).
    """
    x = _fmix32(idx * jnp.uint32(0x9E3779B9)
                + (seed0 ^ jnp.uint32(_SIGN_SALT)))
    x = _fmix32(x ^ seed1)
    return 1.0 - 2.0 * (x & jnp.uint32(1)).astype(jnp.float32)


def basis_seeds(basis_seed, version):
    """The per-round sketch-basis seed pair: (run basis seed, model version)
    -> (2,) uint32. Pure fmix32 avalanche — computable host-side (python
    ints in, jnp scalars out) and in-graph from a traced version counter, so
    the fused entries take it as a TRACED argument and never retrace per
    round."""
    b = jnp.uint32(basis_seed)
    v = jnp.asarray(version).astype(jnp.uint32)
    s0 = _fmix32(v * jnp.uint32(0x9E3779B9) + b)
    s1 = _fmix32(s0 ^ jnp.uint32(0x7F4A7C15))
    return jnp.stack([s0, s1])


def sketch_project(c2d, seeds, group: int):
    """Project a (B, d_pad) stack onto the sketch subspace: y[b, r] =
    g^-1/2 * sum_{j in group r} sign_j * c[b, j], d_pad % group == 0.
    Rows of the implied S are orthonormal (one nonzero per column), so
    S S^T = I and the expand below is S^T exactly."""
    b, dpad = c2d.shape
    assert dpad % group == 0, (dpad, group)
    idx = jnp.arange(dpad, dtype=jnp.uint32)
    s = sketch_signs(seeds[0], seeds[1], idx)
    y = tree_sum((c2d * s).reshape(b, dpad // group, group))[..., 0]
    return y * jnp.float32(1.0 / float(group) ** 0.5)


def sketch_expand(y2d, seeds, group: int, offset=0):
    """S^T: a (B, r) subspace slice back to (B, r*group) flat coordinates
    starting at GLOBAL element ``offset`` (traced ok; offset % group == 0).
    Elementwise in the output index, so segment-local expansion on a mesh
    is bit-identical to the whole-vector expand."""
    b, r = y2d.shape
    idx = (jnp.asarray(offset).astype(jnp.uint32)
           + jnp.arange(r * group, dtype=jnp.uint32))
    s = sketch_signs(seeds[0], seeds[1], idx)
    x = jnp.repeat(y2d, group, axis=-1) * s
    return x * jnp.float32(1.0 / float(group) ** 0.5)


# ---------------------------------------------------------------------------
# Chunked threefry dither (streaming encode of the b=1 wire convention)
# ---------------------------------------------------------------------------


def threefry_uniform_rows(key, row_start, rows: int, lanes: int = LANES):
    """Rows [row_start, row_start+rows) of the EXACT uniform field
    ``jax.random.uniform(key, (total_rows, lanes), f32)``, for any
    ``total_rows >= row_start + rows`` — the b=1 wire
    convention's dither — without materializing the whole field.

    Under jax's partitionable threefry (the default), element ``j`` of a
    32-bit draw is ``w0 ^ w1`` of ``threefry_2x32(key, (hi=0, lo=j))`` for
    its flat index ``j``: a law local to each index, so any row chunk is
    that law on its own indices (``row_start`` may be traced — one
    compilation covers every chunk of a given shape). The bits->f32 mapping
    is jax's (top 23 bits into the mantissa of 1.x, minus 1). Bit-exactness with the full draw is pinned in
    tests/test_mesh2d.py, chunk-boundary cases included.
    """
    from jax.extend.random import threefry_2x32
    j = (jnp.uint32(row_start) * jnp.uint32(lanes)
         + jnp.arange(rows * lanes, dtype=jnp.uint32))
    out = threefry_2x32(jnp.asarray(key).reshape(-1)[:2].astype(jnp.uint32),
                        jnp.concatenate([jnp.zeros_like(j), j]))
    bits32 = out[:j.size] ^ out[j.size:]
    u = jax.lax.bitcast_convert_type(
        (bits32 >> 9) | jnp.uint32(0x3F800000), jnp.float32) - 1.0
    return u.reshape(rows, lanes)


# ---------------------------------------------------------------------------
# Unpack + dequantize
# ---------------------------------------------------------------------------


def _unpack_dequantize_block(p, norm2d, bits: int):
    """Shared block math: packed uint8 (R, 128/per_byte) + norms (R, 1) ->
    f32 (R, 128). Used by the kernel and the fused off-TPU route."""
    s = (1 << (bits - 1)) - 1
    sign, mag = _decode_codes(p, bits)
    scale = norm2d / float(s)  # (R, 1), broadcasts over lanes
    return sign * mag * scale


def _unpack_dequantize_kernel(p_ref, norm_ref, out_ref, *, bits: int):
    """One block: packed uint8 (R, 128/per_byte) + norms (R, 1) -> f32 (R, 128)."""
    out_ref[...] = _unpack_dequantize_block(p_ref[...], norm_ref[...], bits)


@functools.partial(jax.jit, static_argnames=("bits", "interpret", "force_pallas"))
def qsgd_unpack_dequantize(packed: jnp.ndarray, norms: jnp.ndarray,
                           bits: int, interpret: bool = True,
                           force_pallas: bool = False) -> jnp.ndarray:
    """Inverse of qsgd_quantize_pack; returns f32 (rows, 128).

    Accepts wire-layout rows; the pallas route pads to BLOCK_ROWS tiles
    internally (zero rows, sliced off). Off-TPU the shared block math runs
    as one XLA-fused computation — bit-identical to the interpreted kernel
    (``force_pallas=True`` exercises it)."""
    per_byte = 8 // bits
    in_lanes = LANES // per_byte
    rows = packed.shape[0]
    assert packed.shape[1] == in_lanes, packed.shape
    norms2d = norms.reshape(rows, 1).astype(jnp.float32)
    if interpret and not force_pallas:
        return _unpack_dequantize_block(packed, norms2d, bits)
    rpad = (-rows) % BLOCK_ROWS
    if rpad:
        packed = jnp.concatenate(
            [packed, jnp.zeros((rpad, in_lanes), jnp.uint8)])
        norms2d = jnp.concatenate(
            [norms2d, jnp.zeros((rpad, 1), jnp.float32)])
    grid = ((rows + rpad) // BLOCK_ROWS,)
    out = pl.pallas_call(
        functools.partial(_unpack_dequantize_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, in_lanes), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + rpad, LANES), jnp.float32),
        interpret=interpret,
    )(packed, norms2d)
    return out[:rows]
