"""Quantizers (compression operators) per Definition 2.1 of the QAFeL paper.

A quantizer Q: R^d -> R^d satisfies  E_Q ||Q(x) - x||^2 <= (1 - delta) ||x||^2
for a compression parameter delta > 0.  Example B.1 of the paper defines the
three standard operators implemented here:

* ``qsgd_s`` — stochastic n-bit quantization (Alistarh et al., 2017). Sends
  ||x||, sign(x) and stochastically rounded integer levels xi(x, s). Unbiased.
  For an n-bit code we use 1 sign bit + (n-1) magnitude bits, i.e.
  s = 2**(n-1) - 1 levels, matching the paper's "n bits per coordinate"
  wire-size accounting (kB/upload tables in Appendix E).
* ``top_k`` — keeps the k largest-magnitude coordinates. Biased; delta = k/d.
* ``rand_k`` — keeps k uniformly random coordinates. With ``scaled=True`` the
  kept coordinates are multiplied by d/k which makes the operator unbiased
  (the variant the paper's client-side analysis needs); with ``scaled=False``
  it is the contractive version with delta = k/d.
* ``identity`` — no compression (delta = 1); turns QAFeL into exact FedBuff.

Two call surfaces are provided:

* ``qdq(x, key)``: quantize-dequantize in floating point. This is what runs
  *inside* jitted/pjit'd training steps (the reconstruction is all the math
  needs; the wire format is accounted analytically). Operates leaf-wise with
  independent randomness per leaf.
* ``encode(x, key)`` / ``decode(msg)``: the actual packed wire format (uint8
  payloads) used by the host-level async simulator and the byte-accounting
  benchmarks. ``encode`` flattens the WHOLE pytree into one contiguous f32
  vector (``TreeLayout`` records leaf shapes/dtypes/offsets) and compresses
  it in a single pass — for qsgd that is exactly one quantize-pack Pallas
  kernel dispatch per message (interpret mode on CPU, real kernels on TPU),
  one padding tail, and one contiguous uint8 payload + bucket-norm vector
  that the server buffer can stack and feed straight into the fused
  dequantize-accumulate kernel (``repro.kernels.buffer_agg``) without ever
  materialising the decoded f32 delta. See DESIGN.md ("Packed wire layout").

The legacy per-leaf wire path is kept as ``encode_leafwise``/
``decode_leafwise`` for A/B benchmarking; ``decode`` accepts both formats.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.tree import split_key_tree

# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class QuantizerSpec:
    """Declarative description of a quantizer; hashable, storable in configs."""

    kind: str  # "qsgd" | "top_k" | "rand_k" | "identity" | "lowrank"
    bits: int = 4  # for qsgd/lowrank: total bits per coordinate (incl. sign)
    fraction: float = 0.1  # for top_k / rand_k: k = ceil(fraction * d)
    scaled: bool = True  # rand_k only: unbiased (d/k) scaling
    # qsgd bucketing (Alistarh et al.'s implementation; the paper's kB tables
    # show ~0.2 extra bits/coord = one fp32 norm per O(128) coords). Bucketing
    # is what keeps 1 - delta < 1 at model sizes: a single whole-tensor norm
    # gives 1 - delta ~ sqrt(2d)/s >> 1 and the hidden-state loop diverges.
    # 128 matches the Pallas kernel's lane width (one norm per VMEM row).
    bucket_size: int = 128
    # lowrank only: contiguous elements sketched into ONE subspace coordinate
    # (rank = padded_d / group). Must divide the 128-lane bucket row so a
    # mesh segment of whole bucket rows maps to whole subspace coordinates —
    # the segment-local expand law.
    group: int = 32

    def __post_init__(self):
        if self.kind not in ("qsgd", "top_k", "rand_k", "identity", "lowrank"):
            raise ValueError(f"unknown quantizer kind: {self.kind}")
        if self.kind in ("qsgd", "lowrank") and not (2 <= self.bits <= 8):
            raise ValueError(f"{self.kind} bits must be in [2, 8]")
        if self.kind in ("top_k", "rand_k") and not (0.0 < self.fraction <= 1.0):
            raise ValueError("fraction must be in (0, 1]")
        if self.kind == "lowrank" and (
                self.group < 2 or self.bucket_size % self.group != 0):
            raise ValueError("lowrank group must be >= 2 and divide the "
                             f"{self.bucket_size}-lane bucket row")

    # -- properties -----------------------------------------------------
    @property
    def unbiased(self) -> bool:
        if self.kind == "qsgd" or self.kind == "identity":
            return True
        if self.kind == "rand_k":
            return self.scaled
        return False  # top_k

    @property
    def levels(self) -> int:
        """qsgd: number of magnitude levels s (1 sign bit + bits-1 magnitude)."""
        return (1 << (self.bits - 1)) - 1

    def rank(self, d: int) -> int:
        """lowrank: subspace dimension d_r for a d-element message. Defined
        over the bucket-row-padded domain (group divides the bucket row), so
        every 128-element wire row maps to ``bucket_size // group`` whole
        subspace coordinates — segment-local on any mesh split."""
        if self.kind != "lowrank":
            raise ValueError(f"rank() is lowrank-only (kind={self.kind})")
        d_pad = math.ceil(d / self.bucket_size) * self.bucket_size
        return d_pad // self.group

    def delta(self, d: int) -> float:
        """Compression parameter delta for dimension d (clipped to (0, 1])."""
        if self.kind == "identity":
            return 1.0
        if self.kind in ("top_k", "rand_k"):
            k = max(1, math.ceil(self.fraction * d))
            return k / d
        # qsgd (Alistarh et al. 2017, Lemma 3.1) applied per bucket of size b:
        # E||Q(x)-x||^2 <= min(2b/s^2, sqrt(2b)/s) ||x||^2 (worst case).
        s = self.levels
        b = min(d, self.bucket_size)
        one_minus_delta = min(2 * b / s**2, math.sqrt(2 * b) / s)
        if self.kind == "lowrank":
            # a rank-d/g sketch keeps a 1/g fraction of the space per round
            # (error feedback recovers the complement across rounds); the
            # qsgd inner quantizer contributes its own factor on top.
            return max(1e-6, (1.0 - one_minus_delta) / self.group)
        return max(1e-6, 1.0 - one_minus_delta)

    def wire_bits(self, d: int) -> int:
        """Exact bits on the wire for a d-dimensional message."""
        if self.kind == "identity":
            return 32 * d
        if self.kind == "qsgd":
            n_buckets = math.ceil(d / self.bucket_size)
            return self.bits * d + 32 * n_buckets  # n bits/coord + fp32 norm/bucket
        if self.kind == "lowrank":
            r = self.rank(d)
            # the subspace message is itself a bucketed qsgd wire message;
            # the basis never ships (both sides re-derive it from the seed)
            return self.bits * r + 32 * math.ceil(r / self.bucket_size)
        k = max(1, math.ceil(self.fraction * d))
        # k (index, value) pairs: 32-bit index + 32-bit value
        return 64 * k

    def label(self) -> str:
        if self.kind == "identity":
            return "identity"
        if self.kind == "qsgd":
            return f"qsgd{self.bits}b"
        if self.kind == "lowrank":
            return f"lowrank{self.bits}g{self.group}"
        return f"{self.kind}{self.fraction:g}"


# ---------------------------------------------------------------------------
# Packed pytree layout
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TreeLayout:
    """Static description of a pytree flattened into one contiguous vector.

    Records, in flattening order, each leaf's shape/dtype/size so a packed
    flat f32 payload can be split back into the original tree. The layout is
    host-side metadata only — it never travels through a kernel.
    """

    treedef: Any
    shapes: tuple
    dtypes: tuple  # dtype names, e.g. "float32"
    sizes: tuple

    @property
    def total_size(self) -> int:
        return sum(self.sizes)

    @staticmethod
    def of(tree) -> "TreeLayout":
        leaves, treedef = jax.tree.flatten(tree)
        return TreeLayout(
            treedef=treedef,
            shapes=tuple(x.shape for x in leaves),
            dtypes=tuple(str(jnp.asarray(x).dtype) for x in leaves),
            sizes=tuple(int(jnp.asarray(x).size) for x in leaves),
        )

    def unflatten(self, flat: jnp.ndarray):
        """Split a flat f32 vector back into the original (shaped, typed) tree."""
        leaves = []
        off = 0
        for shape, dtype, size in zip(self.shapes, self.dtypes, self.sizes):
            leaves.append(flat[off:off + size].reshape(shape).astype(dtype))
            off += size
        return jax.tree.unflatten(self.treedef, leaves)


def flatten_tree(tree):
    """Concatenate all leaves into one flat f32 vector; returns (flat, layout)."""
    layout = TreeLayout.of(tree)
    leaves = jax.tree.leaves(tree)
    flat = jnp.concatenate(
        [jnp.asarray(x).reshape(-1).astype(jnp.float32) for x in leaves]
    ) if leaves else jnp.zeros((0,), jnp.float32)
    return flat, layout


def packed_qsgd_payload(packed, norms, bits: int, n: int,
                        layout: TreeLayout) -> dict:
    """The one source of truth for the packed qsgd wire-payload schema.

    Used by every qsgd encode entry point AND by the fused server flush,
    which frames the broadcast bits produced in-graph."""
    return {"format": "packed", "kind": "qsgd", "packed": packed,
            "norms": norms, "bits": bits, "n": n, "layout": layout}


def packed_identity_payload(flat, n: int, layout: TreeLayout) -> dict:
    """Packed wire-payload schema for identity (full-precision) messages."""
    return {"format": "packed", "kind": "identity", "payload": flat,
            "n": n, "layout": layout}


def packed_lowrank_payload(packed, norms, bits: int, n: int,
                           layout: TreeLayout, rank: int, group: int,
                           seed) -> dict:
    """Packed wire-payload schema for low-rank sketched uploads.

    Self-describing: carries kind=lowrank, the subspace dimension ``rank``
    (= padded n / group), the sketch ``group`` and the (2,) uint32 basis
    ``seed``, so the server can dequantize-accumulate in the d_r space and
    expand segment-locally without any out-of-band state. The codes/norms
    themselves are an ordinary bucketed qsgd message over the rank-length
    subspace vector."""
    return {"format": "packed", "kind": "lowrank", "packed": packed,
            "norms": norms, "bits": bits, "n": n, "layout": layout,
            "rank": rank, "group": group, "seed": seed}


def lowrank_project_flat2d(flat2d: jnp.ndarray, seeds, group: int):
    """Sketch-project a ``(B, n)`` stack to ``(B, rank)`` wire-subspace
    coordinates: zero-pad n to whole 128-lane bucket rows (so the group
    grid aligns with wire rows), then apply the counter-hash Rademacher
    sketch. Traceable; ``seeds`` is the round's (2,) uint32 basis seed."""
    from repro.kernels import qsgd as _kq  # local import: kernels are optional

    b, n = flat2d.shape
    rows = -(-n // _kq.LANES)
    pad = rows * _kq.LANES - n
    if pad:
        flat2d = jnp.concatenate(
            [flat2d, jnp.zeros((b, pad), flat2d.dtype)], axis=1)
    return _kq.sketch_project(flat2d, seeds, group)


def lowrank_expand_flat2d(y2d: jnp.ndarray, seeds, group: int, n: int,
                          offset=0):
    """Expand a ``(B, rank_slice)`` subspace stack back to flat wire
    coordinates, sliced to the true element count ``n`` (pass ``n=None`` to
    keep the padded width — segment callers slice themselves). ``offset``
    is the GLOBAL flat element index of the slice's first output element
    (traced ok), which is what makes the expand segment-local."""
    from repro.kernels import qsgd as _kq  # local import: kernels are optional

    x = _kq.sketch_expand(y2d, seeds, group, offset)
    return x if n is None else x[:, :n]


def flatten_stacked_leaves(leaves, b: int) -> jnp.ndarray:
    """Flatten B-stacked pytree leaves into one ``(b, d)`` f32 stack (the
    wire coordinate order). The ONE implementation of the delta-stack
    flatten, shared by the host-side ``encode_batch`` and the in-jit fused
    cohort step so the two can never diverge. Traceable."""
    if len(leaves) == 1:
        return leaves[0].reshape(b, -1).astype(jnp.float32)
    return jnp.concatenate(
        [l.reshape(b, -1).astype(jnp.float32) for l in leaves], axis=1)


def qsgd_encode_rows(x3d: jnp.ndarray, seeds, bits: int, row_off, *,
                     chunk_rows=None):
    """Counter-hash quantize-pack of a ``(B, R, 128)`` row block whose first
    row sits at GLOBAL wire row ``row_off`` (a traced value is fine — the
    sharded and streamed callers pass axis-index offsets).

    The dither keys on the global element index, so ANY tiling of the rows
    — ``chunk_rows``-sized ``lax.scan`` chunks here, model-axis segments in
    the 2-D cohort step, host-streamed chunks in ``QAFeL.run_client`` —
    emits the same wire bits as one whole-message encode. ``chunk_rows``
    bounds the f32 dither/code transients to one chunk at a time (the tail
    chunk is zero-row-padded; zero rows emit zero codes and are sliced
    off). Returns ``(packed (B, R, 128*bits//8), norms (B, R))``.
    """
    from repro.kernels import qsgd as _kq  # local import: kernels are optional

    b, rows, lanes = x3d.shape
    if chunk_rows is None or chunk_rows >= rows:
        packed, norm = _kq._quantize_pack_batch_block(
            x3d, seeds[:, 0], seeds[:, 1], row_off, bits)
        return packed, norm.reshape(b, rows)
    c = int(chunk_rows)
    nch = -(-rows // c)
    rpad = nch * c - rows
    if rpad:
        x3d = jnp.concatenate(
            [x3d, jnp.zeros((b, rpad, lanes), x3d.dtype)], axis=1)
    x4 = x3d.reshape(b, nch, c, lanes).transpose(1, 0, 2, 3)
    row_off = jnp.asarray(row_off).astype(jnp.uint32)

    def body(_, xs):
        x_c, i = xs
        p_c, n_c = _kq._quantize_pack_batch_block(
            x_c, seeds[:, 0], seeds[:, 1], row_off + i * jnp.uint32(c), bits)
        return None, (p_c, n_c.reshape(b, c))

    _, (p4, n4) = jax.lax.scan(body, None,
                               (x4, jnp.arange(nch, dtype=jnp.uint32)))
    packed = p4.transpose(1, 0, 2, 3).reshape(b, nch * c, -1)[:, :rows]
    norms = n4.transpose(1, 0, 2).reshape(b, nch * c)[:, :rows]
    return packed, norms


def qsgd_encode_flat2d(flat2d: jnp.ndarray, keys, bits: int, *,
                       threefry: bool = False, chunk_rows=None):
    """Traceable batched quantize-pack over an already-flat ``(B, n)`` stack.

    The in-jit callee behind the fused cohort train+encode step
    (``kernels.ops.cohort_train_encode_step``): runs the Pallas kernels'
    shared block math directly in the caller's trace, so training and
    encoding live in ONE computation with no dispatch boundary between them.

    Dither selection mirrors the host-side wire entries exactly:

    * ``threefry=True`` (requires B == 1; ``keys`` is one PRNG key)
      reproduces the single-message path — ``kernels.ops.qsgd_quantize``'s
      host-threefry uniforms — bit for bit, which is what keeps the
      sequential engine's wire bits unchanged by the fusion.
    * ``threefry=False`` (``keys`` is a (B, ...) per-message key stack)
      uses the batched entry's in-kernel counter-hash dither, bit-identical
      to ``kernels.ops.qsgd_quantize_batch``.

    ``chunk_rows`` tiles the encode over fixed-size row chunks inside one
    ``lax.scan`` so no full-width f32 dither/code transient materializes:
    the counter-hash path keys on global element indices and the threefry
    path reproduces exact chunks of the whole-message uniform field
    (``kernels.qsgd.threefry_uniform_rows``), so the emitted wire bits are
    IDENTICAL to the unchunked encode for any chunk size (pinned in
    tests/test_mesh2d.py).

    Returns ``(packed uint8 (B, rows, 128*bits//8), norms f32 (B, rows))``
    in wire layout.
    """
    from repro.kernels import qsgd as _kq  # local import: kernels are optional

    b, n = flat2d.shape
    rows = -(-n // _kq.LANES)
    pad = rows * _kq.LANES - n
    if pad:
        flat2d = jnp.concatenate(
            [flat2d, jnp.zeros((b, pad), flat2d.dtype)], axis=1)
    if threefry:
        if b != 1:
            raise ValueError("threefry dither is the single-message path; "
                             f"got B={b}")
        x2d = flat2d.reshape(rows, _kq.LANES)
        if chunk_rows is not None and chunk_rows < rows:
            c = int(chunk_rows)
            nch = -(-rows // c)
            rpad = nch * c - rows
            if rpad:
                x2d = jnp.concatenate(
                    [x2d, jnp.zeros((rpad, _kq.LANES), x2d.dtype)])
            x3 = x2d.reshape(nch, c, _kq.LANES)

            def body(_, xs):
                x_c, i = xs
                u_c = _kq.threefry_uniform_rows(keys, i * c, c)
                return None, _kq._quantize_pack_block(x_c, u_c, bits)

            _, (p3, n3) = jax.lax.scan(body, None, (x3, jnp.arange(nch)))
            return (p3.reshape(nch * c, -1)[:rows][None],
                    n3.reshape(nch * c)[:rows].reshape(1, rows))
        u2d = jax.random.uniform(keys, (rows, _kq.LANES), dtype=jnp.float32)
        packed, norm = _kq._quantize_pack_block(x2d, u2d, bits)
        return packed[None], norm.reshape(1, rows)
    x3d = flat2d.reshape(b, rows, _kq.LANES)
    seeds = jnp.asarray(keys).reshape(b, -1)[:, :2].astype(jnp.uint32)
    return qsgd_encode_rows(x3d, seeds, bits, 0, chunk_rows=chunk_rows)


def qsgd_decode_flat(packed: jnp.ndarray, norms: jnp.ndarray, bits: int,
                     n: int) -> jnp.ndarray:
    """Traceable dequantize of one wire-layout message to its flat (n,) f32
    vector: the kernels' shared block math in the caller's trace, the
    decode twin of ``qsgd_encode_flat2d``. For graphs GSPMD partitions (the
    distributed round), where a Mosaic kernel cannot run; bit-identical to
    ``kernels.ops.qsgd_dequantize``."""
    from repro.kernels import qsgd as _kq  # local import: kernels are optional

    rows = packed.shape[0]
    x2d = _kq._unpack_dequantize_block(
        packed, jnp.asarray(norms).astype(jnp.float32).reshape(rows, 1), bits)
    return x2d.reshape(-1)[:n]


def qsgd_pack_lastdim(x: jnp.ndarray, key, bits: int, bucket: int = 128):
    """Bucketed qsgd quantize + bit-pack along the LAST dim only.

    The shape-preserving variant of the wire math for tensors whose other
    dims may be sharded (no reshape ever crosses a non-last axis): buckets,
    norms and packing all live inside the last dim. This is the shared
    callee of the distributed pod-quantized exchange
    (``repro.distributed.steps``), which all_gathers the (packed, norms)
    pair across the pod axis instead of raw f32. Requires
    ``x.shape[-1] % (bucket * (8 // bits)) == 0``. Returns
    ``(packed uint8 (..., n * bits // 8), norms f32 (..., n // bucket))``.
    """
    s = (1 << (bits - 1)) - 1
    per_byte = 8 // bits
    xf = x.astype(jnp.float32)
    n = x.shape[-1]
    xb = xf.reshape(x.shape[:-1] + (n // bucket, bucket))
    norms = jnp.sqrt(jnp.sum(xb * xb, axis=-1, keepdims=True))
    inv = jnp.where(norms > 0.0, s / jnp.maximum(norms, 1e-30), 0.0)
    level = jnp.abs(xb) * inv
    low = jnp.floor(level)
    u = jax.random.uniform(key, xb.shape, dtype=jnp.float32)
    xi = jnp.minimum(low + (u < (level - low)), float(s)).astype(jnp.uint32)
    code = ((xb < 0.0).astype(jnp.uint32) << (bits - 1)) | xi
    grouped = code.reshape(x.shape[:-1] + (n // per_byte, per_byte))
    shifts = (jnp.arange(per_byte, dtype=jnp.uint32) * bits)
    packed = jnp.sum(grouped << shifts, axis=-1).astype(jnp.uint8)
    return packed, norms[..., 0]


def qsgd_unpack_lastdim(packed: jnp.ndarray, norms: jnp.ndarray, bits: int,
                        bucket: int = 128) -> jnp.ndarray:
    """Inverse of ``qsgd_pack_lastdim``: codes (..., n*bits//8) + norms
    (..., n//bucket) -> f32 (..., n). Leading dims (e.g. a gathered pod
    axis) pass through untouched."""
    s = (1 << (bits - 1)) - 1
    per_byte = 8 // bits
    shifts = (jnp.arange(per_byte, dtype=jnp.uint32) * bits)
    codes = ((packed[..., None].astype(jnp.uint32) >> shifts)
             & jnp.uint32((1 << bits) - 1))
    codes = codes.reshape(norms.shape + (bucket,))
    mag = (codes & jnp.uint32(s)).astype(jnp.float32)
    sign = 1.0 - 2.0 * ((codes >> (bits - 1)) & 1).astype(jnp.float32)
    vals = sign * mag * (norms[..., None] / float(s))
    return vals.reshape(packed.shape[:-1] + (packed.shape[-1] * per_byte,))


# ---------------------------------------------------------------------------
# qsgd math (pure jnp; the Pallas kernel in repro/kernels mirrors this)
# ---------------------------------------------------------------------------


def _qsgd_qdq_flat(x: jnp.ndarray, key, s: int, bucket: int) -> jnp.ndarray:
    """Quantize-dequantize a flat fp vector: s stochastic levels per bucket."""
    xf = x.astype(jnp.float32)
    n = xf.size
    pad = (-n) % bucket
    xp = jnp.pad(xf, (0, pad)).reshape(-1, bucket)
    norm = jnp.linalg.norm(xp, axis=1, keepdims=True)
    safe = jnp.maximum(norm, 1e-30)
    level = jnp.abs(xp) * (s / safe)
    low = jnp.floor(level)
    prob = level - low
    u = jax.random.uniform(key, xp.shape, dtype=jnp.float32)
    xi = jnp.minimum(low + (u < prob).astype(jnp.float32), float(s))  # in [0, s]
    recon = jnp.sign(xp) * xi * (safe / s)
    recon = jnp.where(norm > 0, recon, jnp.zeros_like(xp))
    return recon.reshape(-1)[:n].astype(x.dtype)


def _top_k_qdq_flat(x: jnp.ndarray, k: int) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    # Single deterministic mask: argsort breaks magnitude ties by index, so
    # exactly k coordinates are kept.
    order = jnp.argsort(-jnp.abs(xf))
    mask = jnp.zeros_like(xf, dtype=bool).at[order[:k]].set(True)
    return jnp.where(mask, xf, 0.0).astype(x.dtype)


def _rand_k_qdq_flat(x: jnp.ndarray, key, k: int, scaled: bool) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    d = x.size
    idx = jax.random.choice(key, d, shape=(k,), replace=False)
    mask = jnp.zeros((d,), dtype=bool).at[idx].set(True)
    out = jnp.where(mask, xf, 0.0)
    if scaled:
        out = out * (d / k)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Quantizer object
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Quantizer:
    spec: QuantizerSpec

    # ---- in-graph float math -------------------------------------------
    def qdq_leaf(self, x: jnp.ndarray, key) -> jnp.ndarray:
        """Quantize-dequantize one array (any shape)."""
        spec = self.spec
        if spec.kind == "identity":
            return x
        flat = x.reshape(-1)
        if spec.kind == "qsgd":
            out = _qsgd_qdq_flat(flat, key, spec.levels, spec.bucket_size)
        elif spec.kind == "top_k":
            k = max(1, math.ceil(spec.fraction * flat.size))
            out = _top_k_qdq_flat(flat, k)
        else:  # rand_k
            k = max(1, math.ceil(spec.fraction * flat.size))
            out = _rand_k_qdq_flat(flat, key, k, spec.scaled)
        return out.reshape(x.shape)

    def qdq(self, tree, key):
        """Quantize-dequantize a pytree, independent randomness per leaf."""
        if self.spec.kind == "identity":
            return tree
        keys = split_key_tree(key, tree)
        return jax.tree.map(self.qdq_leaf, tree, keys)

    def qdq_flat(self, flat: jnp.ndarray, key) -> jnp.ndarray:
        """Quantize-dequantize one already-flat vector (traceable).

        The flat-substrate in-graph entry used by the distributed round for
        the sparse kinds (top_k / rand_k), whose reconstruction equals their
        wire decode exactly (the kept values travel in full precision), and
        available for qsgd/identity for completeness. For qsgd this honours
        ``spec.bucket_size`` like ``qdq`` — the wire path's 128-lane row
        math lives in ``qsgd_encode_flat2d``.
        """
        spec = self.spec
        if spec.kind == "identity":
            return flat
        if spec.kind == "qsgd":
            return _qsgd_qdq_flat(flat, key, spec.levels, spec.bucket_size)
        if spec.kind == "lowrank":
            # sketch -> qsgd-qdq in the subspace -> expand; the basis seed
            # derives from the call key (standalone qdq has no round state)
            seeds = jnp.asarray(key).reshape(-1)[:2].astype(jnp.uint32)
            n = int(flat.size)
            y = lowrank_project_flat2d(flat[None], seeds, spec.group)
            yq = _qsgd_qdq_flat(y[0], key, spec.levels, spec.bucket_size)
            return lowrank_expand_flat2d(yq[None], seeds, spec.group, n)[0]
        k = max(1, math.ceil(spec.fraction * flat.size))
        if spec.kind == "top_k":
            return _top_k_qdq_flat(flat, k)
        return _rand_k_qdq_flat(flat, key, k, spec.scaled)

    # ---- wire format ----------------------------------------------------
    def encode_leaf(self, x: jnp.ndarray, key) -> dict:
        """Encode one array into its packed wire message (host-level path)."""
        from repro.kernels import ops as kops  # local import: kernels are optional

        spec = self.spec
        flat = x.reshape(-1).astype(jnp.float32)
        if spec.kind == "identity":
            return {"kind": "identity", "payload": flat, "shape": x.shape, "dtype": str(x.dtype)}
        if spec.kind == "qsgd":
            # The wire path uses the Pallas kernel; its bucket is the 128-lane
            # row. The in-graph qdq path honours spec.bucket_size exactly.
            packed, norms = kops.qsgd_quantize(flat, key, spec.bits)
            return {
                "kind": "qsgd",
                "packed": packed,
                "norms": norms,
                "bits": spec.bits,
                "n": flat.size,
                "shape": x.shape,
                "dtype": str(x.dtype),
            }
        k = max(1, math.ceil(spec.fraction * flat.size))
        if spec.kind == "top_k":
            vals, idx = jax.lax.top_k(jnp.abs(flat), k)
            vals = flat[idx]
        else:
            idx = jax.random.choice(key, flat.size, shape=(k,), replace=False)
            vals = flat[idx]
            if spec.scaled:
                vals = vals * (flat.size / k)
        return {
            "kind": spec.kind,
            "idx": idx.astype(jnp.int32),
            "vals": vals,
            "n": flat.size,
            "shape": x.shape,
            "dtype": str(x.dtype),
        }

    def decode_leaf(self, msg: dict) -> jnp.ndarray:
        from repro.kernels import ops as kops

        kind = msg["kind"]
        if kind == "identity":
            out = msg["payload"]
        elif kind == "qsgd":
            out = kops.qsgd_dequantize(msg["packed"], msg["norms"], msg["bits"], msg["n"])
        else:
            out = jnp.zeros((msg["n"],), jnp.float32).at[msg["idx"]].set(msg["vals"])
        return out.reshape(msg["shape"]).astype(msg["dtype"])

    # ---- packed wire format (the default path) --------------------------
    def encode(self, tree, key) -> dict:
        """Encode a whole pytree as ONE contiguous packed message.

        The tree is flattened into a single flat f32 vector (``TreeLayout``
        records how to undo it) and compressed in one pass — for qsgd this is
        exactly one quantize-pack kernel dispatch with a single padding tail,
        regardless of how many leaves the model has.
        """
        flat, layout = flatten_tree(tree)
        return self.encode_flat(flat, layout, key)

    def encode_flat(self, flat: jnp.ndarray, layout: TreeLayout, key) -> dict:
        """Flat-first encode: compress an already-flat f32 vector.

        This is the canonical wire entry point for callers that hold the
        model in its device-resident flat form (the server's flush path) —
        no tree is ever materialized. ``encode`` is the tree-view
        convenience wrapper around it.
        """
        from repro.kernels import ops as kops  # local import: kernels are optional

        spec = self.spec
        n = int(flat.size)
        if spec.kind == "identity":
            return packed_identity_payload(flat, n, layout)
        if spec.kind == "qsgd":
            packed, norms = kops.qsgd_quantize(flat, key, spec.bits)
            return packed_qsgd_payload(packed, norms, spec.bits, n, layout)
        if spec.kind == "lowrank":
            # standalone encode: basis seed derives from the call key (the
            # payload is self-describing so decode never needs round state;
            # the protocol's fused path passes the version-keyed seed
            # explicitly via ``basis_seed``)
            seeds = jnp.asarray(key).reshape(-1)[:2].astype(jnp.uint32)
            return self.encode_lowrank_flat(flat, layout, key, seeds)
        k = max(1, math.ceil(spec.fraction * n))
        if spec.kind == "top_k":
            order = jnp.argsort(-jnp.abs(flat))
            idx = order[:k]
            vals = flat[idx]
        else:  # rand_k
            idx = jax.random.choice(key, n, shape=(k,), replace=False)
            vals = flat[idx]
            if spec.scaled:
                vals = vals * (n / k)
        return {"format": "packed", "kind": spec.kind, "idx": idx.astype(jnp.int32),
                "vals": vals, "n": n, "layout": layout}

    def encode_batch(self, stacked_tree, keys) -> list:
        """Encode a cohort of B deltas (leaves stacked on a leading B axis,
        e.g. the output of a vmap'ed client update) as B packed messages.

        For qsgd the whole (B, d) stack goes through ONE batched quantize-pack
        kernel dispatch (``kops.qsgd_quantize_batch``) whose stochastic-
        rounding dither is generated in-kernel from each message's key, so
        B > 1 messages differ bit-wise from ``encode``'s threefry dither
        (same wire format, unbiasedness and error bound). A cohort of one IS
        a single message: B == 1 delegates to ``encode`` and is bit-identical
        to the sequential path — the seeded-equivalence anchor. ``keys`` is a
        (B, ...) stack of per-message PRNG keys. Returns a list of B message
        dicts in the packed wire format ``encode`` produces.
        """
        from repro.kernels import ops as kops  # local import: kernels are optional

        spec = self.spec
        leaves = jax.tree.leaves(stacked_tree)
        if not leaves:
            raise ValueError("encode_batch needs a non-empty tree")
        b = int(leaves[0].shape[0])
        if b == 1:
            return [self.encode(jax.tree.map(lambda l: l[0], stacked_tree),
                                jnp.asarray(keys)[0])]
        layout = TreeLayout.of(jax.tree.map(lambda l: l[0], stacked_tree))
        flat2d = flatten_stacked_leaves(leaves, b)
        n = int(flat2d.shape[1])
        keys = jnp.asarray(keys)
        # per-message payloads are handed back as numpy: the host-level wire
        # format is plain bytes, and numpy slicing is a view, not one
        # dispatched device op per message
        if spec.kind == "identity":
            flat2d = np.asarray(flat2d)
            return [packed_identity_payload(flat2d[i], n, layout)
                    for i in range(b)]
        if spec.kind == "qsgd":
            packed, norms = kops.qsgd_quantize_batch(flat2d, keys, spec.bits)
            packed, norms = np.asarray(packed), np.asarray(norms)
            return [packed_qsgd_payload(packed[i], norms[i], spec.bits, n,
                                        layout) for i in range(b)]
        if spec.kind == "lowrank":
            raise ValueError(
                "lowrank cohort encodes ride the fused cohort step "
                "(kernels.ops.cohort_train_encode_step): the basis seed is "
                "round state that encode_batch does not carry")
        k = max(1, math.ceil(spec.fraction * n))
        if spec.kind == "top_k":
            idx = jnp.argsort(-jnp.abs(flat2d), axis=1)[:, :k]
            vals = jnp.take_along_axis(flat2d, idx, axis=1)
        else:  # rand_k: independent index draws per message
            idx = jax.vmap(
                lambda kk: jax.random.choice(kk, n, shape=(k,), replace=False)
            )(keys)
            vals = jnp.take_along_axis(flat2d, idx, axis=1)
            if spec.scaled:
                vals = vals * (n / k)
        idx = np.asarray(idx.astype(jnp.int32))
        vals = np.asarray(vals)
        return [{"format": "packed", "kind": spec.kind,
                 "idx": idx[i], "vals": vals[i], "n": n,
                 "layout": layout} for i in range(b)]

    def encode_fast(self, tree, key) -> dict:
        """Single-message encode through the batched kernel entry.

        Same packed wire format as ``encode``, but the stochastic-rounding
        dither is the batched kernel's in-kernel counter hash — no host-side
        threefry pass and no per-cell interpret machinery off-TPU. Used on
        the server's flush hot path (one hidden-state broadcast per K
        uploads). Non-qsgd quantizers have no kernel in the loop and simply
        delegate to ``encode``.
        """
        flat, layout = flatten_tree(tree)
        return self.encode_fast_flat(flat, layout, key)

    def encode_fast_flat(self, flat: jnp.ndarray, layout: TreeLayout, key) -> dict:
        """Flat-first variant of ``encode_fast`` (no tree ever materialized)."""
        from repro.kernels import ops as kops  # local import: kernels are optional

        if self.spec.kind != "qsgd":
            return self.encode_flat(flat, layout, key)
        n = int(flat.size)
        packed, norms = kops.qsgd_quantize_batch(
            flat[None], jnp.asarray(key).reshape(1, -1), self.spec.bits)
        return packed_qsgd_payload(packed[0], norms[0], self.spec.bits, n,
                                   layout)

    def encode_lowrank_flat(self, flat: jnp.ndarray, layout: TreeLayout,
                            key, basis_seed) -> dict:
        """Lowrank wire encode of one flat vector under an EXPLICIT (2,)
        uint32 basis seed — the protocol entry (the seed is the round's
        ``kernels.qsgd.basis_seeds`` value both sides share)."""
        from repro.kernels import ops as kops  # local import: kernels are optional

        spec = self.spec
        n = int(flat.size)
        seeds = jnp.asarray(basis_seed).reshape(-1)[:2].astype(jnp.uint32)
        y = lowrank_project_flat2d(flat[None], seeds, spec.group)
        packed, norms = kops.qsgd_quantize(y[0], key, spec.bits)
        return packed_lowrank_payload(packed, norms, spec.bits, n, layout,
                                      int(y.shape[1]), spec.group,
                                      np.asarray(seeds))

    def decode_flat(self, enc, mesh=None) -> jnp.ndarray:
        """Dequantize a packed message to its flat f32 vector (no unflatten).
        ``mesh``: the payload's rows are spread over it (a sharded server's
        broadcast), so qsgd codes decode row-locally on every device."""
        from repro.kernels import ops as kops

        kind = enc["kind"]
        if kind == "identity":
            return enc["payload"]
        if kind == "qsgd" and mesh is not None:
            return kops.qsgd_dequantize_on_mesh(enc["packed"], enc["norms"],
                                                enc["bits"], enc["n"], mesh)
        if kind == "qsgd":
            return kops.qsgd_dequantize(enc["packed"], enc["norms"],
                                        enc["bits"], enc["n"])
        if kind == "lowrank":
            y = kops.qsgd_dequantize(enc["packed"], enc["norms"],
                                     enc["bits"], enc["rank"])
            seeds = jnp.asarray(enc["seed"]).astype(jnp.uint32)
            return lowrank_expand_flat2d(y[None], seeds, enc["group"],
                                         enc["n"])[0]
        return jnp.zeros((enc["n"],), jnp.float32).at[enc["idx"]].set(enc["vals"])

    def decode(self, enc):
        """Decode either wire format (packed single-buffer or legacy per-leaf)."""
        if "msgs" in enc:  # legacy per-leaf format
            return self.decode_leafwise(enc)
        return enc["layout"].unflatten(self.decode_flat(enc))

    # ---- legacy per-leaf wire format (kept for A/B comparison) ----------
    def encode_leafwise(self, tree, key):
        """One message dict per leaf — one kernel dispatch per leaf, each
        padded to a full tile. Superseded by ``encode``; kept as the baseline
        the packed path is benchmarked and tested against."""
        keys = split_key_tree(key, tree)
        leaves, treedef = jax.tree.flatten(tree)
        kleaves = jax.tree.leaves(keys)
        msgs = [self.encode_leaf(x, k) for x, k in zip(leaves, kleaves)]
        return {"treedef": treedef, "msgs": msgs}

    def decode_leafwise(self, enc):
        leaves = [self.decode_leaf(m) for m in enc["msgs"]]
        return jax.tree.unflatten(enc["treedef"], leaves)

    # ---- accounting ------------------------------------------------------
    def wire_bits_tree(self, tree) -> int:
        """Per-leaf analytic accounting (the paper's Appendix E model)."""
        return sum(self.spec.wire_bits(int(x.size)) for x in jax.tree.leaves(tree))

    def wire_bytes_tree(self, tree) -> float:
        return self.wire_bits_tree(tree) / 8.0

    def wire_bits_packed(self, tree_or_layout) -> int:
        """Exact bits on the wire for the packed single-buffer format: the
        whole tree is one d-dimensional message, so bucket norms are shared
        across leaf boundaries (<= the per-leaf sum)."""
        if isinstance(tree_or_layout, TreeLayout):
            d = tree_or_layout.total_size
        else:
            d = sum(int(x.size) for x in jax.tree.leaves(tree_or_layout))
        return self.spec.wire_bits(d)

    def wire_bytes_packed(self, tree_or_layout) -> float:
        return self.wire_bits_packed(tree_or_layout) / 8.0

    def delta_tree(self, tree) -> float:
        """Worst-case (min over leaves) compression parameter."""
        return min(self.spec.delta(int(x.size)) for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Constructors / registry
# ---------------------------------------------------------------------------


def make_quantizer(spec_or_name) -> Quantizer:
    """Build a Quantizer from a QuantizerSpec or a shorthand string.

    Shorthand: "qsgd4", "qsgd8", "top_k0.1", "rand_k0.05", "identity".
    """
    if isinstance(spec_or_name, Quantizer):
        return spec_or_name
    if isinstance(spec_or_name, QuantizerSpec):
        return Quantizer(spec_or_name)
    name = spec_or_name
    if name == "identity" or name is None:
        return Quantizer(QuantizerSpec("identity"))
    if name.startswith("lowrank"):
        # "lowrank", "lowrank4", "lowrank4g32": <bits>[g<group>]
        body = name[len("lowrank"):]
        bits_s, _, group_s = body.partition("g")
        return Quantizer(QuantizerSpec("lowrank", bits=int(bits_s or 4),
                                       group=int(group_s or 32)))
    if name.startswith("qsgd"):
        return Quantizer(QuantizerSpec("qsgd", bits=int(name[len("qsgd"):] or 4)))
    if name.startswith("top_k"):
        return Quantizer(QuantizerSpec("top_k", fraction=float(name[len("top_k"):] or 0.1)))
    if name.startswith("rand_k"):
        return Quantizer(QuantizerSpec("rand_k", fraction=float(name[len("rand_k"):] or 0.1)))
    raise ValueError(f"unknown quantizer: {name!r}")


IDENTITY = make_quantizer("identity")
