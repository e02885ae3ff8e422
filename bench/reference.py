"""The plain reference of QAFeL's flushes, and the comparison that decides
a run's ``correct``.

During set-up the harness records two blocks of consecutive flushes: the
run's first flushes, from the seed's weights, and as many flushes at
steady state, once the pipeline is full and a whole concurrency of uploads
has come back since, so their uploads were trained at model versions well
past 0 and carry the staleness of the timed window. For each block it
keeps every upload that reached ``receive`` (client id, model version,
bits, its packed codes and bucket norms as they crossed the wire, and the
dither and training keys its cohort dispatch was given), each flush's key,
and the server's x, x-hat and momentum before the block, after its first
flush and after its last. Every broadcast of set-up is kept as it crossed
the wire. The reference then replays the blocks in plain ``jax.numpy``:

* the replicas' decode: the x-hat of every version in use is the seed's
  weights plus the reference's own decode of each broadcast since;
* the client step: each upload is trained again from that x-hat at the
  upload's version, on the client's batches, and encoded with the
  upload's dither seed; its bucket norms and codes are compared with the
  wire's;
* the flush: from the state before the block (the seed's for the first
  block, the server's for the steady one), the K uploads, as they crossed
  the wire, are dequantized, weighted by 1/sqrt(1 + tau) / K and summed;
  the momentum, the server step and the broadcast encode of x - x-hat
  follow, and x-hat moves by the broadcast that went out, as a replica's
  does; the broadcasts' norms and codes are compared, the
  first flush's staleness-weighted sum and the block's changes of x and
  x-hat per leaf, and x and x-hat after the block per row of 128 values.

Upload numbers: ``upload_norm_gap`` (the worst bucket), ``upload_l2_gap``
(the worst upload's whole-message norm) and ``upload_code_mismatch``. A
cell's limits file names the numbers it compares.

Nothing of the system under test is imported here. A separable model is
replayed over blocks of whole rows, so the reference fits beside nothing.
With ``control`` the same replay runs a second time in that lower
precision, and the control's outputs take the program's place.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import wire

LANES = wire.LANES


@dataclasses.dataclass
class Upload:
    cid: int
    version: int
    bits: int
    packed: np.ndarray  # (rows, 128 * bits // 8) uint8, as on the wire
    norms: np.ndarray  # (rows,) f32
    k_train: np.ndarray  # (2,) uint32
    k_enc: np.ndarray  # (2,) uint32


@dataclasses.dataclass
class Block:
    """Consecutive checked flushes, numbered from ``first`` (1-based) in
    the run, and the server's state around them as host arrays of n
    values: ``start`` (x, m, h before the block; None for the seed's),
    ``m1`` (the momentum after the first flush) and ``end`` (x and h after
    the last)."""
    first: int
    start: Optional[Dict[str, np.ndarray]] = None
    uploads: List[List[Upload]] = dataclasses.field(default_factory=list)
    flush_keys: List[np.ndarray] = dataclasses.field(default_factory=list)
    m1: Optional[np.ndarray] = None
    end: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)

    def staleness(self) -> tuple:
        taus = [self.first + i - 1 - up.version
                for i, ups in enumerate(self.uploads) for up in ups]
        return min(taus), max(taus)


@dataclasses.dataclass
class Record:
    """What the program produced in set-up: the checked blocks and every
    broadcast, as (packed, norms), in order."""
    n: int
    sbits: int
    blocks: List[Block] = dataclasses.field(default_factory=list)
    broadcasts: List[tuple] = dataclasses.field(default_factory=list)


@functools.partial(jax.jit, static_argnames=("bounds", "e0"))
def leaf_sumsq(v, base, *, bounds, e0: int = 0):
    """Sum of squares of ``v - base`` over the part of each leaf that lies
    in the block starting at global index ``e0`` (f32 sums)."""
    d = v.astype(jnp.float32) - base.astype(jnp.float32)
    e1 = e0 + d.shape[0]
    sums = []
    for a, b in bounds:
        a2, b2 = max(a, e0), min(b, e1)
        if a2 >= b2:
            sums.append(jnp.float32(0))
        else:
            seg = d[a2 - e0:b2 - e0]
            sums.append(jnp.sum(seg * seg))
    return jnp.stack(sums)


@functools.partial(jax.jit, static_argnames=("bits",))
def unpack_dev(packed, *, bits: int):
    """Packed uint8 (R, 128 * bits // 8) -> codes int32 (R, 128)."""
    per_byte = 8 // bits
    p = packed.astype(jnp.int32)
    shifts = bits * jnp.arange(per_byte, dtype=jnp.int32)
    codes = (p[:, :, None] >> shifts) & ((1 << bits) - 1)
    return codes.reshape(p.shape[0], LANES)


@jax.jit
def _mismatch(a, b):
    return jnp.sum(a != b)


def _pad_rows(v, rows: int):
    pad = rows * LANES - v.shape[0]
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
    return v.reshape(rows, LANES)


class _Gaps:
    """Accumulates the compared numbers of one side against the
    reference."""

    def __init__(self, n_uploads: int, n_flushes: int):
        self.up_ref = [[] for _ in range(n_uploads)]
        self.up_oth = [[] for _ in range(n_uploads)]
        self.bc_ref = [[] for _ in range(n_flushes)]
        self.bc_oth = [[] for _ in range(n_flushes)]
        self.up_mis = 0
        self.up_codes = 0
        self.bc_mis = 0
        self.bc_codes = 0

    @staticmethod
    def _norm_gap(ref: List[np.ndarray], oth: List[np.ndarray]) -> tuple:
        """Worst bucket's gap of norms over the larger of its and the
        median bucket's reference norm; with that bucket's row and both
        norms."""
        r = np.concatenate(ref).astype(np.float64)
        o = np.concatenate(oth).astype(np.float64)
        scale = np.maximum(r, np.median(r))
        if not (scale > 0).any():
            return (0.0 if np.array_equal(r, o) else 1.0), 0, 0.0, 0.0
        gap = np.where(scale > 0, np.abs(o - r) / np.where(scale > 0, scale,
                                                           1.0), 0.0)
        i = int(gap.argmax())
        return float(gap[i]), i, float(r[i]), float(o[i])

    @staticmethod
    def _l2_gap(ref: List[np.ndarray], oth: List[np.ndarray]) -> float:
        """Gap of a whole message's norm over the reference's."""
        r = math.sqrt(sum(float(np.sum(np.square(v, dtype=np.float64)))
                          for v in ref))
        o = math.sqrt(sum(float(np.sum(np.square(v, dtype=np.float64)))
                          for v in oth))
        return abs(o - r) / r if r > 0 else float(o != r)

    def numbers(self) -> Dict[str, float]:
        up = [self._norm_gap(r, o) for r, o in zip(self.up_ref, self.up_oth)]
        bc = [self._norm_gap(r, o) for r, o in zip(self.bc_ref, self.bc_oth)]
        return {
            "upload_norm_gap": max((g[0] for g in up), default=0.0),
            "upload_l2_gap": max((self._l2_gap(r, o) for r, o in
                                  zip(self.up_ref, self.up_oth)),
                                 default=0.0),
            "upload_code_mismatch": self.up_mis / max(self.up_codes, 1),
            "broadcast_norm_gap": max((g[0] for g in bc), default=0.0),
            "broadcast_code_mismatch": self.bc_mis / max(self.bc_codes, 1),
        }

    def worst(self) -> Dict[str, Any]:
        """Where the worst bucket gaps sit: (checked upload or flush in
        order, row, reference norm, other norm)."""
        out = {}
        for name, pairs in (("upload", zip(self.up_ref, self.up_oth)),
                            ("broadcast", zip(self.bc_ref, self.bc_oth))):
            gaps = [self._norm_gap(r, o) for r, o in pairs]
            if gaps:
                j = max(range(len(gaps)), key=lambda i: gaps[i][0])
                out[name] = [j, *gaps[j][1:]]
        return out


@jax.jit
def _row_norms(ref_end, start, other_end):
    """Per 128-value row: the norm of the reference's change, and the norm
    of the other side's end state less the reference's."""
    r = (ref_end - start.astype(ref_end.dtype)).astype(jnp.float32)
    d = other_end.astype(jnp.float32) - ref_end.astype(jnp.float32)
    r, d = r.reshape(-1, LANES), d.reshape(-1, LANES)
    return jnp.sqrt(jnp.sum(r * r, axis=1)), jnp.sqrt(jnp.sum(d * d, axis=1))


def _leaf_gap(ref_sq: np.ndarray, oth_sq: np.ndarray,
              keep: np.ndarray) -> float:
    """Worst leaf's gap between the two norms, against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    r = np.sqrt(np.maximum(ref_sq, 0.0))
    o = np.sqrt(np.maximum(oth_sq, 0.0))
    if not keep.any():
        return 0.0
    scale = np.maximum(r, np.median(r[keep]))
    scale = np.where(scale > 0, scale, 1.0)
    return float((np.abs(o - r) / scale)[keep].max())


def _history(rec: Record, versions: set, x0, r0: int, r1: int, sides):
    """x-hat of each needed version on rows [r0, r1), per side: the
    seed's weights plus that side's decode of each broadcast since."""
    out = {s: {} for s in sides}
    h = {s: x0.astype(dt) for s, dt in sides.items()}
    for v in range(max(versions) + 1):
        if v:
            pk, pn = rec.broadcasts[v - 1]
            codes = unpack_dev(jnp.asarray(pk[r0:r1]), bits=rec.sbits)
            for s, dt in sides.items():
                h[s] = h[s] + wire.decode_rows(
                    codes, jnp.asarray(pn[r0:r1]), bits=rec.sbits,
                    dtype=dt).reshape(-1)
        if v in versions:
            for s in sides:
                out[s][v] = h[s]
    return out


def replay(rec: Record, model, cfg: dict, qcfg: dict, data, init_flat,
           unflatten, bounds: tuple, block_rows: int,
           control=None) -> Dict[str, Any]:
    """Replay the recorded blocks in float32 at the highest precision and
    compare. ``control`` (a dtype) replaces the program's outputs by the
    same replay in that precision. Returns the compared numbers, how many
    leaves the leaf gaps left out and each block's staleness range."""
    n = rec.n
    rows = wire.rows_for(n)
    if model.SEPARABLE:
        chunks = [(r, min(r + block_rows, rows))
                  for r in range(0, rows, block_rows)]
    else:
        chunks = [(0, rows)]
    sides = {"ref": jnp.float32}
    if control is not None:
        sides["ctl"] = control
    flat = [(blk, ups) for blk in rec.blocks for ups in blk.uploads]
    gaps = _Gaps(sum(len(u) for _, u in flat), len(flat))
    versions = {up.version for _, ups in flat for up in ups}
    nl = len(bounds)
    names = ("m1", "x", "h")
    sq = {s: [{k: np.zeros(nl) for k in names} for _ in rec.blocks]
          for s in list(sides) + ["prog"]}
    rows = [{name: ([], []) for name in ("x", "h")} for _ in rec.blocks]
    beta, lr, k = qcfg["server_momentum"], qcfg["server_lr"], qcfg["buffer_size"]
    delta_fns = {s: jax.jit(functools.partial(
        model.ref_client_delta, unflatten=unflatten, cfg=cfg, qcfg=qcfg,
        dtype=dt)) for s, dt in sides.items()}

    def host_rows(v, e0, e1, r0, r1):
        return _pad_rows(jnp.asarray(v[e0:e1]), r1 - r0).reshape(-1)

    with jax.default_matmul_precision("highest"):
        for r0, r1 in chunks:
            e0, e1 = r0 * LANES, min(r1 * LANES, n)
            x0 = _pad_rows(init_flat(e0, e1), r1 - r0).reshape(-1)
            hist = _history(rec, versions, x0, r0, r1, sides)
            j = f_all = 0
            for b, blk in enumerate(rec.blocks):
                if blk.start is None:
                    start = {"x": x0, "m": jnp.zeros_like(x0), "h": x0}
                else:
                    start = {name: host_rows(v, e0, e1, r0, r1)
                             for name, v in blk.start.items()}
                st = {s: {name: v.astype(dt) for name, v in start.items()}
                      for s, dt in sides.items()}
                for i, ups in enumerate(blk.uploads):
                    f = blk.first + i
                    for up in ups:
                        batch = model.client_batch(data, cfg, up.cid)
                        outs = {}
                        for s, dt in sides.items():
                            hv = hist[s][up.version]
                            hv = hv if model.SEPARABLE else hv[:n]
                            delta = delta_fns[s](hv, batch,
                                                 jnp.asarray(up.k_train))
                            outs[s] = wire.encode_rows(
                                _pad_rows(delta, r1 - r0),
                                jnp.asarray(up.k_enc), r0, bits=up.bits,
                                dtype=dt)
                        if control is None:
                            oc = unpack_dev(jnp.asarray(up.packed[r0:r1]),
                                            bits=up.bits)
                            on = up.norms[r0:r1]
                        else:
                            oc, on = outs["ctl"]
                        gaps.up_ref[j].append(np.asarray(outs["ref"][1]))
                        gaps.up_oth[j].append(np.asarray(on, np.float32))
                        gaps.up_mis += int(_mismatch(outs["ref"][0], oc))
                        gaps.up_codes += (e1 - e0)
                        j += 1
                    seed = jnp.asarray(blk.flush_keys[i])
                    bouts = {}
                    for s, dt in sides.items():
                        agg = jnp.zeros_like(x0, dt)
                        for up in ups:
                            tau = (f - 1) - up.version
                            w = 1.0 / math.sqrt(1.0 + tau) / k
                            q = wire.decode_rows(
                                unpack_dev(jnp.asarray(up.packed[r0:r1]),
                                           bits=up.bits),
                                jnp.asarray(up.norms[r0:r1]), bits=up.bits,
                                dtype=dt).reshape(-1)
                            agg = agg + jnp.asarray(w, dt) * q
                        m = beta * st[s]["m"] + agg
                        x = st[s]["x"] + lr * m
                        bouts[s] = wire.encode_rows(
                            (x - st[s]["h"]).reshape(-1, LANES), seed, r0,
                            bits=rec.sbits, dtype=dt)
                        st[s]["x"], st[s]["m"] = x, m
                        if i == 0:
                            sq[s][b]["m1"] += np.asarray(leaf_sumsq(
                                agg[:e1 - e0], jnp.zeros((), dt),
                                bounds=bounds, e0=e0), np.float64)
                    if control is None:
                        pk, pn = rec.broadcasts[f - 1]
                        oc = unpack_dev(jnp.asarray(pk[r0:r1]),
                                        bits=rec.sbits)
                        on = pn[r0:r1]
                    else:
                        oc, on = bouts["ctl"]
                    # x-hat moves by the broadcast that went out, as every
                    # replica's does: a code that rounds the other way at
                    # its dither threshold must not carry into the next
                    # flush's x - x-hat
                    for s, dt in sides.items():
                        st[s]["h"] = st[s]["h"] + wire.decode_rows(
                            oc, jnp.asarray(on), bits=rec.sbits,
                            dtype=dt).reshape(-1)
                    gaps.bc_ref[f_all].append(np.asarray(bouts["ref"][1]))
                    gaps.bc_oth[f_all].append(np.asarray(on, np.float32))
                    gaps.bc_mis += int(_mismatch(bouts["ref"][0], oc))
                    gaps.bc_codes += (e1 - e0)
                    f_all += 1
                ends = {s: {"x": st[s]["x"], "h": st[s]["h"]} for s in sides}
                if control is None:
                    prog = {name: host_rows(v, e0, e1, r0, r1)
                            for name, v in blk.end.items()}
                    ends["prog"] = prog
                    m1 = host_rows(blk.m1, e0, e1, r0, r1)
                    sq["prog"][b]["m1"] += np.asarray(leaf_sumsq(
                        m1[:e1 - e0], beta * start["m"][:e1 - e0],
                        bounds=bounds, e0=e0), np.float64)
                for s, end in ends.items():
                    for name in ("x", "h"):
                        sq[s][b][name] += np.asarray(leaf_sumsq(
                            end[name][:e1 - e0], start[name][:e1 - e0],
                            bounds=bounds, e0=e0), np.float64)
                oth = ends["ctl" if control is not None else "prog"]
                for name in ("x", "h"):
                    ref_rows, gap_rows = _row_norms(
                        ends["ref"][name], start[name], oth[name])
                    rows[b][name][0].append(np.asarray(ref_rows))
                    rows[b][name][1].append(np.asarray(gap_rows))
                del st, ends
            del hist
    numbers = gaps.numbers()
    other = "ctl" if control is not None else "prog"
    left_out = 0
    labels = (("first_update_gap", "m1"), ("x_change_gap", "x"),
              ("h_change_gap", "h"))
    numbers.update({label: 0.0 for label, _ in labels})
    for b in range(len(rec.blocks)):
        ref = sq["ref"][b]
        m1 = np.sqrt(ref["m1"])
        keep = m1 >= 1e-3 * np.median(m1)
        left_out = max(left_out, int((~keep).sum()))
        for label, name in labels:
            numbers[label] = max(numbers[label], _leaf_gap(
                ref[name], sq[other][b][name], keep))
    worst = gaps.worst()
    for name in ("x", "h"):
        label = f"{name}_row_gap"
        numbers[label] = 0.0
        for b, blk_rows in enumerate(rows):
            ref_rows = np.concatenate(blk_rows[name][0]).astype(np.float64)
            gap_rows = np.concatenate(blk_rows[name][1]).astype(np.float64)
            scale = np.maximum(ref_rows, np.median(ref_rows))
            gap = np.where(scale > 0, gap_rows / np.where(scale > 0, scale,
                                                          1.0), 0.0)
            i = int(gap.argmax())
            if gap[i] >= numbers[label]:
                numbers[label] = float(gap[i])
                worst[label] = [b, i, float(gap_rows[i]), float(ref_rows[i])]
    return {"numbers": numbers, "leaves_left_out": left_out, "leaves": nl,
            "staleness": [blk.staleness() for blk in rec.blocks],
            "worst": worst}
