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
the wire. The reference then replays the blocks in plain ``jax.numpy``, in
two phases:

* the client phase: the uploads of both blocks in ascending version order
  (stably by their recorded order). Each side keeps one running x-hat,
  the replicas' decode: the seed's weights plus the reference's own decode
  of each broadcast since, moved forward one broadcast at a time. Each
  upload is trained again from it at the upload's version, on the
  client's batches, and encoded with the upload's dither seed; its bucket
  norms and codes are compared with the wire's. A separable model runs
  this phase per block of rows; any other model on the whole vector,
  holding one x-hat per side, one upload's training and its encode;
* the flush phase, per block of rows for every model, since all of it is
  elementwise or per 128-value row: from the state before the block (the
  seed's for the first block, the server's for the steady one), the K
  uploads, as they crossed the wire, are dequantized, weighted by
  1/sqrt(1 + tau) / K and summed; the momentum, the server step and the
  broadcast encode of x - x-hat follow, and x-hat moves by the broadcast
  that went out, as a replica's does; the broadcasts' norms and codes are
  compared, the first flush's staleness-weighted sum and the block's
  changes of x and x-hat per leaf, and x and x-hat after the block per
  row of 128 values. It reads only the wire's uploads and broadcasts, the
  blocks' recorded states and the seed's weights on those rows.

Upload numbers: ``upload_norm_gap`` (the worst bucket), ``upload_l2_gap``
(the worst upload's whole-message norm) and ``upload_code_mismatch``. A
cell's limits file names the numbers it compares.

Nothing of the system under test is imported here. The rows of a block
are the configuration's ``reference_block_rows`` (all rows where it is
not set), so the reference fits beside nothing; neither phase holds more
than one x-hat per side, whatever the number of versions in use. With
``control`` the same replay runs a second time in that lower precision,
and the control's outputs take the program's place.
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


def _hats(rec: Record, x0, r0: int, r1: int, sides):
    """Each side's running x-hat on rows [r0, r1), at versions 0, 1, 2, ...
    in turn: the seed's weights ``x0``, then that side's decode of each
    recorded broadcast added in order. Yields the same dict each time,
    holding only the current version."""
    h = {s: x0.astype(dt) for s, dt in sides.items()}
    del x0
    yield h
    for pk, pn in rec.broadcasts:
        codes = unpack_dev(jnp.asarray(pk[r0:r1]), bits=rec.sbits)
        for s, dt in sides.items():
            h[s] = h[s] + wire.decode_rows(
                codes, jnp.asarray(pn[r0:r1]), bits=rec.sbits,
                dtype=dt).reshape(-1)
        yield h


class _Replay:
    """One replay of a record against the program, or against the control
    in ``control``'s precision: the two phases over rows [r0, r1), and
    what they have compared so far."""

    def __init__(self, rec: Record, model, cfg: dict, qcfg: dict, data,
                 init_flat, unflatten, bounds: tuple, control):
        self.rec, self.model, self.cfg, self.qcfg = rec, model, cfg, qcfg
        self.data, self.init_flat, self.bounds = data, init_flat, bounds
        self.control = control
        self.sides = {"ref": jnp.float32}
        if control is not None:
            self.sides["ctl"] = control
        self.uploads = [up for blk in rec.blocks for ups in blk.uploads
                        for up in ups]
        self.gaps = _Gaps(len(self.uploads),
                          sum(len(blk.uploads) for blk in rec.blocks))
        nl = len(bounds)
        self.sq = {s: [{k: np.zeros(nl) for k in ("m1", "x", "h")}
                       for _ in rec.blocks]
                   for s in list(self.sides) + ["prog"]}
        self.row_gaps = [{name: ([], []) for name in ("x", "h")}
                         for _ in rec.blocks]
        self.delta_fns = {s: jax.jit(functools.partial(
            model.ref_client_delta, unflatten=unflatten, cfg=cfg, qcfg=qcfg,
            dtype=dt)) for s, dt in self.sides.items()}

    def _elems(self, r0: int, r1: int) -> tuple:
        return r0 * LANES, min(r1 * LANES, self.rec.n)

    def _x0(self, r0: int, r1: int):
        e0, e1 = self._elems(r0, r1)
        return _pad_rows(self.init_flat(e0, e1), r1 - r0).reshape(-1)

    def client_phase(self, r0: int, r1: int) -> None:
        """Every upload, in ascending version order (stably by recorded
        order), trained again from the running x-hat at its version and
        encoded with its dither seed; its numbers go in at its recorded
        index."""
        rec, model, gaps = self.rec, self.model, self.gaps
        e0, e1 = self._elems(r0, r1)
        hats = _hats(rec, self._x0(r0, r1), r0, r1, self.sides)
        hat, version = next(hats), 0
        order = sorted(range(len(self.uploads)),
                       key=lambda j: self.uploads[j].version)
        for j in order:
            up = self.uploads[j]
            while version < up.version:
                hat, version = next(hats), version + 1
            batch = model.client_batch(self.data, self.cfg, up.cid)
            outs = {}
            for s, dt in self.sides.items():
                hv = hat[s] if model.SEPARABLE else hat[s][:rec.n]
                delta = self.delta_fns[s](hv, batch, jnp.asarray(up.k_train))
                del hv
                outs[s] = wire.encode_rows(
                    _pad_rows(delta, r1 - r0), jnp.asarray(up.k_enc), r0,
                    bits=up.bits, dtype=dt)
                del delta
            if self.control is None:
                oc = unpack_dev(jnp.asarray(up.packed[r0:r1]), bits=up.bits)
                on = up.norms[r0:r1]
            else:
                oc, on = outs["ctl"]
            gaps.up_ref[j].append(np.asarray(outs["ref"][1]))
            gaps.up_oth[j].append(np.asarray(on, np.float32))
            gaps.up_mis += int(_mismatch(outs["ref"][0], oc))
            gaps.up_codes += (e1 - e0)
            del outs, oc, on

    def flush_phase(self, r0: int, r1: int) -> None:
        """Every flush from its block's recorded start: the K uploads as
        they crossed the wire, weighted and summed, the momentum, the server
        step and the broadcast encode; x-hat moves by the broadcast that
        went out. Elementwise or per row, so it runs on any row block."""
        rec, gaps, sides, bounds = self.rec, self.gaps, self.sides, self.bounds
        e0, e1 = self._elems(r0, r1)
        beta = self.qcfg["server_momentum"]
        lr, k = self.qcfg["server_lr"], self.qcfg["buffer_size"]
        x0 = self._x0(r0, r1)

        def host_rows(v):
            return _pad_rows(jnp.asarray(v[e0:e1]), r1 - r0).reshape(-1)

        f_all = 0
        for b, blk in enumerate(rec.blocks):
            if blk.start is None:
                start = {"x": x0, "m": jnp.zeros_like(x0), "h": x0}
            else:
                start = {name: host_rows(v) for name, v in blk.start.items()}
            st = {s: {name: v.astype(dt) for name, v in start.items()}
                  for s, dt in sides.items()}
            for i, ups in enumerate(blk.uploads):
                f = blk.first + i
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
                        self.sq[s][b]["m1"] += np.asarray(leaf_sumsq(
                            agg[:e1 - e0], jnp.zeros((), dt),
                            bounds=bounds, e0=e0), np.float64)
                if self.control is None:
                    pk, pn = rec.broadcasts[f - 1]
                    oc = unpack_dev(jnp.asarray(pk[r0:r1]), bits=rec.sbits)
                    on = pn[r0:r1]
                else:
                    oc, on = bouts["ctl"]
                # x-hat moves by the broadcast that went out, as every
                # replica's does: a code that rounds the other way at its
                # dither threshold must not carry into the next flush's
                # x - x-hat
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
            if self.control is None:
                ends["prog"] = {name: host_rows(v)
                                for name, v in blk.end.items()}
                m1 = host_rows(blk.m1)
                self.sq["prog"][b]["m1"] += np.asarray(leaf_sumsq(
                    m1[:e1 - e0], beta * start["m"][:e1 - e0],
                    bounds=bounds, e0=e0), np.float64)
            for s, end in ends.items():
                for name in ("x", "h"):
                    self.sq[s][b][name] += np.asarray(leaf_sumsq(
                        end[name][:e1 - e0], start[name][:e1 - e0],
                        bounds=bounds, e0=e0), np.float64)
            oth = ends["ctl" if self.control is not None else "prog"]
            for name in ("x", "h"):
                ref_rows, gap_rows = _row_norms(
                    ends["ref"][name], start[name], oth[name])
                self.row_gaps[b][name][0].append(np.asarray(ref_rows))
                self.row_gaps[b][name][1].append(np.asarray(gap_rows))
            del st, ends

    def result(self) -> Dict[str, Any]:
        numbers = self.gaps.numbers()
        other = "ctl" if self.control is not None else "prog"
        left_out = 0
        labels = (("first_update_gap", "m1"), ("x_change_gap", "x"),
                  ("h_change_gap", "h"))
        numbers.update({label: 0.0 for label, _ in labels})
        for b in range(len(self.rec.blocks)):
            ref = self.sq["ref"][b]
            m1 = np.sqrt(ref["m1"])
            keep = m1 >= 1e-3 * np.median(m1)
            left_out = max(left_out, int((~keep).sum()))
            for label, name in labels:
                numbers[label] = max(numbers[label], _leaf_gap(
                    ref[name], self.sq[other][b][name], keep))
        worst = self.gaps.worst()
        for name in ("x", "h"):
            label = f"{name}_row_gap"
            numbers[label] = 0.0
            for b, blk_rows in enumerate(self.row_gaps):
                ref_rows = np.concatenate(blk_rows[name][0]).astype(
                    np.float64)
                gap_rows = np.concatenate(blk_rows[name][1]).astype(
                    np.float64)
                scale = np.maximum(ref_rows, np.median(ref_rows))
                gap = np.where(scale > 0, gap_rows / np.where(
                    scale > 0, scale, 1.0), 0.0)
                i = int(gap.argmax())
                if gap[i] >= numbers[label]:
                    numbers[label] = float(gap[i])
                    worst[label] = [b, i, float(gap_rows[i]),
                                    float(ref_rows[i])]
        return {"numbers": numbers, "leaves_left_out": left_out,
                "leaves": len(self.bounds),
                "staleness": [blk.staleness() for blk in self.rec.blocks],
                "worst": worst}


def replay(rec: Record, model, cfg: dict, qcfg: dict, data, init_flat,
           unflatten, bounds: tuple, block_rows: int,
           control=None) -> Dict[str, Any]:
    """Replay the recorded blocks in float32 at the highest precision and
    compare: the client phase over blocks of ``block_rows`` rows for a
    separable model and over the whole vector otherwise, then the flush
    phase over blocks of ``block_rows`` rows. ``control`` (a dtype)
    replaces the program's outputs by the same replay in that precision.
    Returns the compared numbers, how many leaves the leaf gaps left out
    and each block's staleness range."""
    rows = wire.rows_for(rec.n)
    blocks = [(r, min(r + block_rows, rows))
              for r in range(0, rows, block_rows)]
    rp = _Replay(rec, model, cfg, qcfg, data, init_flat, unflatten, bounds,
                 control)
    with jax.default_matmul_precision("highest"):
        for r0, r1 in (blocks if model.SEPARABLE else [(0, rows)]):
            rp.client_phase(r0, r1)
        for r0, r1 in blocks:
            rp.flush_phase(r0, r1)
    return rp.result()
