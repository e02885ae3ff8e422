"""Wire protocol: message framing and exact byte accounting.

Every client->server upload and server->client broadcast in the host-level
simulator is a ``Message`` carrying a real encoded payload — a single
contiguous packed buffer per message (uint8 qsgd codes + bucket norms, or
sparse index/value pairs for top_k/rand_k) produced by ``Quantizer.encode``.
The byte model matches the paper's Appendix E tables applied to the whole
flattened model: ``n bits / coordinate + one fp32 norm per 128-coordinate
bucket`` for n-bit qsgd, and ``64 bits / kept coordinate`` for top_k /
rand_k. Because the packed format shares bucket norms across leaf
boundaries, its exact size is <= the per-leaf sum (equal when every leaf is
bucket-aligned).

Broadcasts fan out: one encoded server message is delivered to every
concurrently active client, so ``TrafficMeter.record`` takes the receiver
count and ``broadcast_MB`` accounts bytes actually sent on the downlink.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import numpy as np

from repro.core.quantizers import (Quantizer, TreeLayout,
                                   packed_identity_payload,
                                   packed_lowrank_payload,
                                   packed_qsgd_payload)
from repro.obs import spans

CLIENT_UPDATE = "client_update"
HIDDEN_BROADCAST = "hidden_broadcast"


@dataclasses.dataclass
class Message:
    kind: str
    payload: Any  # Quantizer.encode(...) packed dict (or legacy per-leaf dict)
    wire_bytes: float
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)


def encode_message(kind: str, quantizer: Quantizer, tree, key, *,
                   fast: bool = False, **meta) -> Message:
    """Frame one encoded pytree. ``fast=True`` routes through the batched
    kernel entry's in-kernel dither (``Quantizer.encode_fast``) — same wire
    format, used on the server's flush hot path."""
    enc = quantizer.encode_fast(tree, key) if fast else quantizer.encode(tree, key)
    return Message(kind=kind, payload=enc,
                   wire_bytes=quantizer.wire_bytes_packed(enc["layout"]),
                   meta=dict(meta))


def encode_message_flat(kind: str, quantizer: Quantizer, flat, layout, key, *,
                        fast: bool = False, **meta) -> Message:
    """Flat-first framing: encode an already-flat f32 vector (the server's
    device-resident representation) without materializing a tree view."""
    enc = (quantizer.encode_fast_flat(flat, layout, key) if fast
           else quantizer.encode_flat(flat, layout, key))
    return Message(kind=kind, payload=enc,
                   wire_bytes=quantizer.wire_bytes_packed(layout),
                   meta=dict(meta))


def frame_packed_message(kind: str, quantizer: Quantizer, enc: dict,
                         **meta) -> Message:
    """Frame an already-encoded packed payload (e.g. the broadcast bits
    produced inside the fused ``server_flush_step``) as a wire Message."""
    return Message(kind=kind, payload=enc,
                   wire_bytes=quantizer.wire_bytes_packed(enc["layout"]),
                   meta=dict(meta))


def _to_host(*arrays) -> List[np.ndarray]:
    """Host numpy copies of device arrays, their bytes added to the
    host-link counter ``spans.d2h_bytes``."""
    out = [np.asarray(a) for a in arrays]
    spans.d2h_bytes += sum(a.nbytes for a in out)
    return out


def payloads_from_fused(quantizer: Quantizer, out: dict, layout: TreeLayout,
                        enc_keys=None, *, count: Optional[int] = None,
                        to_numpy: bool = False, basis_seed=None) -> List[dict]:
    """Slice per-member wire payload dicts out of one fused cohort
    train+encode output (``kernels.ops.cohort_train_encode_step``).

    ``count`` limits slicing to the first N rows — tier groups are
    mask-padded to the full cohort size, and the padding rows past the
    group's real members must not be encoded (for sparse kinds each row is
    a real argsort/choice dispatch). ``to_numpy=True`` converts the batch
    to host numpy ONCE so the per-member payloads are views (no
    per-message device ops), counting the copied bytes in
    ``repro.obs.spans.d2h_bytes`` — the cohort engine's mode; the
    sequential b=1 caller keeps device arrays. Sparse quantizers
    (data-dependent wire shapes) encode each member's flat row eagerly
    through the existing ``encode_flat`` with its per-member key.
    """
    n = layout.total_size
    kind = quantizer.spec.kind
    if kind == "qsgd":
        packed, norms = out["packed"], out["norms"]
        if to_numpy:
            packed, norms = _to_host(packed, norms)
        count = packed.shape[0] if count is None else count
        return [packed_qsgd_payload(packed[i], norms[i], quantizer.spec.bits,
                                    n, layout)
                for i in range(count)]
    if kind == "lowrank":
        if basis_seed is None:
            raise ValueError("lowrank payloads need the round's basis_seed")
        packed, norms = out["packed"], out["norms"]
        if to_numpy:
            packed, norms = _to_host(packed, norms)
        seed = np.asarray(basis_seed).reshape(-1)[:2].astype(np.uint32)
        rank = quantizer.spec.rank(n)
        count = packed.shape[0] if count is None else count
        return [packed_lowrank_payload(packed[i], norms[i],
                                       quantizer.spec.bits, n, layout, rank,
                                       quantizer.spec.group, seed)
                for i in range(count)]
    flat = out["flat"]
    count = flat.shape[0] if count is None else count
    if kind == "identity":
        if to_numpy:
            flat, = _to_host(flat)
        return [packed_identity_payload(flat[i], n, layout)
                for i in range(count)]
    return [quantizer.encode_flat(flat[i], layout, enc_keys[i])
            for i in range(count)]


def frame_cohort_messages(kind: str, quantizer: Quantizer, out: dict,
                          layout: TreeLayout, enc_keys=None, *,
                          version: int = 0, count: Optional[int] = None,
                          to_numpy: bool = False,
                          basis_seed=None) -> List[Message]:
    """Frame one fused cohort output as wire Messages (shared wire size,
    shared model ``version``) — the only step between the single fused
    dispatch and ``QAFeL.receive``. ``count`` limits framing to a mask-
    padded tier group's real members. ``basis_seed`` is required for
    lowrank cohorts (rides the payload so the server can expand)."""
    wire = quantizer.wire_bytes_packed(layout)
    return [Message(kind=kind, payload=enc, wire_bytes=wire,
                    meta={"version": version})
            for enc in payloads_from_fused(quantizer, out, layout, enc_keys,
                                           count=count, to_numpy=to_numpy,
                                           basis_seed=basis_seed)]


def packed_qsgd_chunk_payload(packed_c, norms_c, bits: int, n: int,
                              layout: TreeLayout, *, row0: int, seq: int,
                              last: bool) -> dict:
    """One streamed segment of a packed qsgd upload: ``packed_c`` /
    ``norms_c`` are the wire rows ``[row0, row0 + len(norms_c))`` of the
    full ``(rows_for(n), ...)`` message. The chunk is self-describing
    (bits / n / layout ride on every chunk) so a receiver can validate it
    against its buffer window before any chunk mutates state."""
    return {"format": "packed_chunk", "kind": "qsgd", "packed": packed_c,
            "norms": norms_c, "bits": bits, "n": n, "layout": layout,
            "row0": int(row0), "rows": int(norms_c.shape[0]),
            "seq": int(seq), "last": bool(last)}


def frame_chunk_messages(kind: str, quantizer: Quantizer, chunks: List[dict],
                         layout: TreeLayout, *, version: int = 0,
                         stream: int = 0) -> List[Message]:
    """Frame the streamed chunks of ONE upload as wire Messages.

    Per-chunk wire bytes are the chunk's packed codes + one fp32 norm per
    row; the LAST chunk absorbs the rounding remainder so the stream's
    total is EXACTLY ``wire_bytes_packed(layout)`` — byte accounting is
    conserved against the unstreamed message, chunk count notwithstanding.
    """
    total = quantizer.wire_bytes_packed(layout)
    msgs, spent = [], 0.0
    for ch in chunks:
        wire = (total - spent if ch["last"]
                else float(ch["packed"].size + 4 * ch["rows"]))
        spent += wire
        msgs.append(Message(kind=kind, payload=ch, wire_bytes=wire,
                            meta={"version": version, "stream": stream}))
    return msgs


def payload_wire_bytes(enc) -> Optional[float]:
    """Exact framed bytes of ONE packed payload, derived from the payload
    itself (it is self-describing) rather than a full-model layout estimate.

    This is what keeps mixed-kind fill windows honest: a lowrank upload is
    a ``rank``-length wire message regardless of the d-length model it
    sketches, and a bit-width-tier client's message is priced at ITS bits,
    not the server quantizer's. Returns None for payloads that don't
    self-describe (legacy per-leaf dicts) — callers fall back to the
    framing-time estimate."""
    if not isinstance(enc, dict) or enc.get("format") != "packed":
        return None
    kind = enc.get("kind")
    if kind == "lowrank":
        r = int(enc["rank"])
        return (enc["bits"] * r + 32 * math.ceil(r / 128)) / 8.0
    if kind == "qsgd":
        n = int(enc["n"])
        return (enc["bits"] * n + 32 * math.ceil(n / 128)) / 8.0
    if kind == "identity":
        return 32 * int(enc["n"]) / 8.0
    if "idx" in enc:  # sparse: 32-bit index + 32-bit value per kept coord
        return 64 * int(np.asarray(enc["idx"]).shape[-1]) / 8.0
    return None


def payload_kind_label(enc) -> str:
    """Stable per-kind bucket label for traffic accounting ("qsgd4",
    "lowrank4g32", "identity", ...)."""
    if not isinstance(enc, dict):
        return "tree"
    kind = enc.get("kind")
    if kind == "lowrank":
        return f"lowrank{enc['bits']}g{enc['group']}"
    if kind == "qsgd":
        return f"qsgd{enc['bits']}"
    if kind is not None:
        return str(kind)
    return "sparse" if "idx" in enc else "other"


def decode_message(quantizer: Quantizer, msg: Message):
    return quantizer.decode(msg.payload)


def decode_message_flat(quantizer: Quantizer, msg: Message, mesh=None):
    """Decode a packed message to its flat f32 vector (no unflatten);
    ``mesh`` as in ``Quantizer.decode_flat``."""
    return quantizer.decode_flat(msg.payload, mesh=mesh)


@dataclasses.dataclass
class TrafficMeter:
    """Accumulates the paper's communication metrics.

    ``broadcast_bytes`` counts downlink fan-out: a server message delivered
    to ``n_receivers`` concurrent clients costs ``n_receivers *`` its wire
    size. ``broadcast_wire_bytes`` keeps the per-message (single-copy) total
    so kB-per-broadcast stays comparable to the paper's tables.
    """

    uploads: int = 0
    broadcasts: int = 0
    upload_bytes: float = 0.0
    broadcast_bytes: float = 0.0
    broadcast_wire_bytes: float = 0.0
    broadcast_receivers: int = 0
    # uploads rejected by the server's staleness drop policy: the bytes were
    # still spent on the uplink, but the update never entered the buffer
    uploads_dropped: int = 0
    dropped_bytes: float = 0.0
    # per-kind uplink breakdown ("qsgd4", "lowrank4g32", ...): mixed-kind
    # windows (bit-width tiers, lowrank cohorts) must not be averaged into
    # one apples-and-oranges kB_per_upload figure
    uploads_by_kind: Dict[str, int] = dataclasses.field(default_factory=dict)
    upload_bytes_by_kind: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    def _upload_size(self, msg: Message) -> float:
        # Prefer the payload's own self-described size: lowrank / tier
        # messages are smaller than the server quantizer's full-model wire
        # estimate, and streamed chunks conserve their own totals.
        actual = payload_wire_bytes(msg.payload)
        return msg.wire_bytes if actual is None else actual

    def record(self, msg: Message, n_receivers: int = 1):
        if msg.kind == CLIENT_UPDATE:
            wire = self._upload_size(msg)
            self.uploads += 1
            self.upload_bytes += wire
            label = payload_kind_label(msg.payload)
            self.uploads_by_kind[label] = self.uploads_by_kind.get(label, 0) + 1
            self.upload_bytes_by_kind[label] = (
                self.upload_bytes_by_kind.get(label, 0.0) + wire)
        else:
            self.broadcasts += 1
            self.broadcast_bytes += msg.wire_bytes * n_receivers
            self.broadcast_wire_bytes += msg.wire_bytes
            self.broadcast_receivers += n_receivers

    def record_stream(self, enc, stream_bytes: float):
        """One COMPLETE chunked upload (already reassembled): the stream's
        summed framed bytes count as a single upload, bucketed under the
        kind its chunks self-describe (every chunk carries kind/bits)."""
        self.uploads += 1
        self.upload_bytes += stream_bytes
        label = payload_kind_label(enc)
        self.uploads_by_kind[label] = self.uploads_by_kind.get(label, 0) + 1
        self.upload_bytes_by_kind[label] = (
            self.upload_bytes_by_kind.get(label, 0.0) + stream_bytes)

    def record_dropped(self, msg: Message):
        """An upload rejected at the server (e.g. staleness bound exceeded)."""
        self.uploads_dropped += 1
        self.dropped_bytes += self._upload_size(msg)

    def summary(self) -> Dict[str, float]:
        by_kind = {f"kB_per_upload/{k}": self.upload_bytes_by_kind[k] / c / 1e3
                   for k, c in self.uploads_by_kind.items() if c}
        return {
            "uploads": self.uploads,
            "broadcasts": self.broadcasts,
            "upload_MB": self.upload_bytes / 1e6,
            "broadcast_MB": self.broadcast_bytes / 1e6,
            "kB_per_upload": (self.upload_bytes / self.uploads / 1e3) if self.uploads else 0.0,
            **by_kind,
            "kB_per_broadcast": (self.broadcast_wire_bytes / self.broadcasts / 1e3
                                 if self.broadcasts else 0.0),
            "mean_broadcast_fanout": (self.broadcast_receivers / self.broadcasts
                                      if self.broadcasts else 0.0),
            "uploads_dropped": self.uploads_dropped,
            "dropped_MB": self.dropped_bytes / 1e6,
        }
