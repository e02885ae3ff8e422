"""Server-side update buffer (the "Buff" in FedBuff/QAFeL, Algorithm 1).

The buffer is **flat-first**: every accepted upload ultimately lands in the
single flat-f32 coordinate space of the server's ``TreeLayout`` (PR 1/2's
packed wire format already proves that is the natural server
representation), and nothing is ever unflattened inside the buffer.

Two ingestion modes:

* **Tree mode** (``add``): accumulates already-decoded client deltas
  (weighted by staleness scaling) into one flat f32 accumulator — O(1)
  memory in K. ``add_decoded_flat`` is the same thing for callers that
  already hold the flat vector (no tree round-trip).
* **Packed mode** (``add_encoded``, enabled by passing ``quantizer=``):
  stores the K uploads exactly as they arrived on the wire — stacked uint8
  qsgd codes + per-bucket norms (O(K * bits/32) of the f32 footprint), or
  sparse (idx, vals) pairs for top_k/rand_k — and defers ALL dequantization
  to flush time.

Three release surfaces once K samples have arrived:

* ``drain()`` → ``FlushBatch``: the raw ingredients (stacked codes, norms,
  normalized weights, pre-scaled flat residual) for the fused one-dispatch
  ``server_flush_step`` — the aggregation itself happens *inside* the
  server's single jitted flush, so no aggregate is materialized here. A
  window of host-numpy uploads is stacked into one of two reused host
  staging pairs and, with no decoded residual, launches no device op:
  the flush dispatch is the first thing that touches the device.
* ``flush_flat()`` → the aggregated flat f32 Delta-bar (one fused
  dequantize-accumulate kernel pass for qsgd stacks).
* ``flush()`` → the tree view of ``flush_flat()`` — the legacy surface,
  kept for callers that still want pytrees (tests, A/B benchmarks).

All three reset the buffer.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantizers import Quantizer, TreeLayout, flatten_tree
from repro.obs import spans


@dataclasses.dataclass
class FlushBatch:
    """The raw, pre-aggregation contents of one full buffer window.

    ``weights`` is already divided by the normalization denominator and
    ``extra`` (identity/sparse/tree-mode residual) is already scaled by
    1/denom, so the consumer's job is exactly
    ``sum_k weights[k] * dequant(stack[k], norms[k]) + extra``.

    ``weights`` is a host numpy array. When every stacked upload was a
    host numpy array, ``stack`` and ``norms`` are views into one of the
    buffer's two staging pairs: valid until the drain after next, which
    rewrites that pair once the outputs last handed to
    ``UpdateBuffer.fence`` are ready.
    """

    n: int
    layout: TreeLayout
    bits: Optional[int] = None  # qsgd stack bit-width (None when no stack)
    stack: Any = None  # (K, rows, 128*bits//8) uint8 codes, or None
    norms: Any = None  # (K, rows) f32 bucket norms, or None
    weights: Any = None  # (K,) host f32, normalized, or None
    extra: Any = None  # (n,) flat f32 residual, pre-scaled, or None
    # lowrank windows: the stack holds RANK-length subspace wire pairs and
    # every upload carries its own (2,) basis seed (mixed-staleness windows
    # span basis versions)
    kind: Optional[str] = None  # upload kind of the stacked pairs
    seeds: Any = None  # (K, 2) uint32 per-upload basis seeds, or None
    rank: Optional[int] = None  # subspace dimension d_r
    group: Optional[int] = None  # sketch group g (d_r = padded n / g)

    def reduce(self):
        """Aggregate to the flat Delta-bar (the non-fused reference path)."""
        from repro.kernels import ops as kops  # local import: kernels are optional

        if self.stack is not None and self.kind == "lowrank":
            d_pad = kops.rows_for(self.n) * kops.BUCKET
            flat = kops.lowrank_window_delta(
                self.stack, self.norms, self.weights, self.seeds,
                lambda v: v, bits=self.bits, group=self.group,
                y_width=d_pad // self.group, elem0=0, n_out=d_pad)[:self.n]
            return flat if self.extra is None else self.extra + flat
        if self.stack is not None:
            flat = kops.buffer_aggregate(self.stack, self.norms, self.weights,
                                         self.bits, self.n)
            if self.extra is not None:
                flat = self.extra + flat
            return flat
        return self.extra


@dataclasses.dataclass
class UpdateBuffer:
    capacity: int  # K
    quantizer: Optional[Quantizer] = None  # set -> packed mode available
    _acc: Any = None  # tree/flat mode: running flat f32 sum of weighted deltas
    _weightsum: float = 0.0
    count: int = 0
    flushes: int = 0
    # packed mode: raw wire tensors + weights, stacked lazily at flush
    _packed: List[Any] = dataclasses.field(default_factory=list)
    _weights: List[float] = dataclasses.field(default_factory=list)
    _layout: Optional[TreeLayout] = None
    _bits: Optional[int] = None
    _n: Optional[int] = None
    _flat_acc: Any = None  # identity packed mode: flat f32 accumulator
    # lowrank packed mode: per-upload (2,) basis seeds + window sketch shape
    _seeds: List[Any] = dataclasses.field(default_factory=list)
    _rank: Optional[int] = None
    _group: Optional[int] = None
    # host staging of numpy windows (see drain): two (codes, norms) pairs
    # used in turn, and the outputs last handed to fence()
    _stage: List[Any] = dataclasses.field(
        default_factory=lambda: [None, None], repr=False, compare=False)
    _stage_next: int = dataclasses.field(default=0, repr=False,
                                         compare=False)
    _fence: Any = dataclasses.field(default=None, repr=False, compare=False)

    def add(self, delta, weight: float = 1.0) -> None:
        """Tree mode: accumulate an already-decoded delta (flattened here)."""
        flat, layout = flatten_tree(delta)
        self.add_decoded_flat(flat, weight, layout=layout)

    def add_decoded_flat(self, flat, weight: float = 1.0, *,
                         layout: Optional[TreeLayout] = None) -> None:
        """Accumulate an already-decoded *flat f32* delta (no tree view)."""
        if self._layout is None:
            if layout is None:
                raise ValueError("add_decoded_flat into an empty buffer needs "
                                 "a layout (pass layout=, or use add())")
            self._layout = layout
            self._n = int(flat.size)
        elif layout is not None and layout != self._layout:
            raise ValueError("delta layout mismatch: all buffered uploads "
                             "must share the same pytree structure")
        elif int(flat.size) != self._n:
            raise ValueError(f"flat delta size {flat.size} != n={self._n}")
        if self._acc is None:
            self._acc = weight * flat
        else:
            self._acc = weight * flat + self._acc
        self._weightsum += float(weight)
        self.count += 1

    def add_encoded(self, enc: dict, weight: float = 1.0) -> None:
        """Packed mode: store the wire payload itself; no dequantization.

        ``enc`` is a ``Quantizer.encode`` packed message dict. qsgd uploads
        are kept as (codes, norms); top_k/rand_k as (idx, vals); identity
        payloads (already f32 on the wire) fold into a flat accumulator.
        """
        if self.quantizer is None:
            raise RuntimeError("add_encoded requires a quantizer (packed mode)")
        if enc.get("format") != "packed":
            raise ValueError("add_encoded expects a packed message; use "
                             "Quantizer.encode (not encode_leafwise)")
        if enc["kind"] != self.quantizer.spec.kind:
            raise ValueError(f"message kind {enc['kind']!r} does not match "
                             f"buffer quantizer {self.quantizer.spec.kind!r}")
        # validate EVERYTHING before mutating any state, so a rejected
        # message leaves the buffer exactly as it was
        kind = enc["kind"]
        if self._layout is not None:
            if enc["layout"] != self._layout:
                raise ValueError("message layout mismatch: all buffered uploads "
                                 "must encode the same pytree structure")
            if enc.get("bits") != self._bits and self._bits is not None:
                raise ValueError(f"message bits mismatch: {enc.get('bits')} != "
                                 f"{self._bits}")
        if kind == "qsgd":
            from repro.kernels import ops as kops
            if enc["norms"].shape[0] != kops.rows_for(enc["n"]):
                raise ValueError("corrupt qsgd message: norms/rows mismatch")
        if kind == "lowrank":
            from repro.kernels import ops as kops
            spec = self.quantizer.spec
            if enc.get("group") != spec.group:
                raise ValueError(f"lowrank sketch group mismatch: "
                                 f"{enc.get('group')} != {spec.group}")
            if enc.get("rank") != spec.rank(enc["n"]):
                raise ValueError(f"corrupt lowrank message: rank "
                                 f"{enc.get('rank')} != {spec.rank(enc['n'])}")
            if enc["norms"].shape[0] != kops.rows_for(enc["rank"]):
                raise ValueError("corrupt lowrank message: norms/rows "
                                 "mismatch over the rank-length payload")
            seed = np.asarray(enc["seed"], np.uint32).reshape(-1)
            if seed.shape[0] != 2:
                raise ValueError("corrupt lowrank message: basis seed must "
                                 "be (2,) uint32")
            if self._rank is not None and enc["rank"] != self._rank:
                raise ValueError(f"lowrank rank mismatch: {enc['rank']} != "
                                 f"{self._rank}")
        if self._layout is None:
            self._layout = enc["layout"]
            self._n = enc["n"]
        if self._bits is None:
            self._bits = enc.get("bits")

        if kind == "qsgd":
            self._packed.append((enc["packed"], enc["norms"]))
        elif kind == "lowrank":
            self._packed.append((enc["packed"], enc["norms"]))
            self._seeds.append(np.asarray(enc["seed"], np.uint32).reshape(2))
            self._rank = enc["rank"]
            self._group = enc["group"]
        elif kind == "identity":
            if self._flat_acc is None:
                self._flat_acc = enc["payload"] * weight
            else:
                self._flat_acc = self._flat_acc + enc["payload"] * weight
        else:  # top_k / rand_k: wire-sized sparse pairs
            self._packed.append((enc["idx"], enc["vals"]))
        self._weightsum += float(weight)
        self._weights.append(float(weight))
        self.count += 1

    def add_encoded_chunks(self, chunks: List[dict], weight: float = 1.0) -> None:
        """Packed mode: ingest ONE upload that arrived as streamed row
        chunks (``protocol.packed_qsgd_chunk_payload``) — the LLM-scale
        uplink, where no single full packed message ever existed on a
        device. The chunks are validated as a set (same layout/bits/n,
        contiguous gap-free row coverage ``[0, rows_for(n))``) BEFORE any
        state mutates, then assembled into one preallocated host-numpy
        (rows, bytes) + (rows,) pair and stored exactly like an
        ``add_encoded`` qsgd upload — the flush path cannot tell them
        apart."""
        if self.quantizer is None:
            raise RuntimeError("add_encoded_chunks requires a quantizer "
                               "(packed mode)")
        if not chunks:
            raise ValueError("empty chunk stream")
        from repro.kernels import ops as kops
        first = chunks[0]
        if any(ch.get("format") != "packed_chunk" for ch in chunks):
            raise ValueError("add_encoded_chunks expects packed_chunk "
                             "payloads (see protocol.packed_qsgd_chunk_payload)")
        if first["kind"] != "qsgd" or self.quantizer.spec.kind != "qsgd":
            raise ValueError("chunk streaming is defined for qsgd uploads "
                             f"(got {first['kind']!r} into a "
                             f"{self.quantizer.spec.kind!r} buffer)")
        for ch in chunks[1:]:
            if (ch["layout"] != first["layout"] or ch["n"] != first["n"]
                    or ch["bits"] != first["bits"]):
                raise ValueError("inconsistent chunk stream: all chunks must "
                                 "share one layout / n / bits")
        if self._layout is not None:
            if first["layout"] != self._layout:
                raise ValueError("message layout mismatch: all buffered "
                                 "uploads must encode the same pytree "
                                 "structure")
            if self._bits is not None and first["bits"] != self._bits:
                raise ValueError(f"message bits mismatch: {first['bits']} != "
                                 f"{self._bits}")
        rows = kops.rows_for(first["n"])
        ordered = sorted(chunks, key=lambda ch: ch["row0"])
        cover = 0
        for ch in ordered:
            if ch["row0"] != cover:
                raise ValueError(f"chunk stream has a gap/overlap at row "
                                 f"{cover} (next chunk starts at "
                                 f"{ch['row0']})")
            if ch["norms"].shape[0] != ch["rows"] or ch["rows"] <= 0:
                raise ValueError("corrupt chunk: rows/norms mismatch")
            cover += ch["rows"]
        if cover != rows:
            raise ValueError(f"chunk stream covers {cover} rows, message "
                             f"needs {rows}")
        packed = np.empty((rows, ordered[0]["packed"].shape[-1]), np.uint8)
        norms = np.empty((rows,), np.float32)
        for ch in ordered:
            r0, r1 = ch["row0"], ch["row0"] + ch["rows"]
            packed[r0:r1] = np.asarray(ch["packed"])
            norms[r0:r1] = np.asarray(ch["norms"])
        if self._layout is None:
            self._layout = first["layout"]
            self._n = first["n"]
        if self._bits is None:
            self._bits = first["bits"]
        self._packed.append((packed, norms))
        self._weightsum += float(weight)
        self._weights.append(float(weight))
        self.count += 1

    @property
    def full(self) -> bool:
        return self.count >= self.capacity

    @property
    def layout(self) -> Optional[TreeLayout]:
        """The pytree layout of the current fill window (None when empty).
        Exposed so the server can validate uploads against its own layout
        BEFORE ``drain()`` irreversibly resets the window."""
        return self._layout

    def _reset(self) -> None:
        self._acc = None
        self._weightsum = 0.0
        self._packed = []
        self._weights = []
        self._layout = None
        self._bits = None
        self._n = None
        self._flat_acc = None
        self._seeds = []
        self._rank = None
        self._group = None
        self.count = 0
        self.flushes += 1

    def _stage_window(self):
        """Stack the window's host codes and norms into the next staging
        pair and return views of its first m slots (m packed uploads).

        A pair is (K, *codes) and (K, *norms), allocated at its first use
        and again only when the wire shape changes. Before a pair is
        rewritten, the outputs last handed to ``fence`` are waited for: a
        transfer of numpy arguments may still be running after the
        dispatch that read the pair returned."""
        first = [np.asarray(a) for a in self._packed[0]]
        m = len(self._packed)
        k = max(self.capacity, m)
        i = self._stage_next
        pair = self._stage[i]
        if pair is not None and all(s.shape == (k,) + a.shape
                                    and s.dtype == a.dtype
                                    for s, a in zip(pair, first)):
            jax.block_until_ready(self._fence)
            spans.flush_stage_reuses += 1
        else:
            pair = tuple(np.empty((k,) + a.shape, a.dtype) for a in first)
            self._stage[i] = pair
            spans.flush_stage_allocs += 1
        self._stage_next = 1 - i
        codes, norms = pair[0][:m], pair[1][:m]
        np.stack([p for p, _ in self._packed], out=codes)
        np.stack([nm for _, nm in self._packed], out=norms)
        return codes, norms

    def fence(self, outputs) -> None:
        """Hand over the outputs of the dispatch that read the last drain's
        batch: no staging pair is rewritten before they are ready.

        The server hands its new state. Each flush reads the state the one
        before it wrote, so that state is ready only once every earlier
        flush, and the transfer of every window it read, has finished; and
        it is donated only by the next flush, after the next drain. A
        fence holds no device memory beyond the state itself."""
        self._fence = outputs

    def drain(self, *, normalize: str = "capacity") -> FlushBatch:
        """Hand the window's raw ingredients to the fused flush, and reset.

        qsgd uploads come back as one stacked (codes, norms, weights)
        batch; everything else (identity payload accumulator, sparse
        scatter-adds, tree-mode residual) is pre-reduced into one
        pre-scaled flat ``extra`` vector. The op order of the pre-reduction
        matches the eager reference exactly (scaled residual + aggregate).

        ``weights`` is host numpy (the IEEE f32 division of the weights by
        the denominator). A window whose packed uploads are all host numpy
        arrays (the cohort engine's) is stacked into a reused staging pair
        on the host: its ``stack`` and ``norms`` are views that stay valid
        until the drain after next (see ``fence``). Windows of device
        arrays are stacked on the device.
        """
        if not self.full:
            raise RuntimeError(f"flush before full: {self.count}/{self.capacity}")
        denom = (float(self.capacity) if normalize == "capacity"
                 else max(self._weightsum, 1e-12))
        n, layout, bits = self._n, self._layout, self._bits
        kind = self.quantizer.spec.kind if self.quantizer is not None else None

        stack = norms = weights = extra = None
        seeds = rank = group = win_kind = None
        if self._packed and kind in ("qsgd", "lowrank"):
            # Cohort-encoded wire payloads are numpy (host bytes): stack
            # them host-side — one transfer into the kernel call instead of
            # K device stacks. Lowrank stacks are RANK-length wire pairs;
            # the per-upload basis seeds ride along as one (K, 2) array.
            if all(isinstance(p, np.ndarray) for p, _ in self._packed):
                stack, norms = self._stage_window()
            else:
                stack = jnp.stack([p for p, _ in self._packed])
                norms = jnp.stack([nm for _, nm in self._packed])
            weights = np.asarray(self._weights, np.float32) / np.float32(denom)
            win_kind = kind
            if kind == "lowrank":
                seeds = np.stack(self._seeds).astype(np.uint32)
                rank, group = self._rank, self._group
        elif self._packed:  # sparse: scatter-add into one flat sum
            extra = jnp.zeros((n,), jnp.float32)
            for (idx, vals), w in zip(self._packed, self._weights):
                extra = extra.at[idx].add(vals * (w / denom))
        if self._flat_acc is not None:  # identity packed payloads
            flat = self._flat_acc / denom
            extra = flat if extra is None else extra + flat
        if self._acc is not None:
            # decoded (tree/flat-mode) adds landed in the same fill window
            # (e.g. a bit-width-tier client): fold them in, don't drop them
            scaled = (1.0 / denom) * self._acc
            extra = scaled if extra is None else scaled + extra
        batch = FlushBatch(n=n, layout=layout, bits=bits, stack=stack,
                           norms=norms, weights=weights, extra=extra,
                           kind=win_kind, seeds=seeds, rank=rank, group=group)
        self._reset()
        return batch

    def flush_flat(self, *, normalize: str = "capacity"):
        """Return the aggregated flat f32 Delta-bar and reset."""
        flat = self.drain(normalize=normalize).reduce()
        self.fence(flat)
        return flat

    def flush(self, *, normalize: str = "capacity"):
        """Return the aggregate Delta-bar as a tree view and reset.

        normalize: "capacity" -> divide by K (Algorithm 1 line 11);
                   "weights"  -> divide by the sum of staleness weights.
        """
        layout = self._layout
        if layout is None:
            raise RuntimeError(f"flush before full: {self.count}/{self.capacity}")
        return layout.unflatten(self.flush_flat(normalize=normalize))
