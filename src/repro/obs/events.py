"""Structured run tracing: typed events, bounded ring, compile counters.

``RunTracer`` is the host-side half of the telemetry substrate: the sim
engines stamp it with the simulated clock (``set_sim_time``) and the
protocol layer (``QAFeL.receive`` / ``_flush``) emits one typed event per
upload, drop, flush and broadcast; engines add eval and compile events.
Events land in a bounded in-memory ring (overflow counted, never raised)
and export as JSONL — one JSON object per line, validated by
``repro.obs.schema``.

``CompileWatch`` turns ``analysis_static.trace_guard.ENTRIES`` — the same
registry the flcheck compiled pass patches — into polling dispatch/compile
counters: each fused entry group's (re)trace counter is snapshotted and the
delta since the last poll reported, so a tracer can record *when* in a run
a fused entry was (re)compiled. Compile events are inherently warm-cache
dependent (a second same-process run recompiles nothing), so they are
excluded from the deterministic-stream comparisons and from ``metrics()``.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import time
from typing import Any, Dict, Iterable, List, Optional

EVENT_KINDS = ("upload", "drop", "flush", "broadcast", "eval", "compile")

# wall-clock fields: excluded when comparing event streams across runs
WALL_CLOCK_FIELDS = ("t_wall",)


@dataclasses.dataclass(frozen=True)
class Event:
    """One typed telemetry event."""

    kind: str  # one of EVENT_KINDS
    seq: int  # emission index, strictly increasing per tracer
    step: int  # server step (model version) at emission
    t_sim: float  # simulated clock (engine-stamped)
    t_wall: float  # host wall clock (time.time())
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> Dict[str, Any]:
        out = {"kind": self.kind, "seq": self.seq, "step": self.step,
               "t_sim": self.t_sim, "t_wall": self.t_wall}
        out.update(self.data)
        return out

    def comparable(self) -> Dict[str, Any]:
        """The event minus its wall-clock fields — what same-seed runs are
        compared on."""
        out = self.as_dict()
        for f in WALL_CLOCK_FIELDS:
            out.pop(f, None)
        return out


class CompileWatch:
    """Polling view of the fused entries' (re)trace counters.

    Built on ``trace_guard.ENTRIES`` so the groups and counters stay the
    single source of truth shared with the flcheck compiled pass.
    """

    def __init__(self):
        from repro.analysis_static.trace_guard import ENTRIES
        self._entries = ENTRIES
        self._last = self.totals()

    def totals(self) -> Dict[str, int]:
        """Current absolute (re)trace count per fused entry group."""
        from repro.kernels import ops as kops
        return {group: int(getattr(kops, counter))
                for group, (_, counter) in self._entries.items()}

    def poll(self) -> Dict[str, int]:
        """(Re)traces per group since the previous poll (zeros omitted)."""
        now = self.totals()
        delta = {g: now[g] - self._last[g] for g in now
                 if now[g] != self._last[g]}
        self._last = now
        return delta


class RunTracer:
    """Typed event ring + time-series registry for one run.

    ``taps`` switches the in-dispatch metric taps on for any algorithm this
    tracer is attached to (``QAFeL(..., telemetry=tracer)``); with
    ``taps=False`` the tracer still records the host-side event stream but
    every fused dispatch keeps its pre-telemetry signature and cost.
    """

    def __init__(self, capacity: int = 65536, *, taps: bool = True,
                 wall_clock=time.time):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.taps = taps
        self.dropped_events = 0  # ring overflow (oldest evicted)
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self._seq = 0
        self._sim_time = 0.0
        self._wall = wall_clock
        self._compiles = CompileWatch()

    # -- clock + emission --------------------------------------------------
    @property
    def sim_time(self) -> float:
        return self._sim_time

    def set_sim_time(self, t: float) -> None:
        self._sim_time = float(t)

    def emit(self, kind: str, *, step: int = 0, **data) -> Event:
        if kind not in EVENT_KINDS:
            raise ValueError(f"unknown event kind {kind!r}; "
                             f"known: {EVENT_KINDS}")
        if len(self._events) == self.capacity:
            self.dropped_events += 1
        ev = Event(kind=kind, seq=self._seq, step=int(step),
                   t_sim=self._sim_time, t_wall=float(self._wall()),
                   data=data)
        self._seq += 1
        self._events.append(ev)
        return ev

    def poll_compiles(self, *, step: int = 0) -> int:
        """Record a compile event per fused entry group (re)traced since
        the last poll; returns the number of events emitted."""
        emitted = 0
        for group, retraces in sorted(self._compiles.poll().items()):
            self.emit("compile", step=step, entry=group, retraces=retraces)
            emitted += 1
        return emitted

    # -- read side ---------------------------------------------------------
    def __len__(self) -> int:
        return len(self._events)

    def events(self, kind: Optional[str] = None) -> List[Event]:
        if kind is None:
            return list(self._events)
        return [e for e in self._events if e.kind == kind]

    def series(self, kind: str, field: str, *,
               subfield: Optional[str] = None) -> List[Any]:
        """Time-series registry: one value per event of ``kind``, pulled
        from ``data[field]`` (or ``data[field][subfield]`` for tap dicts);
        events missing the field are skipped."""
        out = []
        for e in self._events:
            if e.kind != kind or field not in e.data:
                continue
            v = e.data[field]
            if subfield is not None:
                if not isinstance(v, dict) or subfield not in v:
                    continue
                v = v[subfield]
            out.append(v)
        return out

    def counters(self) -> Dict[str, int]:
        """Event counts per kind + the absolute dispatch/compile totals and
        host-link bytes and flush staging counts (``repro.obs.spans``)."""
        from repro.obs import spans
        out = {f"events_{k}": 0 for k in EVENT_KINDS}
        for e in self._events:
            out[f"events_{e.kind}"] += 1
        out["events_evicted"] = self.dropped_events
        for group, total in self._compiles.totals().items():
            out[f"traces_{group}"] = total
        out["d2h_bytes"] = spans.d2h_bytes
        out["h2d_bytes"] = spans.h2d_bytes
        out["flush_stage_allocs"] = spans.flush_stage_allocs
        out["flush_stage_reuses"] = spans.flush_stage_reuses
        return out

    def metrics(self) -> Dict[str, Any]:
        """The deterministic telemetry keys merged into ``metrics()``:
        per-flush and per-upload tap series (tuples, so two runs' metrics
        dicts compare with ``==``). Compile/dispatch counters stay OUT —
        they depend on jit-cache warmth, and same-seed runs are compared on
        full metrics equality."""
        from repro.obs.taps import COHORT_TAP_NAMES, FLUSH_TAP_NAMES
        out: Dict[str, Any] = {}
        flush_taps = self.series("flush", "taps")
        if flush_taps:
            for name in FLUSH_TAP_NAMES:
                out[f"flush/{name}"] = tuple(t[name] for t in flush_taps
                                             if name in t)
        upload_taps = self.series("upload", "taps")
        if upload_taps:
            for name in COHORT_TAP_NAMES:
                out[f"upload/{name}"] = tuple(t[name] for t in upload_taps
                                              if name in t)
        pops = self.series("eval", "population")
        if pops:
            from repro.obs.taps import POPULATION_STATE_NAMES
            for name in POPULATION_STATE_NAMES:
                out[f"population/{name}"] = tuple(p[name] for p in pops
                                                  if name in p)
        return out

    # -- export ------------------------------------------------------------
    def to_jsonl(self, path) -> int:
        """Write the ring as JSONL (one event per line); returns the number
        of events written."""
        events = self.events()
        with open(path, "w") as f:
            for e in events:
                f.write(json.dumps(e.as_dict()) + "\n")
        return len(events)

    def iter_dicts(self) -> Iterable[Dict[str, Any]]:
        for e in self._events:
            yield e.as_dict()
