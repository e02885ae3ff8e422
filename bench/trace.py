"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``jax.profiler`` writes an XSpace (``*.xplane.pb``). Each TPU is a plane
named ``/device:TPU:<i>``; on it the ``XLA Ops`` line holds one event per
operation the chip ran and the ``XLA Modules`` line one event per program
(``jit_<name>(<id>)``). The host's planes hold the benchmark's own
``TraceAnnotation`` spans (``client_step``, ``receive``, ``flush``) on the
same clock.

From these: the busy time of each chip (the union of its operations'
intervals inside the window), device time per program and per operation
name, and the device's idle gaps, each labelled by the
benchmark span that was open on the host when the gap began (``engine``
when none was: the cohort engine's own code).
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_LABELS = ("client_step", "receive", "flush")


@dataclasses.dataclass
class Interval:
    name: str
    start: int  # ns, trace clock
    end: int


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Interval]
    modules: List[Interval]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    spans: List[Interval]  # the benchmark's host spans


def newest_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _op_name(text: str) -> str:
    """An XLA op event is named by its whole HLO instruction
    (``%fusion.55 = f32[...] fusion(...)``): keep the instruction's own
    name, which is what patterns match."""
    return text.split(" = ", 1)[0]


def _events(line, names=None) -> List[Interval]:
    out = []
    for e in line.events:
        name = e.name
        if names is None or name in names:
            out.append(Interval(_op_name(name), int(e.start_ns),
                                int(e.end_ns)))
    return out


def load(path: Path) -> Trace:
    """Read an ``.xplane.pb`` into device op/module intervals and host
    spans. Only planes and lines this module reads are kept."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
            mods = (_events(lines[MODULES_LINE]) if MODULES_LINE in lines
                    else [])
            devices.append(Device(plane.name, ops, mods))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(_events(ln, HOST_LABELS))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    spans.sort(key=lambda s: s.start)
    return Trace(devices, spans)


def merge(intervals: Sequence[Interval], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """The union of intervals, clipped to [lo, hi), as sorted disjoint
    pairs."""
    out: List[Tuple[int, int]] = []
    for iv in sorted(intervals, key=lambda i: i.start):
        a, b = max(iv.start, lo), min(iv.end, hi)
        if a >= b:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


@dataclasses.dataclass
class Reduction:
    """The numbers a traced window gives, all in seconds."""
    window_s: float
    busy_s: List[float]  # per chip
    lo: int
    hi: int
    trace: Trace

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    def _in(self, ivs: Sequence[Interval]):
        return [iv for iv in ivs if iv.end > self.lo and iv.start < self.hi]

    def module_time(self, pattern: str) -> Tuple[float, int]:
        """Seconds on all chips, and calls, of programs whose name matches
        ``pattern`` (searched), inside the window."""
        rx = re.compile(pattern)
        total, calls = 0, 0
        for dev in self.trace.devices:
            for iv in self._in(dev.modules):
                if rx.search(iv.name):
                    total += min(iv.end, self.hi) - max(iv.start, self.lo)
                    calls += 1
        return total / 1e9, calls

    def op_time(self, pattern: str) -> Tuple[float, int]:
        """Seconds on all chips, and count, of operations whose HLO name
        (``%name.N``) matches ``pattern`` (searched), inside the
        window."""
        rx = re.compile(pattern)
        total, calls = 0, 0
        for dev in self.trace.devices:
            for iv in self._in(dev.ops):
                if rx.search(iv.name):
                    total += min(iv.end, self.hi) - max(iv.start, self.lo)
                    calls += 1
        return total / 1e9, calls

    def top_ops(self, k: int = 10) -> List[list]:
        """The operation names with the most device time (summed over
        chips), in seconds."""
        by = defaultdict(int)
        for dev in self.trace.devices:
            for iv in self._in(dev.ops):
                by[iv.name] += min(iv.end, self.hi) - max(iv.start, self.lo)
        top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]

    def idle_by_host(self, k: int = 10) -> List[list]:
        """Idle time of the first chip inside the window, summed by the
        host span open when each gap began."""
        dev = self.trace.devices[0]
        busy = merge(dev.ops, self.lo, self.hi)
        gaps, prev = [], self.lo
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = b
        if prev < self.hi:
            gaps.append((prev, self.hi))
        spans = self.trace.spans
        starts = [s.start for s in spans]
        by = defaultdict(int)
        for a, b in gaps:
            # host spans never overlap: only the last one that began
            # before the gap can be open at its start
            i = bisect.bisect_right(starts, a) - 1
            label = spans[i].name if i >= 0 and spans[i].end > a else "engine"
            by[label] += b - a
        top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]


def reduce(trace: Trace, window_s: float) -> Reduction:
    """Fix the window on the trace's clock, from the first benchmark span
    for ``window_s`` seconds, and take each chip's busy time in it."""
    if not trace.devices:
        raise ValueError("the trace holds no TPU plane")
    if trace.spans:
        lo = trace.spans[0].start
    else:
        lo = min(iv.start for d in trace.devices for iv in d.ops)
    hi = lo + int(window_s * 1e9)
    for d in trace.devices:
        last = max((iv.end for iv in d.ops), default=lo)
        if hi - last > max(0.05 * (hi - lo), 5e8):
            # the window closes right after a flush has finished on the
            # device; a trace whose operations end long before has lost
            # its tail
            raise ValueError(
                f"{d.name}: the trace's operations stop {(hi - last) / 1e9:.3f}"
                f" s before the window's end: the device dropped events")
    busy = [sum(b - a for a, b in merge(d.ops, lo, hi)) / 1e9
            for d in trace.devices]
    return Reduction(window_s, busy, lo, hi, trace)
