"""Telemetry substrate: in-dispatch metric taps, structured run tracing,
and host spans on the profiler's clock.

Three parts (see DESIGN.md "Telemetry substrate"):

* **In-dispatch taps** (``repro.obs.taps``): small flat f32 vectors of
  device-computed scalars — per-upload / per-broadcast relative
  quantization error, delta/update norms, staleness-weight stats — emitted
  by the SAME fused dispatches that do the work (``kernels.ops.
  server_flush_step(_sharded)`` / ``cohort_train_encode_step`` with
  ``taps=True``). Zero extra kernel entries, one extra output; tap values
  are engine- and sharding-invariant at a fixed seed.
* **Run tracing** (``repro.obs.events``): a ``RunTracer`` recording typed
  events (upload, drop, flush, broadcast, eval, compile) with sim-clock
  and wall-clock timestamps into a bounded in-memory ring, exportable as
  JSONL (schema-checked by ``repro.obs.schema``), plus dispatch/compile
  counters built on ``analysis_static.trace_guard``'s entry registry.
* **Host spans** (``repro.obs.spans``): ``qafel.*`` profiler annotations
  inside the engine, the buffer and the flush, on the device trace's
  clock, and the host-link byte counters ``RunTracer.counters()`` reports.

``repro.obs.metrics.collect`` is the ONE metrics surface: the pre-existing
``TrafficMeter`` / ``StalenessMonitor`` / ``accuracy_trace`` keys are
preserved bit-for-bit, and telemetry series appear as additional keys only
when a tracer is attached.
"""
from repro.obs.events import EVENT_KINDS, CompileWatch, Event, RunTracer
from repro.obs.metrics import collect
from repro.obs.records import AccuracyPoint
from repro.obs.report import summary_table, write_jsonl
from repro.obs.schema import validate_events, validate_jsonl
from repro.obs.taps import (COHORT_TAP_NAMES, FLUSH_TAP_NAMES,
                            cohort_tap_rows, flush_tap_vector,
                            named_cohort_taps, named_flush_taps)

__all__ = [
    "AccuracyPoint",
    "COHORT_TAP_NAMES",
    "CompileWatch",
    "EVENT_KINDS",
    "Event",
    "FLUSH_TAP_NAMES",
    "RunTracer",
    "cohort_tap_rows",
    "collect",
    "flush_tap_vector",
    "named_cohort_taps",
    "named_flush_taps",
    "summary_table",
    "validate_events",
    "validate_jsonl",
    "write_jsonl",
]
