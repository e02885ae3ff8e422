"""Host spans of the program, on the profiler's clock, and host-link bytes.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation``: under
``jax.profiler.trace`` each span lands in the same XSpace as the device's
planes, on the same clock, with its ``ids`` as event stats; with the
profiler off it costs about a microsecond. Every span of the program is
named ``qafel.*`` and opens where the work happens:

* the cohort engine (``repro.sim.cohort``): ``qafel.engine.admit`` (one
  cohort's admission) holding ``.keys``, ``.data``, ``.dispatch`` and
  ``.codes_to_host`` (the wait for the step, the codes' copy and the
  framing; the device trace, where the step ends, splits the wait from
  the copy); ``qafel.engine.deliver`` (one delivery) holding
  ``.receive_key``, the upload's ``receive`` and ``.replicas``;
* the protocol (``repro.core.qafel``): ``qafel.buffer.add`` and
  ``qafel.flush``, which holds ``qafel.buffer.drain``,
  ``qafel.flush.dispatch`` and ``qafel.flush.frame``.

Two plain counters, in the style of ``kernels.ops.*_TRACES``, count the
bytes the program itself moves over the host link: ``d2h_bytes``, the
cohorts' wire codes fetched to the host (``core.protocol``), and
``h2d_bytes``, the window's wire arrays (codes, norms, residual) handed to
the flush; the flush's (K,) weights and (2,) key are not wire bytes and
are not counted. Two more count the flush's host staging
(``core.buffer.UpdateBuffer.drain``): ``flush_stage_allocs``, staging
pairs allocated, and ``flush_stage_reuses``, drains that wrote into a
pair already there (a steady run allocates two, then only reuses).
``RunTracer.counters()`` reports all four. Kernels hold no spans.
"""
from __future__ import annotations

import jax

d2h_bytes = 0
h2d_bytes = 0
flush_stage_allocs = 0
flush_stage_reuses = 0


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span named ``name``; ``ids`` become the event's stats."""
    return jax.profiler.TraceAnnotation(name, **ids)
