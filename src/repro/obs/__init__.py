"""`repro.obs` — unified observability for the serving stack.

Three layers, one package:

* **trace** — deterministic tick-clock event stream (spans + instants)
  from every seam of the stack, exported as Chrome/Perfetto
  ``trace_event`` JSON; byte-identical across same-seed replays.
* **registry** — typed counter/gauge/histogram aggregation that
  `sched/metrics.summarize()` is built on, plus :func:`provenance`
  run-context headers for BENCH sections.
* **recorder** — bounded flight-recorder ring of recent events, dumped
  to disk automatically on ``HealthError`` / ``RequestFailed`` /
  ``OutOfPages``.

* **analyze** — trace analytics: fold the event stream (live or an
  exported file) into a :class:`TraceReport` — per-request critical
  path, queueing split, role utilization, page-pool pressure — and
  score it against a declarative :class:`SLOSpec`.

Plus :func:`timeit` (the one best-of-N wall timer),
:func:`profile_trace` (optional ``jax.profiler`` hook), :class:`span`
(a host span on the profiler's clock, also feeding ``tracer.wall``) and
:data:`compile_events` (every trace and compile of the process).
"""
from repro.obs.analyze import SLOSpec, TraceReport, analyze, load_trace
from repro.obs.recorder import FlightRecorder
from repro.obs.registry import (Counter, Gauge, Histogram, Registry,
                                percentile, provenance)
from repro.obs.timing import timeit
from repro.obs.trace import (NULL, NullTracer, Tracer, WallTimers,
                             compile_events, profile_trace, span)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "percentile",
    "provenance", "FlightRecorder", "timeit", "NULL", "NullTracer",
    "Tracer", "WallTimers", "profile_trace", "span", "compile_events",
    "SLOSpec", "TraceReport", "analyze", "load_trace",
]
