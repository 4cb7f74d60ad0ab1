"""Search telemetry for the solver (DESIGN.md §8).

Three small host-side layers, wired through ``repro.solver.Solver`` and
``repro.service.SolverService`` behind ``SolverConfig.metrics`` /
``SolverConfig.trace_path``, and the profiler's names:

* :mod:`repro.obs.registry` — a lightweight metrics registry
  (counters / gauges / histograms with labels) whose disabled form hands
  out shared no-op instruments, so instrumentation is zero-cost when
  telemetry is off;
* :mod:`repro.obs.trace` — the JSONL trace writer and the per-kind record
  schema it validates against (``tools/trace_report.py`` consumes these
  traces and re-validates with the same tables);
* :mod:`repro.obs.collect` — the per-round collector both drivers call at
  round boundaries.  Every number it reports is derived on the host from
  arrays the round loop already materializes (lane counters, the
  open-work vector, the incumbent table), so collection adds no device
  syncs to the hot path and the search tree is bit-identical with
  telemetry on or off (asserted in ``tests/test_obs.py``);
* :mod:`repro.obs.spans` — named host spans and device scopes, from
  frozen name tables, that go into ``jax.profiler``'s own trace.  Always
  on, behind no config field: a span costs about a microsecond when no
  profiler records, a scope only changes metadata.
"""

from repro.obs.collect import RoundCollector
from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                MetricsSnapshot)
from repro.obs.spans import SCOPE_NAMES, SPAN_NAMES, scope, span
from repro.obs.trace import (TRACE_KINDS, TRACE_SCHEMA_VERSION, TraceError,
                             TraceWriter, read_trace, validate_record)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "MetricsSnapshot",
    "RoundCollector",
    "SCOPE_NAMES",
    "SPAN_NAMES",
    "TRACE_KINDS",
    "TRACE_SCHEMA_VERSION",
    "TraceError",
    "TraceWriter",
    "read_trace",
    "scope",
    "span",
    "validate_record",
]
