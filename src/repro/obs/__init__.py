"""repro.obs — the unified telemetry layer (tracing + metrics).

The paper's procedure is *measure, then configure*; this package is the
measuring half every subsystem reports through:

- :class:`~repro.obs.trace.Tracer` / :class:`~repro.obs.trace.Span` —
  nestable phase-level wall-clock spans, Chrome-trace/Perfetto export,
  and a zero-cost disabled fast path.
- :data:`~repro.obs.scopes.COMPONENTS` — the ``jax.named_scope`` names
  that label a step's device work in a profiler trace.
- :class:`~repro.obs.metrics.MetricsRegistry` — counters, gauges, and
  p50/p95/p99 histograms; renders the ``repro.api/metrics/v1`` section
  that every measured ``Report`` carries (``validate_metrics`` is the
  schema check ``repro.api.report`` delegates to).  ``TRACE_COUNTS``
  counts what the program chose while it was traced (which attention
  implementation each layer got).

See ``docs/observability.md`` for the walkthrough and
``tools/bench_trajectory.py`` for the per-PR ``BENCH_<area>.json``
trajectory these sections feed.
"""
from repro.obs.metrics import (METRICS_SCHEMA_ID, TRACE_COUNTS, Counter,
                               Gauge, Histogram, MetricsRegistry, percentile,
                               validate_metrics)
from repro.obs.trace import NULL_TRACER, Span, SpanEvent, Tracer

__all__ = [
    "METRICS_SCHEMA_ID", "TRACE_COUNTS", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "percentile", "validate_metrics",
    "NULL_TRACER", "Span", "SpanEvent", "Tracer",
]
