"""Observability layer for the DSI pipeline (ISSUE 7).

Three stdlib-only pieces, threaded through every DSI stage:

  * :mod:`repro.obs.trace` — thread-safe span tracing with clock
    injection and a Chrome-trace/Perfetto exporter; enabled spans also
    reach a ``jax.profiler`` trace.  Disabled by default
    (``NULL_TRACER``), zero-cost when off; ``phase`` times a span into
    counters either way.
  * :mod:`repro.obs.meta` — per-field counter/gauge metadata for the
    metric dataclasses; one source of truth shared by ``merge`` methods,
    the registry, and the REPRO-M002 monotonicity rule.
  * :mod:`repro.obs.registry` — a ``MetricsRegistry`` unifying the metric
    dataclasses behind one snapshot/delta API; the ``ElasticController``
    observations are rebuilt on these deltas.

``python -m repro.obs.report`` turns a trace + registry snapshot into the
paper's Table-7/Table-9 stall-attribution breakdown;
``python -m repro.obs.smoke`` produces a traced two-tenant artifact for
CI (see docs/observability.md).
"""
from repro.obs.meta import counter, gauge, merge_metrics, metric_fields
from repro.obs.registry import MetricsRegistry, Snapshot
from repro.obs.trace import NULL_TRACER, NullTracer, Tracer, phase

__all__ = [
    "counter", "gauge", "merge_metrics", "metric_fields",
    "MetricsRegistry", "Snapshot",
    "Tracer", "NullTracer", "NULL_TRACER", "phase",
]
