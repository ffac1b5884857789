"""Unified observability: metrics registry, structured tracer, exporters.

Three zero-dependency parts (docs/observability.md has the full tour):

* ``metrics`` -- counters / gauges / log-bucketed mergeable histograms
  behind a ``MetricsRegistry`` (``NULL_REGISTRY`` to opt out);
* ``trace`` -- span + counter events with Chrome/Perfetto JSON export
  (``NULL_TRACER`` is the zero-overhead default);
* ``export`` / ``report`` -- Prometheus text + JSON snapshots, and the
  ``python -m repro.obs.report`` stall-attribution CLI.
"""

from repro.obs.export import (metrics_json, prometheus_text,
                              validate_prometheus_text, write_metrics,
                              write_prometheus)
from repro.obs.metrics import (NULL_REGISTRY, Counter, Gauge, Histogram,
                               MetricsRegistry, NullRegistry,
                               merge_histograms)
from repro.obs.trace import NULL_TRACER, NullTracer, Tracer, watch_jit

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "NullRegistry",
    "NULL_REGISTRY", "merge_histograms", "Tracer", "NullTracer",
    "NULL_TRACER", "watch_jit", "prometheus_text", "validate_prometheus_text",
    "metrics_json", "write_metrics", "write_prometheus",
]
