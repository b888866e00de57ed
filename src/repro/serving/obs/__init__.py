"""Serving observability: structured tracing, live metrics, health HTTP.

Three parts, all host-side and zero-cost when unused:

  * ``span`` / ``TraceRecorder`` — host spans on the profiler's clock
    (``repro.*`` annotations in a ``jax.profiler`` trace) and a
    fixed-capacity ring buffer of them plus request-lifecycle spans,
    exportable as Chrome trace-event JSON (open in
    https://ui.perfetto.dev).  Engines take ``tracer=None``.
  * ``MetricsRegistry`` / ``instrument_engine`` — Prometheus-style
    counters/gauges/histograms fed by scrape-time callbacks over the
    engines' existing ``EngineStats``/scheduler state.
  * ``MetricsServer`` — stdlib HTTP endpoint serving ``/metrics``
    (Prometheus text), ``/metrics.json``, and ``/healthz`` (503 under
    backpressure/drain).
"""

from repro.serving.obs.httpd import MetricsServer, PROM_CONTENT_TYPE
from repro.serving.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    instrument_engine,
)
from repro.serving.obs.trace import TraceRecorder, span

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "MetricsServer",
    "PROM_CONTENT_TYPE",
    "TraceRecorder",
    "instrument_engine",
    "span",
]
