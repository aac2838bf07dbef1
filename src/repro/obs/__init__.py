"""repro.obs — request-lifecycle tracing + serving metrics.

The serving-side observability tier (docs/observability.md), the
counterpart of repro.tune's kernel-level rooflines:

  obs.events    Tracer protocol (nil-by-default engine hooks),
                ServeTracer recorder, RequestRecord derived spans,
                Chrome trace-event export (Perfetto-loadable)
  obs.metrics   Counter/Gauge/Histogram registry with fixed log-spaced
                latency buckets; JSON exposition; the ONE home for
                percentile math in the serving stack
  obs.spans     `span(name, **counts)`: host spans in the profiler's
                trace (engine.* and trainer.* phases, `.wait` marks the
                host blocking on the device)
  python -m repro.obs report trace.json
                per-request latency table from an exported trace

Wiring: `Engine(cfg, params, tracer=ServeTracer())`, or
`launch/serve.py --trace-out trace.json --metrics-json metrics.json`.
"""
from repro.obs.events import RequestRecord, ServeTracer, Tracer
from repro.obs.metrics import (BUCKET_RATIO, LATENCY_BUCKETS, Counter,
                               Gauge, Histogram, MetricsRegistry,
                               log_buckets, percentiles)
from repro.obs.spans import span

__all__ = [
    "BUCKET_RATIO", "Counter", "Gauge", "Histogram", "LATENCY_BUCKETS",
    "MetricsRegistry", "RequestRecord", "ServeTracer", "Tracer",
    "log_buckets", "percentiles", "span",
]
