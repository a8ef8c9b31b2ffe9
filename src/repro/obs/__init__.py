"""repro.obs — unified tracing + metrics across edge, blockchain, and
storage layers.  See README.md in this directory."""
from repro.obs.metrics import (Counter, CounterGroup, Gauge, Histogram,
                               MetricsRegistry, DEFAULT_TIME_BUCKETS,
                               canonical_name, exp_buckets,
                               merge_namespaced)
from repro.obs.trace import NOOP_SPAN, Observability, Span, Tracer, now

__all__ = [
    "Counter", "CounterGroup", "Gauge", "Histogram", "MetricsRegistry",
    "DEFAULT_TIME_BUCKETS", "canonical_name", "exp_buckets",
    "merge_namespaced", "NOOP_SPAN", "Observability", "Span", "Tracer",
    "now",
]
