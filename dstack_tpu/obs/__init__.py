"""Shared telemetry core: metric primitives + Prometheus rendering.

One implementation of counters/gauges/histograms used by every layer —
the control-plane HTTP middleware (``server/sentry_compat.py``), the cluster
``/metrics`` renderer (``server/services/prometheus.py``), the serve
engine (``serve/metrics.py``), and the train-step telemetry hook
(``train/step.py``) — so escaping rules, bucket layouts, and the text
exposition format cannot drift between exporters. Reference dstack
relays DCGM exporter text and ships Sentry tracing; this module is the
TPU translation's first-party equivalent, import-light by design (no
jax, no aiohttp) so tools and tests can enumerate metric families
without pulling an accelerator runtime.
"""

from dstack_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    escape_label,
    LATENCY_BUCKETS_S,
    SHORT_LATENCY_BUCKETS_S,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "escape_label",
    "LATENCY_BUCKETS_S",
    "SHORT_LATENCY_BUCKETS_S",
]
