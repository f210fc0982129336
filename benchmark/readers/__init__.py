"""Per-layer metric readers, found by name.

A metric's file (``benchmark/metrics/<name>.json``) names a ``reader``
(a module here) and its ``args``. A reader takes the run's context
(counters scraped at the window's edges, the client's records, the
reduced trace, configuration, cell, device) and returns the number, or
None when it finds nothing to read; the harness then leaves the metric
out of the line.
"""

import importlib


def percentile(values, q: float) -> float:
    """Linear-interpolated q-th percentile (0..100) of a non-empty list."""
    v = sorted(values)
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def read(metric: dict, ctx: dict):
    mod = importlib.import_module(f"benchmark.readers.{metric['reader']}")
    return mod.read(ctx, **metric.get("args", {}))
