"""``prom_rate`` over several program counters, summed: with histograms'
``_sum``s of seconds and ``scale`` 100, the share of the window spent in
all of them together, in %. One name absent → nothing to read."""

from benchmark.readers import prom_rate


def read(ctx, names: list, scale: float = 1.0):
    parts = [prom_rate.read(ctx, name=n, scale=scale) for n in names]
    return None if None in parts else sum(parts)
