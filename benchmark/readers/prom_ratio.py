"""Δnumerator / Δdenominator of two program counters over the window,
times ``scale``: exact means from histogram ``_sum``/``_count`` pairs,
never read off bucket edges."""


def read(ctx, num: str, den: str, scale: float = 1.0):
    b, a = ctx["prom_before"], ctx["prom_after"]
    if num not in a or den not in a:
        return None
    d_den = a[den] - b.get(den, 0.0)
    if d_den <= 0:
        return None
    return (a[num] - b.get(num, 0.0)) / d_den * scale
