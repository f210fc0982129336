"""Increase of one program counter over the window per second of the
window, times ``scale``: with a histogram's ``_sum`` of seconds and
``scale`` 100, the share of the window spent there, in %."""


def read(ctx, name: str, scale: float = 1.0):
    a = ctx["prom_after"]
    if name not in a:
        return None
    return (a[name] - ctx["prom_before"].get(name, 0.0)) / ctx["seconds"] * scale
