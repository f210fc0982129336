"""Increase of one program counter over the window."""


def read(ctx, name: str):
    a = ctx["prom_after"]
    if name not in a:
        return None
    return a[name] - ctx["prom_before"].get(name, 0.0)
