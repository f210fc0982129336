"""One program counter as scraped at the window's start: what the
program had counted before the measured window, i.e. over its boot and
the ramp."""


def read(ctx, name: str):
    return ctx["prom_before"].get(name)
