"""How late the generator sent: a percentile of (sent - due) over the
window's requests, in ms. A starved generator must not read as a fast
server."""


from benchmark.readers import percentile


def read(ctx, q: float = 95.0):
    t_open, t_close = ctx["window"]
    lag = [
        (r.sent - r.due) * 1e3 for r in ctx["records"]
        if r.sent is not None and t_open <= r.due < t_close
    ]
    return percentile(lag, q) if lag else None
