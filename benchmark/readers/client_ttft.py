"""A percentile of the time to the first streamed token as the client
saw it, in ms, over the requests due in the window: from when a request
was due (open loop) or sent (closed loop) to its first token-carrying
delta. A failed request, or one with no token ``drain_s`` after the
window closed, counts as the window's length."""


from benchmark.readers import percentile


def read(ctx, q: float = 95.0):
    t_open, t_close = ctx["window"]
    ttft = [
        (r.first - r.due) * 1e3 if (r.first is not None and r.error is None)
        else ctx["seconds"] * 1e3
        for r in ctx["records"] if t_open <= r.due < t_close
    ]
    return percentile(ttft, q) if ttft else None
