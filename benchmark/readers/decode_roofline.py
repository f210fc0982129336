"""Share of its roofline that the decode programs reached in the traced
window, in %.

Least time = steps × max(FLOPs / peak FLOP/s, bytes / peak bytes/s) of
one decode step at the live batch and context (``benchmark/costs``:
the module the configuration file names under ``costs``, else
``decode``; the contract is ``decode_step(llama_config, batch, context)
-> {"flops", "bytes", "weight_bytes", "cache_bytes"}``),
over the device's busy time in the trace: all of it, whatever the
programs are called, so prefill and the sampler sit in the denominator
and the share is a floor of the decode program's own. Naming or
splitting programs does not move it. Steps, batch and context come
from the client's records inside the traced span, with no knowledge of
the engine's decode modes: a token that arrived while ``n`` requests
were decoding is 1/n of a step.
"""

import importlib

from benchmark import costs


def live_decode_stats(records, t0, t1):
    """→ (steps, mean live batch, mean context) inside [t0, t1]."""
    spans = [
        (r.deltas[0][0], r.deltas[-1][0], len(r.req["prompt_ids"]), r)
        for r in records if len(r.deltas) >= 2
    ]
    steps = live_sum = ctx_sum = n = 0.0
    for first, last, n_prompt, r in spans:
        seen = 0
        for t, k in r.deltas:
            seen += k
            if not (t0 <= t <= t1) or t == first:
                continue
            live = sum(1 for f, l, _, _ in spans if f < t <= l)
            if live:
                steps += k / live
                live_sum += live * k
                ctx_sum += (n_prompt + seen) * k
                n += k
    if not n:
        return 0.0, 0.0, 0.0
    return steps, live_sum / n, ctx_sum / n


def read(ctx):
    trace = ctx["trace"]
    if not trace or "start_done" not in ctx["trace_span"]:
        return None
    device_s = trace["busy_s"]
    if device_s <= 0:
        return None
    t0 = ctx["trace_span"]["start_done"]
    t1 = ctx["trace_span"].get("stop", t0 + trace["window_s"])
    steps, batch, context = live_decode_stats(ctx["records"], t0, t1)
    if not steps:
        return None
    step_costs = importlib.import_module(
        f"benchmark.costs.{ctx['config'].get('costs', 'decode')}"
    )
    step = step_costs.decode_step(ctx["config"]["llama_config"], batch, context)
    roof = costs.roofline_seconds(step["flops"], step["bytes"], ctx["device"]["kind"])
    print(
        f"decode_roofline steps={steps:.1f} live_batch={batch:.2f} context={context:.0f} "
        f"bound={roof['bound']} least_step_ms={roof['seconds'] * 1e3:.3f} "
        f"device_s={device_s:.4f}"
    )
    return 100.0 * steps * roof["seconds"] / device_s
