"""Mean device time of one run of the named programs in the traced
window, in ms: their summed ``XLA Modules`` time over their summed
calls. A macro-step program runs once for several tokens and counts
once, as ``engine_step_ms`` does."""


def read(ctx, programs: list):
    trace = ctx["trace"]
    if not trace:
        return None
    calls = sum(trace["program_calls"].get(p, 0) for p in programs)
    if not calls:
        return None
    return sum(trace["programs_s"].get(p, 0.0) for p in programs) / calls * 1e3
