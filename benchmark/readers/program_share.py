"""Share of the device's busy time that the named programs took in the
traced window, in %."""


def read(ctx, programs: list):
    trace = ctx["trace"]
    if not trace or trace["busy_s"] <= 0:
        return None
    got = sum(v for k, v in trace["programs_s"].items() if k in programs)
    return 100.0 * got / trace["busy_s"]
