"""Share of the traced slice, in percent, in which no operation ran on
the device: 1 - (union of the device planes' operation intervals, mean
over the chips used) / slice.  From the profiler trace alone."""


def reduce(params: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
