"""Ratio of counter deltas over the window, times ``scale``.
Parameters: ``numerator`` and ``denominator`` (lists of counter names,
each side summed), ``scale`` (1 for a plain ratio, 100 for a share in
percent).  Counters are the ``nomad.*`` registry's as
``GET /v1/agent/metrics`` serves them, read at the window's two edges,
plus the harness's own ``bench.jobs_completed``."""


def reduce(params: dict, ctx: dict):
    def delta(names):
        return sum(ctx["counters_close"][n] - ctx["counters_open"][n]
                   for n in names)

    den = delta(params["denominator"])
    if den <= 0:
        return None
    return float(params.get("scale", 1)) * delta(params["numerator"]) / den
