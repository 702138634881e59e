"""Which host step the device was idle under.

The program's spans are on the tracer's clock (``perf_counter`` minus
its epoch; ``ctx["span_clock_offset"]`` adds back to ``perf_counter``);
the profiler's module events (``ctx["trace"]["modules"]``: name, start,
duration) are on the device plane's clock, whose epoch nothing states.
``align`` lays both on the span clock: inside the traced slice the k-th
``device.dispatch`` span of program P (the jitted function's name) is
the k-th module event ``jit_P(<n>)``; the clock offset is the median of
(event start - span start) over the pairs.  It then CHECKS the offset:
every event must start inside its span (for a span tagged ``async=1``:
not before the span's start), give or take ``tolerance_ms``.  More than
``misfit_limit`` (1 %) of events outside, or no pair at all, and there
is no alignment: the reader returns None rather than a number laid on
a clock it could not verify.  Where the counts of spans and events of a
program differ (a dispatch astride the slice's edge), the shorter list
slides along the longer and the shift with the tightest offsets wins.

``reduce``: of the time inside the slice in which no module ran on the
device, the share (percent) that lies under a ``worker.batch`` span
(its ``worker.dequeue`` child, the wait for work, taken out), under an
``applier.*`` / ``raft.apply`` / ``server.apply.*`` span, or in a
stretch with no evaluation in flight (between ``eval.created`` and the
``sched.status`` that wrote a terminal status).  Notes: the offset and
what epoch it implies, the residual, the misfits, and the ten longest
gaps, each with the leaf spans that cover most of it.
"""
import statistics
import time

from xplane import union_ns

TERMINAL = ("complete", "failed", "canceled")
WAITS = ("broker.wait", "query.blocked", "worker.dequeue")


def program_of(module_name: str) -> str:
    """``jit__scatter_jit_impl(2)`` -> ``_scatter_jit_impl``."""
    name = module_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def pair_up(spans: list, events: list) -> list:
    """[(span, event)] in order; the shorter list slid along the longer
    to where the offsets (event start - span start) spread least."""
    short, long_ = (spans, events) if len(spans) <= len(events) \
        else (events, spans)
    if not short:
        return []
    best = None
    for shift in range(len(long_) - len(short) + 1):
        window = long_[shift:shift + len(short)]
        pairs = list(zip(short, window)) if short is spans \
            else list(zip(window, short))
        offs = [e[1] - s["t0"] for s, e in pairs]
        spread = max(offs) - min(offs)
        if best is None or spread < best[0]:
            best = (spread, pairs)
    return best[1]


def align(ctx: dict, tolerance_ms: float = 5.0,
          misfit_limit: float = 0.01):
    """{"offset", "residual", "misfits", "pairs", "slice"} with
    ``offset`` = device clock - span clock, or None; says why in
    ``ctx["notes"]`` either way."""
    trace = ctx.get("trace")
    if not trace or not trace.get("modules") or \
            not trace.get("slice_perf"):
        return None
    to_span = -ctx["span_clock_offset"]
    lo, hi = (t + to_span for t in trace["slice_perf"])
    by_program = {}
    for s in ctx["spans"]:
        if s["name"] == "device.dispatch" and lo <= s["t0"] <= hi:
            by_program.setdefault((s.get("tags") or {}).get("program"),
                                  []).append(s)
    events = {}
    for ev in trace["modules"]:
        events.setdefault(program_of(ev[0]), []).append(ev)
    pairs = []
    for program, evs in events.items():
        evs.sort(key=lambda e: e[1])
        pairs += pair_up(sorted(by_program.get(program, []),
                                key=lambda s: s["t0"]), evs)
    if not pairs:
        ctx["notes"].append(
            f"clock alignment: {len(trace['modules'])} module events "
            f"({sorted(events)}), no device.dispatch span to pair them "
            f"with ({sorted(map(str, by_program))})")
        return None
    offsets = [e[1] - s["t0"] for s, e in pairs]
    offset = statistics.median(offsets)
    tol = tolerance_ms * 1e-3
    misfits = 0
    for s, e in pairs:
        start = e[1] - offset
        late = not (s.get("tags") or {}).get("async") and \
            start > s["t0"] + s["dur"] + tol
        misfits += start < s["t0"] - tol or late
    residual = max(abs(o - offset) for o in offsets)
    # Which epoch: the device clock read against perf_counter and
    # against the wall clock, now (both drift by nothing in a run).
    device_minus_perf = offset + to_span
    wall_minus_perf = time.time() - time.perf_counter()
    ctx["notes"].append(
        f"clock alignment: {len(pairs)} pairs of "
        f"{len(trace['modules'])} module events; device clock - "
        f"perf_counter = {device_minus_perf:.6f}s (wall - perf_counter "
        f"= {wall_minus_perf:.6f}s; slice opened at perf_counter "
        f"{trace['slice_perf'][0]:.6f}s); residual "
        f"{1e3 * residual:.3f} ms; {misfits} events outside their "
        f"spans (tolerance {tolerance_ms} ms)")
    if misfits > misfit_limit * len(pairs):
        return None
    return {"offset": offset, "residual": residual, "misfits": misfits,
            "pairs": len(pairs), "slice": (lo, hi)}


def merged(intervals: list) -> list:
    """(start, end) pairs merged where they overlap, in order."""
    return union_ns([(a, b - a) for a, b in intervals])[1]


def overlap(lo: float, hi: float, cover: list) -> float:
    """Seconds of [lo, hi] under the merged intervals ``cover``."""
    return sum(max(0.0, min(hi, b) - max(lo, a)) for a, b in cover)


def reduce(params: dict, ctx: dict):
    got = align(ctx, float(params.get("tolerance_ms", 5.0)),
                float(params.get("misfit_limit", 0.01)))
    if got is None:
        return None
    lo, hi = got["slice"]
    busy = merged([(s - got["offset"], s - got["offset"] + d)
                   for _n, s, d in ctx["trace"]["modules"]])
    gaps, edge = [], lo
    for a, b in busy:
        if a > edge:
            gaps.append((edge, min(a, hi)))
        edge = max(edge, b)
    if edge < hi:
        gaps.append((edge, hi))
    gaps = [(a, b) for a, b in gaps if b > a]
    if not gaps:
        return None

    cover, waits, in_flight, created, leaves = [], [], [], {}, []
    parents = {s.get("parent_id") for s in ctx["spans"]}
    for s in ctx["spans"]:
        name, a, b = s["name"], s["t0"], s["t0"] + s["dur"]
        tags = s.get("tags") or {}
        if name == "worker.batch" or name == "raft.apply" or \
                name.startswith(("applier.", "server.apply.")):
            cover.append((a, b))
        elif name == "worker.dequeue":
            waits.append((a, b))
        elif name == "eval.created":
            created[tags.get("eval_id")] = a
        elif name == "sched.status" and tags.get("status") in TERMINAL \
                and tags.get("eval_id") in created:
            in_flight.append((created[tags["eval_id"]], b))
        if s["dur"] > 0 and s.get("span_id") not in parents and \
                name not in WAITS and b > lo and a < hi:
            leaves.append((a, b, name))
    # Evaluations still in flight at the window's close, or created
    # before it opened, have no end or no start here: a stretch counts
    # as "no evaluation in flight" only between two that are whole.
    flying = merged(in_flight)
    nothing = [(b0, a1) for (_a0, b0), (a1, _b1) in zip(flying, flying[1:])]
    waits = merged(waits)
    worked = []
    for a, b in merged(cover):
        for wa, wb in waits:    # take the waits for work out
            if wa < b and wb > a:
                if wa > a:
                    worked.append((a, wa))
                a = max(a, wb)
        if b > a:
            worked.append((a, b))
    named = merged(worked + nothing)

    idle = sum(b - a for a, b in gaps)
    attributed = sum(overlap(a, b, named) for a, b in gaps)
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        under = {}
        for la, lb, name in leaves:
            if min(b, lb) > max(a, la):
                under.setdefault(name, []).append((max(a, la), min(b, lb)))
        top = sorted(((n, sum(e - s for s, e in merged(iv)))
                      for n, iv in under.items()),
                     key=lambda kv: -kv[1])[:4]
        ctx["notes"].append(
            f"idle gap {b - a:.3f}s at +{a - lo:.3f}s of the slice: "
            f"{overlap(a, b, named):.3f}s attributed; leaf spans under it: "
            + (", ".join(f"{n} {secs:.3f}s" for n, secs in top) or "none"))
    return 100.0 * attributed / idle
