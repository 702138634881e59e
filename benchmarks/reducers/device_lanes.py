"""Share, in percent, of the window's planning lanes that an engine
other than the numpy twin placed.  A lane is one evaluation planned by
one kernel call: a ``sched.dispatch`` span (a fused device window writes
one per lane over the same interval, the twin one per lane as it runs
them) whose ``engine`` tag is ``device`` on one chip, ``sharded`` over a
mesh, or ``host``; and every kernel call of a one-by-one re-plan, which
its ``sched.retry`` span counts by engine (``host_calls``,
``device_calls``) where the program states them.
``device_dispatch_share`` counts DISPATCHES, of which a fused device
window is one and the twin's are one a lane; this counts the work.  The
notes give the lanes by engine and, where the spans carry them, the
narrowest and widest window (``lanes``) and estimate (``cost``) each
engine was given.  Parameters: none."""


def reduce(params: dict, ctx: dict):
    by_engine, replans = {}, {"host": 0, "device": 0}
    for s in ctx["spans"]:
        tags = s.get("tags") or {}
        if s["name"] == "sched.dispatch" and "engine" in tags:
            by_engine.setdefault(tags["engine"], []).append(tags)
        elif s["name"] == "sched.retry" and "host_calls" in tags:
            replans["host"] += tags["host_calls"]
            replans["device"] += tags.get("device_calls", 0)
    total = sum(len(v) for v in by_engine.values()) + sum(replans.values())
    if not total:
        return None
    for engine, lanes in sorted(by_engine.items()):
        note = f"sched.dispatch: engine {engine}: {len(lanes)} lanes"
        for tag in ("lanes", "cost"):
            seen = [t[tag] for t in lanes if tag in t]
            if seen:
                note += f"; {tag} {min(seen)}..{max(seen)}"
        ctx["notes"].append(note)
    ctx["notes"].append(f"sched.retry: kernel calls of one-by-one re-plans, "
                        f"by engine: {replans}")
    on_host = len(by_engine.get("host", ())) + replans["host"]
    return 100.0 * (total - on_host) / total
