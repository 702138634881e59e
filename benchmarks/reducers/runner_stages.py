"""The fused runner's thread, stage by stage: what it computed, what it
chose to wait for, and what it stood runnable and did not run.

Every stage span of the runner's thread (``worker.batch`` and its
``worker.sync/snapshot/ack``, ``sched.begin/dispatch/finish/submit/
status``, ``sched.retry`` with its ``retry.*`` children and the
``retry.refresh`` before it, a kernel window's ``window.stack/upload``)
carries ``cpu_s``, the thread's CPU seconds over the span, and
``blocked_s``, the seconds it spent off the CPU inside waits it CHOSE (a
plan's future, a raft index, a raft apply, the device fetch).  What is
left of a span, ``dur - cpu_s - blocked_s``, the thread was runnable and
did not run: the interpreter lock, the OS, a lock nobody declared.  Of
GIL wait that is a LOWER bound: waking from a chosen wait queues for the
lock too, and the program counts that as blocked.  A fused stage writes
one span a lane over the window's one interval with the window's one
pair: spans of one name, start and duration are ONE window here.

``held`` is a batch less its ``worker.dequeue`` children (the wait for
work), as ``runner_cycle`` has it.  ``what`` picks the number, percent:

- ``retry_cycle_share``: sum of ``sched.retry`` (one one-by-one re-plan
  each) / sum of held.  0.0 where no evaluation re-planned; needs no
  tag, so the parent's program reads it too.
- ``blocked_share``: sum of ``blocked_s`` / sum of held, over the
  ``worker.batch`` spans that carry the pair.
- ``stalled_share``: sum of (held - ``cpu_s`` - ``blocked_s``) / sum of
  held, over the same.  With ``runner_cycle``'s ``on_cpu_share`` the
  three make 100.
- ``retry_stalled_share``: sum of (dur - ``cpu_s`` - ``blocked_s``) /
  sum of dur over the ``sched.retry`` spans that carry the pair;
  nothing where no evaluation re-planned.

A program that writes no ``blocked_s`` reads nothing for the last
three, and nothing raises.  Once a run the reader notes one table: for
each stage name (a kernel call's by its ``engine``: ``retry.dispatch
[host]``), its windows, seconds, mean, and the three shares of its
seconds; the held seconds under no stage span at all; and the kernel
windows' ``device.dispatch`` spans (those that say ``fetch_s``): their
mean duration, fetch and upload.
"""
from xplane import union_ns

NOTED = "runner_stages.noted"


def pair_of(span: dict):
    """(``cpu_s``, ``blocked_s``) of a span, None where it lacks one."""
    tags = span.get("tags") or {}
    if "cpu_s" in tags and "blocked_s" in tags:
        return tags["cpu_s"], tags["blocked_s"]
    return None


def held_by_batch(spans: list) -> dict:
    """{span id: (the ``worker.batch`` span, its seconds less its
    dequeue)}."""
    batches = {s["span_id"]: s for s in spans
               if s["name"] == "worker.batch" and "span_id" in s}
    waited = dict.fromkeys(batches, 0.0)
    for s in spans:
        if s["name"] == "worker.dequeue" and s.get("parent_id") in waited:
            waited[s["parent_id"]] += s["dur"]
    return {i: (b, b["dur"] - waited[i]) for i, b in batches.items()}


def stage_windows(spans: list, threads: set) -> dict:
    """{name: [(t0, dur, cpu_s, blocked_s)]} of the spans on ``threads``
    that carry the pair, a fused window's lanes counted once."""
    seen, out = set(), {}
    for s in spans:
        pair = pair_of(s)
        if pair is None or s.get("thread") not in threads:
            continue
        engine = (s.get("tags") or {}).get("engine")
        name = s["name"] + (f" [{engine}]" if engine else "")
        key = (name, s["t0"], s["dur"])
        if key not in seen:
            seen.add(key)
            out.setdefault(name, []).append((s["t0"], s["dur"], *pair))
    return out


def note_table(ctx: dict, batches: dict) -> None:
    """The run's one table (the first call of a run writes it)."""
    if ctx.get(NOTED):
        return
    ctx[NOTED] = True
    threads = {b.get("thread") for b, _held in batches.values()}
    stages = stage_windows(ctx["spans"], threads)
    if not stages:
        return
    held = sum(h for _b, h in batches.values())
    ctx["notes"].append(
        "runner stages (a fused window once): name: windows, seconds, "
        "mean ms; of its seconds on a CPU / in waits it chose / runnable "
        "and not running")
    for name, rows in sorted(stages.items(),
                             key=lambda kv: -sum(r[1] for r in kv[1])):
        secs = sum(r[1] for r in rows)
        cpu, blocked = sum(r[2] for r in rows), sum(r[3] for r in rows)
        if name == "worker.batch":  # its shares are of the held seconds
            secs = held
        if secs <= 0:
            continue
        ctx["notes"].append(
            f"  {name}: {len(rows)}, {secs:.3f}s, "
            f"{1e3 * secs / len(rows):.3f} ms; {100 * cpu / secs:.1f}% / "
            f"{100 * blocked / secs:.1f}% / "
            f"{100 * (secs - cpu - blocked) / secs:.1f}%")
    inside = union_ns([(r[0], r[1]) for name, rows in stages.items()
                       if name != "worker.batch" for r in rows])[0]
    ctx["notes"].append(
        f"  holding a batch {held:.3f}s, of it under no stage span "
        f"{max(0.0, held - inside):.3f}s")
    fused = [s for s in ctx["spans"] if s["name"] == "device.dispatch"
             and "fetch_s" in (s.get("tags") or {})]
    if fused:
        n = len(fused)
        ctx["notes"].append(
            f"  device.dispatch of a kernel window: {n}, mean "
            f"{1e3 * sum(s['dur'] for s in fused) / n:.3f} ms, of it in "
            f"the fetch {1e3 * sum(s['tags']['fetch_s'] for s in fused) / n:.3f}"
            f" ms; {sum(s['tags'].get('h2d_bytes', 0) for s in fused) / n:.0f}"
            " bytes uploaded a window")


def reduce(params: dict, ctx: dict):
    what = params["what"]
    if what not in ("retry_cycle_share", "blocked_share", "stalled_share",
                    "retry_stalled_share"):
        raise ValueError(f"runner_stages: unknown what={what!r}")
    batches = held_by_batch(ctx["spans"])
    if not batches:
        return None
    note_table(ctx, batches)
    retries = [s for s in ctx["spans"] if s["name"] == "sched.retry"]
    if what == "retry_cycle_share":
        held = sum(h for _b, h in batches.values())
        if held <= 0:
            return None
        ctx["notes"].append(
            f"sched.retry: {len(retries)} one-by-one re-plans, "
            f"{sum(s['dur'] for s in retries):.3f}s of {held:.3f}s held")
        return 100.0 * sum(s["dur"] for s in retries) / held
    if what == "retry_stalled_share":
        rows = [(s["dur"], *pair_of(s)) for s in retries if pair_of(s)]
    else:
        rows = [(h, *pair_of(b)) for b, h in batches.values() if pair_of(b)]
    whole = sum(r[0] for r in rows)
    if whole <= 0:
        return None
    if what == "blocked_share":
        return 100.0 * sum(r[2] for r in rows) / whole
    return 100.0 * sum(r[0] - r[1] - r[2] for r in rows) / whole
