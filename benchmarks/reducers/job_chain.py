"""One job's chain of spans, from the HTTP socket to the answer.

For every evaluation the window's spans tell whole: the
``http.serve.job_register`` span that roots its trace (start: the socket
went readable), the ``sched.status`` span that wrote its terminal
status, and the first ``http.serve.eval_get`` span that answered a
client with that status (end: the response written).  ``what`` picks
the number:

- ``wake_lag_ms``: mean, over evaluations, of the end of the status
  write -> the end of the answering request.  An evaluation with no
  answering span is left out, never counted as 0.
- ``unattributed_share``: median, over evaluations, of the share (in
  percent) of [start, end] that lies under none of the evaluation's
  LEAF spans (spans with no child; a parent's own time is not
  attributed).  An evaluation's leaf spans are those of its own trace,
  of the requests that read it (``http.serve.eval_get`` tagged with its
  id), and, inside every ``worker.batch`` it rode and until its own
  terminal status is written, those of the batch and of the batch's
  other lanes: the stages of a fused batch are shared, and while
  another lane is prepared or re-planned this one waits under that
  lane's ``sched.begin`` or ``sched.retry``.  The reader notes the
  number of evaluations, the median interval, which span names cover
  most of it, and between which leaf spans the uncovered time lies.

Nothing to read (a program without these spans) gives None.
"""
import statistics

from xplane import union_ns

TERMINAL = ("complete", "failed", "canceled")


def union_s(intervals: list) -> float:
    """Seconds covered by (start, end) pairs, overlaps counted once."""
    return union_ns([(a, b - a) for a, b in intervals])[0]


def clip(spans: list, lo: float, hi: float) -> list:
    out = []
    for s in spans:
        a, b = max(s["t0"], lo), min(s["t0"] + s["dur"], hi)
        if b > a:
            out.append((a, b, s["name"]))
    return out


def chains(spans: list, root: str) -> list:
    """[{eval_id, trace_id, start, wrote, answer}] of the evaluations
    whose trace has a ``root`` span; ``wrote`` / ``answer`` (end times)
    are None where the spans lack them."""
    roots, anchors, wrote, answers = {}, {}, {}, {}
    for s in spans:
        name, tags = s["name"], s.get("tags") or {}
        if name == root:
            roots[s.get("trace_id")] = s
        elif name == "eval.created":
            anchors.setdefault(tags.get("eval_id"), s)
        elif name == "sched.status" and tags.get("status") in TERMINAL:
            end = s["t0"] + s["dur"]
            wrote[tags["eval_id"]] = min(end, wrote.get(tags["eval_id"],
                                                        end))
        elif name == "http.serve.eval_get" and \
                tags.get("eval_status") in TERMINAL:
            end = s["t0"] + s["dur"]
            answers[tags["eval_id"]] = min(
                end, answers.get(tags["eval_id"], end))
    out = []
    for eval_id, anchor in anchors.items():
        start = roots.get(anchor.get("trace_id"))
        if start is not None:
            out.append({"eval_id": eval_id, "trace_id": anchor["trace_id"],
                        "start": start["t0"], "wrote": wrote.get(eval_id),
                        "answer": answers.get(eval_id)})
    return out


def unattributed(spans: list, found: list) -> list:
    """[(share uncovered, interval, {name: seconds under spans of that
    name}, {(name before, name after): uncovered seconds})] per
    evaluation."""
    parents = {s.get("parent_id") for s in spans}
    by_trace, reads, begins = {}, {}, {}
    for s in spans:
        if s["dur"] > 0 and s.get("span_id") not in parents:
            by_trace.setdefault(s.get("trace_id"), []).append(s)
        tags = s.get("tags") or {}
        if s["name"] == "http.serve.eval_get":
            reads.setdefault(tags.get("eval_id"), set()).add(
                s.get("trace_id"))
        elif s["name"] == "sched.begin":
            begins.setdefault(tags.get("eval_id"), []).append(s["t0"])
    trace_of = {c["eval_id"]: c["trace_id"] for c in found}
    # A batch's own leaves and its lanes', inside the batch.
    batches = []
    for b in spans:
        if b["name"] != "worker.batch":
            continue
        lo, hi = b["t0"], b["t0"] + b["dur"]
        lanes = {e for e, ts in begins.items()
                 if any(lo <= t <= hi for t in ts)}
        held = clip(by_trace.get(b.get("trace_id"), []), lo, hi)
        for e in lanes:
            held += clip(by_trace.get(trace_of.get(e), []), lo, hi)
        batches.append((lanes, held))
    out = []
    for c in found:
        if c["answer"] is None or c["answer"] <= c["start"]:
            continue
        lo, hi = c["start"], c["answer"]
        mine = list(by_trace.get(c["trace_id"], []))
        for trace_id in reads.get(c["eval_id"], ()):
            mine += by_trace.get(trace_id, [])
        covered = clip(mine, lo, hi)
        # The batch carries this evaluation until its terminal status
        # is written; what the runner does for other lanes after that
        # is not this evaluation's wait.
        until = min(hi, c["wrote"]) if c["wrote"] is not None else hi
        for lanes, held in batches:
            if c["eval_id"] in lanes:
                covered += [(max(a, lo), min(b, until), n)
                            for a, b, n in held
                            if min(b, until) > max(a, lo)]
        by_name = {}
        for a, b, n in covered:
            by_name.setdefault(n, []).append((a, b))
        by_name = {n: union_s(iv) for n, iv in by_name.items()}
        # What lies on either side of each uncovered stretch.
        holes, edge, last = {}, lo, "(socket readable)"
        for a, b, n in sorted(covered) + [(hi, hi, "(answer written)")]:
            if a > edge:
                holes[(last, n)] = holes.get((last, n), 0.0) + a - edge
            if b > edge:
                edge, last = b, n
        share = sum(holes.values()) / (hi - lo)
        out.append((share, hi - lo, by_name, holes))
    return out


def reduce(params: dict, ctx: dict):
    found = chains(ctx["spans"],
                   params.get("root", "http.serve.job_register"))
    if params["what"] == "wake_lag_ms":
        lags = [c["answer"] - c["wrote"] for c in found
                if c["wrote"] is not None and c["answer"] is not None]
        if not lags:
            return None
        ctx["notes"].append(
            f"wake lag: {len(lags)} evaluations answered of "
            f"{sum(1 for c in found if c['wrote'] is not None)} written, "
            f"slowest {1e3 * max(lags):.1f} ms")
        return 1e3 * sum(lags) / len(lags)
    if params["what"] != "unattributed_share":
        raise ValueError(f"job_chain: unknown what={params['what']!r}")
    rows = unattributed(ctx["spans"], found)
    if not rows:
        return None
    whole = sum(row[1] for row in rows)
    names, holes = {}, {}
    for _s, _i, by_name, between in rows:
        for n, secs in by_name.items():
            names[n] = names.get(n, 0.0) + secs
        for pair, secs in between.items():
            holes[pair] = holes.get(pair, 0.0) + secs
    top = sorted(names.items(), key=lambda kv: -kv[1])[:10]
    ctx["notes"].append(
        f"whole path: {len(rows)} evaluations, median interval "
        f"{1e3 * statistics.median(row[1] for row in rows):.1f} ms; share "
        "of all intervals under leaf spans of each name (names overlap): "
        + ", ".join(f"{n} {100 * secs / whole:.1f}%" for n, secs in top))
    ctx["notes"].append(
        "whole path: uncovered, by the leaf spans on either side, as a "
        "share of all intervals: " + ", ".join(
            f"{a} -> {b} {100 * secs / whole:.2f}%" for (a, b), secs in
            sorted(holes.items(), key=lambda kv: -kv[1])[:6]))
    return 100.0 * statistics.median(row[0] for row in rows)
