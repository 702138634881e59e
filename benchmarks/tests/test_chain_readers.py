"""The readers PR 26 added, on hand-built span lists (every number
worked out by hand in the comments), the clock alignment on the
recorded v5e trace, and both cells rehearsed with ``--trace 1``: every
new metric is in the line (the one that needs a device plane may be
missing on a CPU, as ``device_idle_share`` is)."""
import json
import os

import pytest

import xplane
from test_rehearsal import BENCH, rehearse
from test_xplane import reducer

HERE = os.path.dirname(os.path.abspath(__file__))

NEW = ["sched_prep_ms", "plan_queued_ms", "applier_verify_ms",
       "store_upsert_ms", "register_apply_ms", "status_apply_ms",
       "http_register_ms", "wake_lag_ms", "blocking_wake_useful_share",
       "runner_busy_share", "runner_on_cpu_share",
       "commit_unattributed_share", "idle_attributed_share"]


def span(name, t0, dur, trace="t1", sid=None, parent=None, **tags):
    return {"name": name, "t0": t0, "dur": dur, "trace_id": trace,
            "span_id": sid or f"{name}@{t0}", "parent_id": parent,
            "thread": "x", "tags": tags}


def chain_spans():
    """Two evaluations in one fused batch.

    e1: socket readable at 0.0, answered at 1.0.  Its own leaves:
    server.apply [0.00, 0.10), broker.wait [0.10, 0.30), sched.begin
    [0.30, 0.40), sched.submit's child sched.status [0.70, 0.80), and
    query.blocked of the answering read [0.20, 0.78).  The batch
    [0.28, 0.85) adds e2's sched.begin [0.40, 0.50) and the shared lane
    spans sched.dispatch [0.50, 0.70) (same t0/dur in both trees:
    counted once); e2's sched.retry [0.80, 0.85) comes after e1's
    status is written and is not e1's wait.  Union [0, 0.80) of
    [0, 1.0]: 20 % uncovered, all of it between the status write and
    the answer.
    e2: readable at 0.05, never answered (no terminal read)."""
    return [
        span("http.serve.job_register", 0.0, 0.12, "t1", "r1"),
        span("server.apply.job_register", 0.0, 0.10, "t1", parent="r1"),
        span("eval.created", 0.10, 0.0, "t1", "a1", "r1", eval_id="e1"),
        span("broker.wait", 0.10, 0.20, "t1", parent="a1", eval_id="e1"),
        span("sched.begin", 0.30, 0.10, "t1", parent="a1", eval_id="e1"),
        span("sched.dispatch", 0.50, 0.20, "t1", parent="a1",
             eval_id="e1"),
        span("sched.submit", 0.70, 0.12, "t1", "s1", "a1", eval_id="e1"),
        span("sched.status", 0.70, 0.10, "t1", parent="s1", eval_id="e1",
             status="complete"),
        span("http.serve.job_register", 0.05, 0.10, "t2", "r2"),
        span("eval.created", 0.12, 0.0, "t2", "a2", "r2", eval_id="e2"),
        span("sched.begin", 0.40, 0.10, "t2", parent="a2", eval_id="e2"),
        span("sched.dispatch", 0.50, 0.20, "t2", parent="a2",
             eval_id="e2"),
        span("sched.retry", 0.80, 0.05, "t2", parent="a2", eval_id="e2"),
        span("sched.status", 0.80, 0.04, "t2", parent="a2", eval_id="e2",
             status="complete"),
        span("worker.batch", 0.28, 0.57, "b1", "b1", lanes=2, cpu_s=0.11),
        span("worker.dequeue", 0.28, 0.02, "b1", parent="b1"),
        # Reads of e1: a wake that changed nothing, then the answer.
        span("http.serve.eval_get", 0.15, 0.04, "g0", "g0", eval_id="e1",
             eval_status="pending", fired="index", changed=0),
        span("http.serve.eval_get", 0.20, 0.80, "g1", "g1", eval_id="e1",
             eval_status="complete", fired="index", changed=1),
        span("query.blocked", 0.20, 0.58, "g1", parent="g1"),
        # A read of e2 that found it pending: not an answer.
        span("http.serve.eval_get", 0.50, 0.01, "g2", "g2", eval_id="e2",
             eval_status="pending", fired="immediate", changed=0),
    ]


def ctx_of(spans, **more):
    return dict({"spans": spans, "notes": []}, **more)


def test_coverage_counts_overlapping_lane_spans_once():
    ctx = ctx_of(chain_spans())
    got = reducer("job_chain").reduce({"what": "unattributed_share"}, ctx)
    assert got == pytest.approx(20.0)
    assert "1 evaluations, median interval 1000.0 ms" in ctx["notes"][0]
    assert "sched.status -> (answer written) 20.00%" in ctx["notes"][1]


def test_union_of_intervals():
    union = reducer("job_chain").union_s
    assert union([(0.0, 1.0), (0.5, 1.5), (3.0, 4.0)]) == pytest.approx(2.5)
    assert union([(0.0, 2.0), (0.5, 1.0)]) == pytest.approx(2.0)
    assert union([]) == 0.0


def test_wake_lag_leaves_an_unanswered_eval_out():
    # e1: written at 0.80, answered at 1.00 -> 200 ms; e2 written at
    # 0.84, never answered: left out, not counted as 0.
    ctx = ctx_of(chain_spans())
    got = reducer("job_chain").reduce({"what": "wake_lag_ms"}, ctx)
    assert got == pytest.approx(200.0)
    assert "1 evaluations answered of 2 written" in ctx["notes"][0]


@pytest.mark.parametrize("what", ["wake_lag_ms", "unattributed_share"])
def test_chain_with_no_answer_span_is_nothing(what):
    spans = [s for s in chain_spans()
             if s["name"] != "http.serve.eval_get"]
    assert reducer("job_chain").reduce({"what": what},
                                       ctx_of(spans)) is None
    # A program without the new spans at all (the parent commit).
    old = [s for s in spans if not s["name"].startswith(
        ("http.", "worker.", "server.", "sched.status", "query."))]
    assert reducer("job_chain").reduce({"what": what}, ctx_of(old)) is None


def test_wake_share_reads_tags():
    # Reads fired by the index: g0 (changed=0), g1 (changed=1) -> 50 %.
    params = {"span": "http.serve.eval_get", "where": {"fired": "index"},
              "tag": "changed", "equals": 1, "scale": 100}
    ctx = ctx_of(chain_spans())
    assert reducer("span_tag_share").reduce(params, ctx) == \
        pytest.approx(50.0)
    assert "3 spans, 2 with" in ctx["notes"][0]
    assert reducer("span_tag_share").reduce(
        dict(params, span="http.serve.nothing"), ctx_of([])) is None


def test_runner_cycle():
    # Two batches: [0.28, 0.85) with 0.02 s of dequeue and 0.11 s of
    # CPU; [0.85, 1.28) with 0.23 s of dequeue and 0.10 s of CPU.
    # Holding a batch: 0.55 + 0.20 = 0.75 s of a 1.00 s extent -> 75 %;
    # on the CPU 0.21 s of those 0.75 s -> 28 %.
    spans = chain_spans() + [
        span("worker.batch", 0.85, 0.43, "b2", "b2", lanes=1, cpu_s=0.10),
        span("worker.dequeue", 0.85, 0.23, "b2", parent="b2")]
    runner = reducer("runner_cycle")
    ctx = ctx_of(spans)
    assert runner.reduce({"what": "busy_share"}, ctx) == \
        pytest.approx(75.0)
    assert "2 batches over 1.000s" in ctx["notes"][0]
    assert runner.reduce({"what": "on_cpu_share"}, ctx) == \
        pytest.approx(28.0)
    assert runner.reduce({"what": "busy_share"}, ctx_of([])) is None
    with pytest.raises(ValueError):
        runner.reduce({"what": "idle"}, ctx)


# -- the clock alignment, on the trace recorded on a v5e (PR 24) -----------
SLICE_S = 6.004533569
SPAN_CLOCK_OFFSET = 1000.0     # perf_counter - span clock
DEVICE_MINUS_PERF = 5000.0     # what the reader has to find


def recorded(shift_s=0.0, async_=1, dispatch_s=0.0002, lead_s=0.0001):
    """The recorded trace and one synthetic ``device.dispatch`` span
    per module event: enqueued ``lead_s`` before the event starts on
    the device (moved by ``shift_s``), on a span clock 1,000 s behind
    ``perf_counter``, the device clock 5,000 s ahead of it."""
    path = os.path.join(HERE, "fixtures", "v5e_scatter.xplane.pb")
    trace = xplane.reduce(xplane.load(path), SLICE_S)
    first = trace["modules"][0][1]
    slice_lo = first - DEVICE_MINUS_PERF - 0.5
    trace["slice_perf"] = (slice_lo, slice_lo + SLICE_S)
    spans = []
    for _name, start, _dur in trace["modules"]:
        tags = {"program": "_scatter_jit_impl", "rows": 8}
        if async_:
            tags["async"] = 1
        spans.append({"name": "device.dispatch", "tags": tags,
                      "t0": start - DEVICE_MINUS_PERF - SPAN_CLOCK_OFFSET
                      - lead_s + shift_s, "dur": dispatch_s})
    return ctx_of(spans, trace=trace, span_clock_offset=SPAN_CLOCK_OFFSET)


def test_alignment_finds_the_offset_of_the_recorded_trace():
    ctx = recorded()
    got = reducer("idle_attribution").align(ctx)
    assert got["pairs"] == 10 and got["misfits"] == 0
    # device clock - span clock = 5,000 + 1,000 s, plus the 0.1 ms
    # every event starts after its enqueue.
    assert got["offset"] == pytest.approx(6000.0001, abs=1e-6)
    assert got["residual"] == pytest.approx(0.0, abs=1e-6)
    assert "10 pairs of 10 module events" in ctx["notes"][0]
    assert "device clock - perf_counter = 5000.0001" in ctx["notes"][0]


def test_alignment_slides_over_a_dispatch_outside_the_slice():
    # The first dispatch's event fell before the trace started: nine
    # events, ten spans.  The nine pair with the LAST nine spans.
    ctx = recorded()
    ctx["trace"]["modules"] = ctx["trace"]["modules"][1:]
    got = reducer("idle_attribution").align(ctx)
    assert got["pairs"] == 9 and got["misfits"] == 0
    assert got["offset"] == pytest.approx(6000.0001, abs=1e-6)


def test_alignment_refuses_events_outside_their_spans():
    # Synchronous dispatches 0.2 ms long whose events start up to ten
    # seconds apart from where their spans say: a median can be taken,
    # but the events do not fit the spans it lays them on.
    ctx = recorded(async_=0)
    for k, s in enumerate(ctx["spans"]):
        s["t0"] += 0.5 * k * (-1) ** k
    align = reducer("idle_attribution")
    assert align.align(ctx) is None
    assert "events outside their spans" in ctx["notes"][0]
    assert align.reduce({}, ctx) is None
    # Nothing to pair at all: no alignment, and a note that says why.
    none = recorded()
    none["spans"] = []
    assert align.align(none) is None
    assert "no device.dispatch span" in none["notes"][0]
    # No device plane (a CPU rehearsal): nothing, silently.
    assert align.reduce({}, ctx_of([], trace=None,
                                   span_clock_offset=0.0)) is None


def test_idle_attribution_on_the_recorded_trace():
    """Ten scatters in six seconds: the device is idle for all but
    0.1 ms.  One ``worker.batch`` span over the first half of the slice
    (its last 0.5 s a wait for work), nothing over the second half:
    2.5 s of ~6.0 s attributed."""
    ctx = recorded()
    lo = ctx["trace"]["slice_perf"][0] - SPAN_CLOCK_OFFSET
    ctx["spans"] += [
        span("worker.batch", lo, 3.0, "b", "b", lanes=1, cpu_s=0.1),
        span("worker.dequeue", lo + 2.5, 0.5, "b", parent="b"),
        span("sched.finish", lo + 0.2, 2.0, "e", parent="missing")]
    got = reducer("idle_attribution").reduce({}, ctx)
    assert got == pytest.approx(100 * 2.5 / SLICE_S, rel=1e-3)
    gaps = [n for n in ctx["notes"] if n.startswith("idle gap")]
    assert len(gaps) == 10
    assert any("sched.finish" in n for n in gaps)


# -- both cells, rehearsed --------------------------------------------------
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_line_carries_every_new_metric(cell):
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert all(cell in entries[name]["workloads"] for name in NEW)
    for name in NEW:
        spec = json.load(open(os.path.join(
            os.path.dirname(HERE), "layer_metrics", f"{name}.json")))
        assert os.path.isfile(os.path.join(
            os.path.dirname(HERE), "reducers", f"{spec['reducer']}.py"))
    result, stderr = rehearse(cell, 1, seed=2**31 + 26)
    got = result["metrics"]
    missing = set(NEW) - set(got)
    assert missing <= {"idle_attributed_share"}, missing
    for name in set(NEW) - missing:
        assert isinstance(got[name]["value"], float)
        assert got[name]["unit"] == entries[name]["unit"]
    assert 0.0 <= got["commit_unattributed_share"]["value"] <= 100.0
    assert 0.0 < got["runner_busy_share"]["value"] <= 100.0
    assert 0.0 < got["runner_on_cpu_share"]["value"] <= 100.0
    assert 0.0 <= got["blocking_wake_useful_share"]["value"] <= 100.0
    assert "'dropped': 0" in stderr
