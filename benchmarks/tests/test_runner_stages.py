"""The readers of the runner's stages (PR 37): ``runner_stages`` on
hand-built spans, every number worked out by hand below; the data
metrics over ``span_mean_ms`` as their files state them; and one
rehearsal of ``fleet131k.storm`` driven so that it re-plans and rides
the kernel, which is where the metrics the plain rehearsal cannot read
(it leaves no straggler and no window crosses the break-even) are read.
"""
import io
import json
import os
from contextlib import redirect_stderr, redirect_stdout

import pytest

import run as bench_run
from conftest import BENCH, ROOT
from test_xplane import reducer

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
# The cells whose traced runs re-plan one by one, and those whose
# windows ride the kernel.
REPLAN = ["baseline4-10k.small", "fleet131k.storm", "fleet100k.stacks",
          "gcd2011-65k.batch10"]
KERNEL = ["fleet131k.storm", "fleet100k.stacks", "gcd2011-65k.batch10"]
# name -> (cells, source, layer, moves, reducer, the span it means)
NEW = {
    "retry_cycle_share": (CELLS, "program_span", "Worker / fused runner",
                          "placements_per_s", "runner_stages", None),
    "retry_ms": (REPLAN, "device_trace", "Worker / fused runner",
                 "placements_per_s", "span_mean_ms", "sched.retry"),
    "retry_begin_ms": (REPLAN, "device_trace", "Worker / fused runner",
                       "placements_per_s", "span_mean_ms", "retry.begin"),
    "retry_finish_ms": (REPLAN, "device_trace", "Worker / fused runner",
                        "placements_per_s", "span_mean_ms", "retry.finish"),
    "retry_submit_ms": (REPLAN, "device_trace",
                        "Plan queue, verify, raft, FSM", "placements_per_s",
                        "span_mean_ms", "retry.submit"),
    "runner_blocked_share": (CELLS, "program_span", "Worker / fused runner",
                             "placements_per_s", "runner_stages", None),
    "runner_stalled_share": (CELLS, "program_span", "Worker / fused runner",
                             "placements_per_s", "runner_stages", None),
    "retry_stalled_share": (REPLAN, "device_trace", "Worker / fused runner",
                            "placements_per_s", "runner_stages", None),
    "plan_encode_ms": (CELLS, "program_span",
                       "Plan queue, verify, raft, FSM", "job_commit_p50_ms",
                       "span_mean_ms", "plan.encode"),
    "place_window_stack_ms": (KERNEL, "device_trace", "Kernels",
                              "placements_per_s", "span_mean_ms",
                              "window.stack"),
    "place_window_upload_ms": (KERNEL, "device_trace", "Kernels",
                               "placements_per_s", "span_mean_ms",
                               "window.upload"),
}


def span(name, t0, dur, span_id=None, parent=None, thread="scheduler-worker",
         **tags):
    return {"name": name, "t0": t0, "dur": dur, "span_id": span_id,
            "parent_id": parent, "trace_id": "t", "thread": thread,
            "tags": tags}


def batch(with_pair=True):
    """One batch [0, 1.1) with 0.1 s of dequeue: held 1.0 s, of it 0.5 s
    on a CPU and 0.3 s in waits the runner chose, so 0.2 s runnable and
    not running.  In it: two prep spans of 0.05 s; a fused kernel window
    of THREE lanes [0.2, 0.4) sharing one pair (0.04 s CPU, 0.10 s
    blocked); and one re-plan [0.5, 0.7) (0.08 s CPU, 0.06 s blocked:
    0.06 s of its 0.2 s stalled) with its four stages.  A span of
    another thread carries a pair too and is not the runner's."""
    def pair(cpu, blocked):
        return {"cpu_s": cpu, "blocked_s": blocked} if with_pair else {}
    out = [span("worker.batch", 0.0, 1.1, "b", lanes=3, cpu_s=0.5,
                **({"blocked_s": 0.3} if with_pair else {})),
           span("worker.dequeue", 0.0, 0.1, "d", parent="b", lanes=3)]
    out += [span("sched.begin", 0.1 + 0.05 * i, 0.05, f"p{i}",
                 **pair(0.04, 0.0)) for i in range(2)]
    out += [span("sched.dispatch", 0.2, 0.2, f"l{i}", fused=3,
                 engine="device", **pair(0.04, 0.10)) for i in range(3)]
    out.append(span("device.dispatch", 0.28, 0.1, "dd", thread="x",
                    fetch_s=0.06, h2d_bytes=1000))
    out.append(span("sched.retry", 0.5, 0.2, "r", twin_s=0.05, twin_slots=1,
                    **pair(0.08, 0.06)))
    out += [span(name, t0, dur, name, parent="r", attempt=1, **pair(dur, 0.0))
            for name, t0, dur in (("retry.begin", 0.50, 0.02),
                                  ("retry.dispatch", 0.52, 0.05),
                                  ("retry.finish", 0.57, 0.03),
                                  ("retry.submit", 0.60, 0.09))]
    out.append(span("sched.status", 0.3, 0.5, "x", thread="http-7",
                    **pair(0.1, 0.1)))
    return out


def read(what, spans):
    ctx = {"spans": spans, "notes": []}
    return reducer("runner_stages").reduce({"what": what}, ctx), ctx["notes"]


def test_shares_of_a_hand_built_batch():
    spans = batch()
    assert read("retry_cycle_share", spans)[0] == pytest.approx(20.0)
    assert read("blocked_share", spans)[0] == pytest.approx(30.0)
    assert read("stalled_share", spans)[0] == pytest.approx(20.0)
    assert read("retry_stalled_share", spans)[0] == pytest.approx(30.0)
    on_cpu = reducer("runner_cycle").reduce(
        {"what": "on_cpu_share"}, {"spans": spans, "notes": []})
    assert on_cpu + 30.0 + 20.0 == pytest.approx(100.0)
    with pytest.raises(ValueError):
        read("idle", spans)


def test_a_fused_window_of_three_lanes_is_one_window_in_the_table():
    _value, notes = read("blocked_share", batch())
    rows = {n.split(":")[0].strip(): n for n in notes if n.startswith("  ")}
    # Three lane spans, one interval, one pair: one window of 0.2 s,
    # 20% on a CPU, 50% blocked, 30% the rest.
    assert "sched.dispatch [device]: 1, 0.200s, 200.000 ms; 20.0% / 50.0% " \
        "/ 30.0%" in rows["sched.dispatch [device]"]
    assert "device.dispatch of a kernel window: 1, mean 100.000 ms, of it " \
        "in the fetch 60.000 ms; 1000 bytes uploaded a window" in notes[-1]
    assert "sched.begin: 2, 0.100s, 50.000 ms; 80.0% / 0.0% / 20.0%" \
        in rows["sched.begin"]
    assert "sched.retry: 1, 0.200s, 200.000 ms; 40.0% / 30.0% / 30.0%" \
        in rows["sched.retry"]
    # The batch's shares are of the seconds it was held, not its span.
    assert "worker.batch: 1, 1.000s, 1000.000 ms; 50.0% / 30.0% / 20.0%" \
        in rows["worker.batch"]
    assert "retry.dispatch: 1, 0.050s" in rows["retry.dispatch"]
    # Another thread's span is not a stage of the runner's.
    assert "sched.status" not in rows
    # 0.1 of prep + 0.2 of window + 0.2 of re-plan under stage spans.
    assert "holding a batch 1.000s, of it under no stage span 0.500s" \
        in rows["holding a batch 1.000s, of it under no stage span 0.500s"]
    # One table a run, whichever metric is read first.
    ctx = {"spans": batch(), "notes": []}
    for what in ("blocked_share", "stalled_share", "retry_stalled_share"):
        reducer("runner_stages").reduce({"what": what}, ctx)
    assert sum(n.startswith("runner stages") for n in ctx["notes"]) == 1


def test_a_batch_with_no_replan():
    spans = [s for s in batch() if not s["name"].startswith(
        ("sched.retry", "retry."))]
    assert read("retry_cycle_share", spans)[0] == 0.0
    assert read("retry_stalled_share", spans)[0] is None
    assert read("blocked_share", spans)[0] == pytest.approx(30.0)


def test_the_parents_spans_read_nothing_and_nothing_raises():
    """No ``blocked_s`` anywhere (the parent's program): the three
    shares that need it read nothing; the re-plans' share of the cycle
    needs no tag and reads what it reads on the change."""
    spans = batch(with_pair=False)
    for what in ("blocked_share", "stalled_share", "retry_stalled_share"):
        value, notes = read(what, spans)
        assert value is None
        assert not any(n.startswith("runner stages") for n in notes)
    assert read("retry_cycle_share", spans)[0] == pytest.approx(20.0)
    for what in ("retry_cycle_share", "blocked_share", "stalled_share",
                 "retry_stalled_share"):
        assert read(what, [])[0] is None
        assert read(what, [span("sched.retry", 0.0, 1.0, "r")])[0] is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_is_listed_as_the_issue_states_it(name):
    cells, source, layer, moves, reader, _span = NEW[name]
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    assert (entry["workloads"], entry["source"], entry["layer"],
            entry["moves"], entry["better"]) == \
        (cells, source, layer, moves, "lower")
    assert entry["unit"] == ("ms" if name.endswith("_ms") else "%")
    spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                       f"{name}.json")))
    assert spec["reducer"] == reader


@pytest.mark.parametrize("name", sorted(
    n for n, spec in NEW.items() if spec[5] is not None))
def test_data_metrics_read_their_span(name):
    """The data metrics: the mean of the spans of their one name, in
    ms; nothing where the program writes none (the parent's; the twin's
    path for the window's two)."""
    spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                       f"{name}.json")))
    wanted = NEW[name][5]
    assert spec["params"] == {"span": wanted}
    spans = batch() + [span(wanted, 2.0, 0.010, "y"),
                       span(wanted, 3.0, 0.030, "z")]
    mine = [s["dur"] for s in spans if s["name"] == wanted]
    got = reducer(spec["reducer"]).reduce(spec["params"],
                                          {"spans": spans, "notes": []})
    assert got == pytest.approx(1e3 * sum(mine) / len(mine))
    none = [s for s in spans if s["name"] != wanted]
    assert reducer(spec["reducer"]).reduce(
        spec["params"], {"spans": none, "notes": []}) is None


def test_a_contended_rehearsal_on_the_kernel_reads_every_new_metric(
        monkeypatch):
    """``fleet131k.storm`` rehearsed with sixteen clients (its plain
    rehearsal's four leave no straggler) and the break-even at nought
    (its 1,024 nodes cross none): the one-by-one re-plans and the kernel's
    windows are there, and the line carries all eleven metrics.  The
    three shares of the batch make 100; a re-plan's four stages make
    its span but for the status write that lies in it."""
    from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler

    monkeypatch.setattr(JaxBinPackScheduler, "HOST_SINGLE_SHOT_COST", 0)
    monkeypatch.setattr(JaxBinPackScheduler, "HOST_ALWAYS_COST", 0)
    monkeypatch.setitem(bench_run.REHEARSAL, "clients", 16)
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = bench_run.main(
            ["--workload", "fleet131k.storm", "--seed", str(2 ** 31 + 37),
             "--seconds", "2", "--trace", "1", "--rehearse"],
            rehearsal_is_never_correct=False)
    assert rc == 0, err.getvalue()[-2000:]
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"] is True
    stderr = err.getvalue()
    assert "'dropped': 0" in stderr
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(got), set(NEW) - set(got)
    assert got["runner_on_cpu_share"] + got["runner_blocked_share"] + \
        got["runner_stalled_share"] == pytest.approx(100.0)
    assert 0.0 < got["retry_cycle_share"] < 100.0
    assert got["device_lane_share"] == 100.0
    table = {line.split("]", 1)[1].split(":")[0].strip(): line
             for line in stderr.splitlines() if "]   " in line}
    dispatch_ms = float(
        table["retry.dispatch [device]"].split(", ")[2].split()[0])
    parts = got["retry_begin_ms"] + dispatch_ms + got["retry_finish_ms"] \
        + got["retry_submit_ms"]
    assert parts <= got["retry_ms"] * (1 + 1e-9)
    assert parts + got["status_apply_ms"] >= 0.9 * got["retry_ms"]
    assert "window.stack" in table and "window.upload" in table
    assert "device.dispatch of a kernel window" in stderr
