"""``fleet100k.stacks``: the cell whose lanes carry three REAL kernel
slots (a job of 'web' x 10, 'frontend' x 5, 'cache' x 1: three asks
that do not dedupe) on 100,000 nodes.

At the rehearsal's 512 nodes no window crosses the executor's
break-even, so the parametrised tests beside this file (which take the
cell from ``BENCHMARK.json`` like every other) rehearse it on the numpy
twin.  Here, as in ``test_fleet131k.py``, the rehearsal is driven with
the break-even at nought, so that every window and every re-plan goes to
the device path the cell takes at its own size: the ``stack_lanes``
plug-in has to have compiled whatever the window then meets, the
comparison has to pass on the kernel's own three-slot picks and scores,
and both controls and every planted fault have to fail it there.
"""
import json
import os

import pytest

import run as bench_run
from conftest import BENCH, ROOT
from test_fleet131k import device_windows, load  # noqa: F401 (fixture)
import test_fleet131k

CELL = "fleet100k.stacks"
BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
TIERS = [("web", 10, 500, 256, 50, ["http"]),
         ("frontend", 5, 500, 128, 100, ["http", "https"]),
         ("cache", 1, 500, 256, 10, ["redis"])]


@pytest.fixture
def drive(monkeypatch):
    """``test_fleet131k.drive`` on this cell."""
    monkeypatch.setattr(test_fleet131k, "CELL", CELL)
    return test_fleet131k.drive


def reducer(metric):
    spec = load("layer_metrics", f"{metric}.json")
    module = bench_run.load_module("reducers", spec["reducer"])
    return lambda spans: module.reduce(spec["params"],
                                       {"spans": spans, "notes": []})


def test_the_cell_is_the_deployment_the_files_state():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("fleet100k", "stacks64", 1)
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == "fleet100k")
    config = load("configs", "fleet100k.json")
    wide = load("configs", "fleet131k.json")
    assert config["nodes"] == 100000
    assert entry["reduced"] == config["reduced"] == ["servers"]
    assert entry["source"] == config["source"]
    assert set(config) == set(wide)
    # The machine, the scoring and the five guarantees are fleet131k's,
    # word for word.
    for key in ("node", "scoring", "guarantees", "servers"):
        assert config[key] == wide[key], key
    # Upstream's rule: the TTL keeps the fleet under 50 beats a second,
    # and a node beats at half its TTL.
    assert config["heartbeat_interval_s"] == (100000 // 50) // 2
    # Every figure this deployment sets itself is under ``assumed``.
    assert {"node", "heartbeat_interval_s", "clients", "job"} == \
        set(config["assumed"])
    assert set(config["stated_by_source"]["groups"]) == \
        {name for name, *_rest in TIERS}
    traffic = load("traffic", "stacks64.json")
    storm = load("traffic", "storm64.json")
    assert traffic["generator"] == "closed_loop_stack"
    assert traffic["clients"] == 64
    assert (traffic["job"]["groups"], traffic["job"]["count"]) == (3, 10)
    assert [(t["name"], t["count"], t["cpu"], t["memory_mb"], t["mbits"],
             t["dynamic_ports"]) for t in traffic["job"]["tiers"]] == TIERS
    # The web tier is the job .small and .storm run.
    assert {k: v for k, v in traffic["job"]["tiers"][0].items()
            if k not in ("name", "count")} == storm["job"]["ask"]
    for key in ("job_timeout_s", "wait", "warmup", "check", "trace"):
        assert traffic[key] == storm[key], key
    assert [p["module"] for p in traffic["prewarm"]] == \
        ["scatter_rows", "stack_lanes"]
    reports = {m["name"] for m in BENCHMARK["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    assert reports == {"placements_per_s", "job_commit_p50_ms", "setup_s"}
    listed = {m["name"]: m["workloads"] for m in BENCHMARK["per_layer"]}
    assert all(CELL in cells for cells in listed.values())
    cells = [w["name"] for w in BENCHMARK["workloads"]]
    assert listed["slots_per_lane"] == cells
    assert listed["real_slot_share"] == ["fleet131k.storm", CELL]
    assert listed["twin_slot_ms"] == ["baseline4-10k.small",
                                      "fleet131k.storm", CELL]


@pytest.mark.parametrize("groups, count, want", [
    (3, 10, [("web", 10), ("frontend", 5), ("cache", 1)]),
    (24, 24, [("web", 10), ("frontend", 5), ("cache", 1)]),  # a rehearsal
    (2, 4, [("web", 4), ("frontend", 4)]),
    (1, 1, [("web", 1)]),
])
def test_the_generator_gives_the_tiers_in_order_under_the_caps(groups, count,
                                                               want):
    generator = bench_run.load_module("generators", "closed_loop_stack")
    job = dict(load("traffic", "stacks64.json")["job"], groups=groups,
               count=count)
    capped = bench_run.rehearsal_traffic(
        {"job": job, "clients": 64})["job"]
    spec = generator.job_spec(capped, 35, 7, 3)
    assert [(g["name"], g["count"]) for g in spec["groups"]] == want
    assert spec["asked"] == sum(n for _name, n in want)
    assert spec["type"] == "service"
    for g, (name, _n, cpu, memory, mbits, ports) in zip(spec["groups"],
                                                        TIERS):
        assert (g["name"], g["cpu"], g["memory_mb"], g["mbits"],
                g["dynamic_ports"]) == (name, cpu, memory, mbits, ports)
    # Job k of client c is a function of (seed, c, k) alone.
    assert generator.job_spec(capped, 35, 7, 3) == spec
    others = [generator.job_spec(capped, *key)["id"]
              for key in ((36, 7, 3), (35, 8, 3), (35, 7, 4))]
    assert len({spec["id"], *others}) == 4


def test_forced_device_windows_compile_nothing_and_are_correct(
        device_windows, drive):  # noqa: F811
    result, outside, stderr = drive(35, trace=1)
    assert result["correct"] is True and not outside
    assert "compilations inside the window: 0 programs" in stderr
    metrics = result["metrics"]
    assert metrics["device_lane_share"]["value"] == 100.0
    assert metrics["device_dispatch_share"]["value"] == 100.0
    # A lane carries the stack's three slots, of a padded eight; a
    # re-plan of what a partial commit left carries fewer.
    assert 1.5 < metrics["slots_per_lane"]["value"] <= 3.0
    assert 12.5 < metrics["real_slot_share"]["value"] <= 37.5
    # Nothing ran on the twin, and a CPU has no device plane.
    for name in ("twin_slot_ms", "place_window_device_ms",
                 "place_kernel_hbm_share"):
        assert name not in metrics
    limit = result["checks"]["score_gap"]["limit"]
    assert result["checks"]["score_gap"]["value"] < limit / 10
    assert result["checks"]["picks"]["value"] > 0


@pytest.mark.parametrize("control, caught_by", [
    ("bf16", "score_gap"), ("worst_first", "score_regret")])
def test_controls_fail_the_kernels_own_windows(device_windows, drive,  # noqa: F811
                                               control, caught_by):
    result, outside, _stderr = drive(36, "--control", control)
    assert result["correct"] is False and caught_by in outside
    assert outside <= {"score_gap", "score_regret"}
    limit = result["checks"]["score_gap"]["limit"]
    assert result["checks"]["program_score_gap"]["value"] < limit / 10


@pytest.mark.parametrize("fault, caught_by", [
    ("state_left_unchanged", {"placement_mismatch"}),
    ("half_of_every_plan", {"placement_mismatch"}),
    ("answer_altered", {"score_gap", "failed_jobs"}),
])
def test_faults_fail_the_kernels_own_windows(device_windows, drive,  # noqa: F811
                                             fault, caught_by):
    result, outside, _stderr = drive(37, fault=fault)
    assert result["correct"] is False
    assert caught_by & outside


def span(name, dur=0.0, **tags):
    return {"name": name, "t0": 0.0, "dur": dur, "tags": tags}


def test_the_three_slot_readers_on_hand_built_spans():
    """A stack lane, a re-plan lane of one slot, a fused device window
    of both on a padded axis of 8, a twin lane and a twin re-plan; the
    parent's spans (no ``slots`` anywhere but on the fused window) read
    nothing, never nought."""
    spans = [
        span("sched.begin", 0.004, slots=3),
        span("sched.begin", 0.002, slots=1),
        span("sched.begin", 0.001),             # no placement needed
        span("device.dispatch", 0.1, program="_place_rounds_batched",
             lanes=2, b_pad=2, g_pad=8, k_cap=16, rounds=1, slots=4),
        span("device.dispatch", 0.001, program="_scatter_jit_impl", rows=64),
        span("sched.dispatch", 0.1, engine="device", slots=3),
        span("sched.dispatch", 0.021, engine="host", slots=3),
        span("sched.retry", 0.050, host_calls=1, twin_s=0.007,
             twin_slots=1),
    ]
    assert reducer("slots_per_lane")(spans) == 2.0
    assert reducer("real_slot_share")(spans) == 25.0
    assert reducer("twin_slot_ms")(spans) == pytest.approx(7.0)
    parent = [
        span("sched.begin", 0.004),
        span("device.dispatch", 0.1, lanes=2, b_pad=2, g_pad=8, slots=2),
        span("sched.dispatch", 0.021, engine="host"),
        span("sched.retry", 0.050, host_calls=1),
    ]
    assert reducer("slots_per_lane")(parent) is None
    assert reducer("real_slot_share")(parent) == 12.5
    assert reducer("twin_slot_ms")(parent) is None
    assert reducer("twin_slot_ms")([]) is None
