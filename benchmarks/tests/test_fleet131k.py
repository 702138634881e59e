"""``fleet131k.storm``: the cell whose fused windows ride the XLA
placement kernel.

At the rehearsal's 512 nodes no window crosses the executor's
break-even, so the parametrised tests beside this file (which take the
cell from ``BENCHMARK.json`` like every other) rehearse it on the numpy
twin.  Here the rehearsal is driven with the break-even at nought, so
that every window and every re-plan goes to the device path the cell
takes at its own size: the ``place_lanes`` plug-in has to have compiled
whatever the window then meets, the comparison has to pass on the
kernel's own picks and scores, and both controls and every planted
fault have to fail it there as they fail it on the twin.
"""
import io
import json
import os
from contextlib import nullcontext, redirect_stderr, redirect_stdout

import pytest

import run as bench_run
from conftest import BENCH, ROOT
from faults import FAULTS

CELL = "fleet131k.storm"
BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


@pytest.fixture
def device_windows(monkeypatch):
    """Every dispatch to the XLA kernels, with no lever of the
    environment set: the two thresholds of the program's one comparison
    at nought for the test's duration."""
    from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler

    monkeypatch.setattr(JaxBinPackScheduler, "HOST_SINGLE_SHOT_COST", 0)
    monkeypatch.setattr(JaxBinPackScheduler, "HOST_ALWAYS_COST", 0)


def drive(seed, *flags, fault=None, trace=0):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err), \
            (FAULTS[fault]() if fault else nullcontext()):
        rc = bench_run.main(
            ["--workload", CELL, "--seed", str(seed), "--seconds", "2",
             "--trace", str(trace), "--rehearse", *flags],
            rehearsal_is_never_correct=False)
    assert rc == 0, err.getvalue()[-2000:]
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    outside = {k for k, c in result["checks"].items()
               if c["limit"] is not None and c["value"] > c["limit"]}
    assert result["correct"] is (not outside)
    return result, outside, err.getvalue()


def test_the_cell_is_the_deployment_the_files_state():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("fleet131k", "storm64", 1)
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == "fleet131k")
    config = load("configs", "fleet131k.json")
    small = load("configs", "baseline4-10k.json")
    assert config["nodes"] == 131072 == 2 ** 17
    assert entry["reduced"] == config["reduced"] == ["servers"]
    assert entry["source"] == config["source"]
    # The machine, the scoring and the five guarantees are baseline4-10k's,
    # word for word.
    for key in ("node", "scoring", "guarantees", "servers"):
        assert config[key] == small[key], key
    # Upstream's rule: the TTL keeps the fleet under 50 beats a second,
    # and a node beats at half its TTL.
    assert config["heartbeat_interval_s"] == (131072 // 50) // 2
    traffic = load("traffic", "storm64.json")
    assert traffic["generator"] == "closed_loop"
    assert traffic["clients"] >= 64
    assert traffic["job"] == load("traffic", "small.json")["job"]
    assert [p["module"] for p in traffic["prewarm"]] == \
        ["scatter_rows", "place_lanes"]
    reports = {m["name"] for m in BENCHMARK["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    assert reports == {"placements_per_s", "job_commit_p50_ms", "setup_s"}
    for name in ("device_lane_share", "place_window_device_ms",
                 "place_kernel_hbm_share", "device_dispatch_share",
                 "sched_dispatch_ms"):
        metric = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
        assert CELL in metric["workloads"], name


def test_forced_device_windows_compile_nothing_and_are_correct(
        device_windows):
    result, outside, stderr = drive(33, trace=1)
    assert result["correct"] is True and not outside
    assert "compilations inside the window: 0 programs" in stderr
    metrics = result["metrics"]
    assert metrics["device_lane_share"]["value"] == 100.0
    assert metrics["device_dispatch_share"]["value"] == 100.0
    # No device plane on a CPU: the readers of the kernel's device time
    # find nothing.
    assert "place_window_device_ms" not in metrics
    assert "place_kernel_hbm_share" not in metrics
    limit = result["checks"]["score_gap"]["limit"]
    assert result["checks"]["score_gap"]["value"] < limit / 10
    assert result["checks"]["picks"]["value"] > 0


@pytest.mark.parametrize("control, caught_by", [
    ("bf16", "score_gap"), ("worst_first", "score_regret")])
def test_controls_fail_the_kernels_own_windows(device_windows, control,
                                               caught_by):
    result, outside, _stderr = drive(34, "--control", control)
    assert result["correct"] is False and caught_by in outside
    assert outside <= {"score_gap", "score_regret"}
    limit = result["checks"]["score_gap"]["limit"]
    assert result["checks"]["program_score_gap"]["value"] < limit / 10


@pytest.mark.parametrize("fault, caught_by", [
    ("state_left_unchanged", {"placement_mismatch"}),
    ("half_of_every_plan", {"placement_mismatch"}),
    ("answer_altered", {"score_gap", "failed_jobs"}),
])
def test_faults_fail_the_kernels_own_windows(device_windows, fault,
                                             caught_by):
    result, outside, _stderr = drive(35, fault=fault)
    assert result["correct"] is False
    assert caught_by & outside


def test_kernel_bytes_counts_real_lanes_and_slots_only():
    """The floor a fused window has to move takes nothing of the
    implementation: padding lanes or slots adds no credit."""
    import kernel_bytes

    tags = {"lanes": 64, "b_pad": 64, "g_pad": 8, "k_cap": 16,
            "rounds": 1, "n_pad": 131072}
    floor = kernel_bytes.fused_rounds_window(tags)
    assert floor == 3 * 131072 * 6 * 4 + 64 * 131072 + 16 * 64 * 8
    assert kernel_bytes.fused_rounds_window(
        dict(tags, b_pad=128, g_pad=16)) == floor
    assert kernel_bytes.fused_rounds_window(dict(tags, slots=128)) > floor
    assert kernel_bytes.fused_rounds_window(dict(tags, lanes=33)) < floor


def test_the_kernel_readers_pair_windows_with_their_module_events():
    """Two fused windows in the slice and one before it: the readers
    take the two, by the device time of the module events they pair
    with; no window in the slice, nothing; a chip that the table of
    peaks does not hold is an error, not a default."""
    reader = bench_run.load_module("reducers", "place_kernel_hbm")
    tags = {"program": "_place_rounds_batched", "lanes": 63, "b_pad": 64,
            "g_pad": 8, "k_cap": 16, "rounds": 1, "n_pad": 131072,
            "h2d_bytes": 100680000, "d2h_bytes": 65536}
    spans = [{"name": "device.dispatch", "t0": t0, "dur": 0.060,
              "tags": dict(tags)} for t0 in (0.5, 2.0, 3.0)]
    spans.append({"name": "device.dispatch", "t0": 2.5, "dur": 0.001,
                  "tags": {"program": "_scatter_jit_impl", "rows": 64,
                           "n_pad": 131072, "async": 1}})
    # Device clock = span clock + 100 s; the slice opens at 1.0.
    modules = [("jit__place_rounds_batched(77)", 102.008, 0.050),
               ("jit__scatter_jit_impl(5)", 102.5001, 0.0001),
               ("jit__place_rounds_batched(77)", 103.008, 0.052)]
    ctx = {"spans": spans, "span_clock_offset": 0.0, "notes": [],
           "trace": {"modules": modules, "slice_perf": (1.0, 6.0)}}
    params = {"tags": ["b_pad", "k_cap", "rounds"], "peaks": "peaks.json"}
    assert reader.reduce(dict(params, what="device_ms"), ctx) == \
        pytest.approx(51.0)
    with pytest.raises(KeyError):       # the CPU is not in peaks.json
        reader.reduce(dict(params, what="hbm_share"), ctx)
    peaks = load("peaks.json")
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819.0e9
    quiet = dict(ctx, spans=spans[-1:])
    assert reader.reduce(dict(params, what="device_ms"), quiet) is None
    assert reader.reduce(dict(params, what="hbm_share"), quiet) is None
    assert reader.reduce(dict(params, what="device_ms"),
                         dict(ctx, trace=None)) is None
