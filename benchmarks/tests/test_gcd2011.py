"""``gcd2011-65k.batch10``: BASELINE.json configs[1] (a batch job of ten
task groups, each under one hard constraint stanza, four with
``distinct_hosts``) on 65,000 nodes in the machine mix of Google's 2011
cluster trace: eight node classes, ten masked kernel slots a lane.

The files first: the classes are the source's table at the stated scale,
each stanza admits the share of the fleet the traffic file says, every
group's admitted capacity holds four times what one run can place of it,
and the prep's gain bound keeps the rounds kernel.  Then a rehearsal of
the cell on both engines, with every group's copies capped at three (a
2 s rehearsal of ten copies fills the 65 nodes of the one class that
group 5 admits at 1,024 nodes): the numpy twin at the rehearsal's size,
and the XLA kernel with the break-even at nought.  Every check reads 0
there; both controls and the two planted faults that ignore a stanza or
``distinct_hosts`` do not come out correct.
"""
import json
import math
import os

import numpy as np
import pytest

import reference
import run as bench_run
from conftest import ROOT
from test_fleet131k import device_windows, load  # noqa: F401 (fixture)
import test_fleet131k

CELL = "gcd2011-65k.batch10"
BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
ENTRY = next(c for c in BENCHMARK["configs"] if c["name"] == "gcd2011-65k")
CONFIG = json.load(open(os.path.join(ROOT, ENTRY["file"])))
TRAFFIC = load("traffic", "batch10x64.json")
# Reiss et al. (SoCC 2012), Table 1: (platform, CPU, memory, machines),
# less the two classes the configuration cuts.
TABLE = [("B", 0.50, 0.50, 6732), ("B", 0.50, 0.25, 3863),
         ("B", 0.50, 0.75, 1001), ("C", 1.00, 1.00, 795),
         ("A", 0.25, 0.25, 126), ("B", 0.50, 0.12, 52),
         ("B", 0.50, 0.97, 5), ("C", 1.00, 0.50, 3)]
# (tier, share of the fleet its stanza admits, distinct_hosts)
STANZAS = [("platform-b", 0.927, False), ("not-a", 0.990, True),
           ("mem-16g-up", 0.679, False), ("mem-under-16g", 0.321, False),
           ("cpu-16000-up", 0.063, True), ("platform-bc", 0.990, False),
           ("mem-24g-up", 0.143, False), ("cpu-under-16000", 0.936, True),
           ("even-name", 0.500, True), ("platform-under-c", 0.936, False)]
N = 65000


@pytest.fixture(scope="module")
def fleet():
    return reference.make_fleet(CONFIG, 2 ** 31 + 41, N)


def job(k: int = 0) -> dict:
    generator = bench_run.load_module("generators", TRAFFIC["generator"])
    return generator.job_spec(TRAFFIC["job"], 41, 0, k)


def test_the_cell_is_the_deployment_the_files_state():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("gcd2011-65k", "batch10x64", 1)
    assert CONFIG["nodes"] == N and CONFIG["servers"] == 1
    assert ENTRY["reduced"] == CONFIG["reduced"] == ["servers", "classes"]
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    assert ENTRY["source"] == CONFIG["source"]
    # The source's machines, at 16,000 MHz and 32,768 MB for 1.0.
    got = [(c["meta"]["platform"], c["node"]["cpu"], c["node"]["memory_mb"],
            c["share"]) for c in CONFIG["node_classes"]]
    assert got == [(p, round(cpu * 16000), round(mem * 32768), n)
                   for p, cpu, mem, n in TABLE]
    mock = load("configs", "fleet131k.json")["node"]
    for c in CONFIG["node_classes"]:
        shape = c["node"]
        assert {k: v for k, v in shape.items()
                if k not in ("cpu", "memory_mb")} == \
            {k: v for k, v in mock.items() if k not in ("cpu", "memory_mb")}
        assert c["attributes"] == {
            "cpu.totalcompute": str(shape["cpu"]),
            "memory.totalbytes": str(shape["memory_mb"] * 2 ** 20)}
    # Platform A is mock.go's node, the machine of every other cell.
    a = next(c for c in CONFIG["node_classes"]
             if c["meta"]["platform"] == "A")
    assert (a["node"]["cpu"], a["node"]["memory_mb"]) == \
        (mock["cpu"], mock["memory_mb"])
    # Upstream's rule: under 50 beats a second, a beat at half the TTL.
    assert CONFIG["heartbeat_interval_s"] == (N // 50) // 2
    assert CONFIG["guarantees"] == \
        load("tests", "fixtures", "classes3.json")["guarantees"]
    assert CONFIG["scoring"]["precision"] == "float32"
    assert CONFIG["scoring"]["batch_anti_affinity_penalty"] == \
        reference.PENALTY["batch"]
    assert TRAFFIC["generator"] == "closed_loop_batch"
    assert TRAFFIC["clients"] == 64
    spec = TRAFFIC["job"]
    assert (spec["type"], spec["groups"], spec["count"]) == ("batch", 10, 10)
    storm = load("traffic", "storm64.json")
    for tier, (name, _share, distinct) in zip(spec["tiers"], STANZAS):
        assert tier["name"] == name and tier["count"] == 10
        assert {k: tier[k] for k in storm["job"]["ask"]} == \
            storm["job"]["ask"]
        assert len(tier["constraints"]) == 1
        assert tier.get("distinct_hosts", False) is distinct
    assert [p["module"] for p in TRAFFIC["prewarm"]] == \
        ["scatter_rows", "constrained_lanes"]
    reports = {m["name"] for m in BENCHMARK["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    assert reports == {"placements_per_s", "job_commit_p50_ms", "setup_s"}
    listed = {m["name"]: m["workloads"] for m in BENCHMARK["per_layer"]}
    assert all(CELL in cells for cells in listed.values())
    assert listed["feasible_row_share"] == listed["distinct_slot_share"] \
        == [CELL]


def test_each_stanza_admits_its_share_and_four_runs_of_copies(fleet):
    """Every group's mask over the whole fleet, as the reference judges
    it: the share the traffic file states, the fleet's mean share the
    one ``feasible_row_share`` should read (64.9), and room for four
    times what a run can place of the group at the top of the
    predicted rate (3,000 placements a second over the window, the
    warm-up and the jobs in flight)."""
    spec = job()
    ask = reference.group_ask(spec["groups"][0])
    free = fleet["capacity"] - fleet["reserved"]
    copies = np.floor(np.min(np.where(ask > 0, free / np.where(
        ask > 0, ask, 1), np.inf), axis=1))
    window_jobs = 3000 * 45 / 100
    per_run = 10 * (window_jobs + 64 * 2 + 64)
    shares = []
    for group, (name, share, _d) in zip(spec["groups"], STANZAS):
        mask = reference.feasible(fleet, spec, group)
        shares.append(mask.mean())
        assert mask.mean() == pytest.approx(share, abs=6e-4), name
        assert copies[mask].sum() >= 4 * per_run, name
    assert 100 * np.mean(shares) == pytest.approx(64.9, abs=0.05)
    # The narrowest, C alone: ~4,100 nodes x 19 copies (bandwidth binds).
    narrow = reference.feasible(fleet, spec, spec["groups"][4])
    assert set(copies[narrow].tolist()) == {19.0}


def gain_bound(fleet: dict, ask) -> float:
    """The prep's BestFit gain bound (``jax_binpack._prepare_device``)
    on the fleet's least free CPU and memory."""
    free = fleet["capacity"] - fleet["reserved"]
    frac_c, frac_m = ask[0] / free[:, 0].min(), ask[1] / free[:, 1].min()
    return 10 * (1 - 10 ** -frac_c) + 10 * (1 - 10 ** -frac_m)


def test_the_cut_classes_would_lose_the_rounds_kernel(fleet):
    ask = reference.group_ask(job()["groups"][0])
    line = 0.95 * reference.PENALTY["batch"]
    bound = gain_bound(fleet, ask)
    assert bound == pytest.approx(4.04, abs=0.01) and bound < line
    for memory in (0.06, 0.03):
        cut = dict(CONFIG, node_classes=CONFIG["node_classes"] + [dict(
            CONFIG["node_classes"][0], name=f"cut-{memory}", share=1,
            node=dict(CONFIG["node_classes"][0]["node"],
                      memory_mb=round(memory * 32768)))])
        whole = reference.make_fleet(cut, 41, 12584)
        assert gain_bound(whole, ask) > line


def test_the_generator_registers_batch_jobs_of_ten_groups():
    spec = job(3)
    assert spec["type"] == "batch" and spec["asked"] == 100
    assert [g["name"] for g in spec["groups"]] == [s[0] for s in STANZAS]
    assert spec["constraints"] == TRAFFIC["job"]["constraints"]
    assert spec["datacenters"] == ["dc1"]
    assert len(reference.job_slots(spec)) == 10
    assert job(3) == spec and job(4)["id"] != spec["id"]
    capped = bench_run.rehearsal_traffic(TRAFFIC)["job"]
    assert bench_run.load_module("generators", "closed_loop_batch").job_spec(
        dict(capped, count=3), 41, 0, 3)["asked"] == 30


def test_the_plug_in_warms_the_windows_that_leave_the_twin():
    """At 65,000 nodes a fused window leaves the twin at 33 lanes of a
    slot axis of 16 (the 64 bucket) and never at 8; a lone eval never
    does.  Both copy buckets of a widest slot of ten are warmed."""
    plug = bench_run.load_module("prewarm", "constrained_lanes")
    params = TRAFFIC["prewarm"][1]
    assert plug.programs(params, N, TRAFFIC) == [
        ("batch", 64, 16, 8, 1), ("batch", 64, 16, 16, 1)]
    assert math.ceil(2 ** 25 / (16 * N)) == 33
    assert plug.programs(params, 1024, TRAFFIC) == []


# -- the rehearsal, on both engines ------------------------------------------

@pytest.fixture
def drive(monkeypatch):
    """``test_fleet131k.drive`` on this cell, three copies a group."""
    monkeypatch.setattr(test_fleet131k, "CELL", CELL)
    monkeypatch.setitem(bench_run.REHEARSAL, "count", 3)
    return test_fleet131k.drive


@pytest.fixture(params=["twin", "kernel"])
def engine(request, monkeypatch):
    if request.param == "kernel":
        from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler

        monkeypatch.setattr(JaxBinPackScheduler, "HOST_SINGLE_SHOT_COST", 0)
        monkeypatch.setattr(JaxBinPackScheduler, "HOST_ALWAYS_COST", 0)
    return request.param


def test_the_rehearsal_is_correct(engine, drive):
    result, outside, stderr = drive(41, trace=1)
    assert result["correct"] is True and not outside
    assert all(c["value"] == 0 for name, c in result["checks"].items()
               if c["limit"] == 0)
    assert "compilations inside the window: 0 programs" in stderr
    metrics = {k: m["value"] for k, m in result["metrics"].items()}
    device = 100.0 if engine == "kernel" else 0.0
    assert metrics["device_lane_share"] == device
    # A first plan carries the ten slots, four of them distinct; a
    # re-plan of what a partial commit left carries fewer.
    assert 5.0 < metrics["slots_per_lane"] <= 10.0
    assert 25.0 < metrics["distinct_slot_share"] <= 60.0
    assert 55.0 < metrics["feasible_row_share"] < 75.0
    assert metrics["multi_round_lane_share"] == 0.0


@pytest.mark.parametrize("fault, control, caught_by", [
    (None, "bf16", "score_gap"), (None, "worst_first", "score_regret"),
    ("constraints_ignored", None, "constraint_violations"),
    ("distinct_ignored", None, "distinct_violations")])
def test_controls_and_faults_are_not_correct(engine, drive, fault, control,
                                             caught_by):
    flags = ("--control", control) if control else ()
    result, outside, _stderr = drive(42, *flags, fault=fault)
    assert result["correct"] is False and caught_by in outside
