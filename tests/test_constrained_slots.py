"""Lanes of ten MASKED kernel slots, four under ``distinct_hosts``, on a
fleet of eight machine shapes: a 512-node prefix of
``benchmarks/node_classes/gcd2011-65k.json`` (Google ClusterData-2011's
machine mix) and the batch job of ``benchmarks/traffic/batch10x64.json``
(ten task groups of one ask, each under one hard stanza over ``$meta``,
``$attr`` or the per-row ``$node.name``), three copies a group — what
``gcd2011-65k.batch10`` sends to the XLA kernel at a CPU's size.

Each slot's mask, as the program compiles it from its own nodes, is the
plain reference's (``benchmarks/reference.feasible_mask``).  Three
scorers that share no code are held to each other lane by lane and slot
by slot on a fused window of such lanes: the XLA kernel
(``ops/binpack.place_rounds_batch``, here on the CPU backend), the numpy
twin (``ops/binpack_host.place_rounds_host``) and the reference in
float64; no pick of a distinct slot lands on a node its job already
holds.  Last, the served path on both engines (the twin, and the kernel
with the break-even at nought): the benchmark's own ``Served``, the
cell's generator, and the committed allocations through
``check.compare``, with the prep's new tags and counters and the mask
compiler's span and counters carrying their values.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

from nomad_tpu.models.fleet import _pad_to
from nomad_tpu.ops.binpack import place_rounds_batch
from nomad_tpu.ops.binpack_host import place_rounds_host
from nomad_tpu.scheduler.batch import pad_lanes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
N, COPIES, SLOTS, DISTINCT = 512, 3, 10, 4
G_PAD, K_CAP = _pad_to(SLOTS), _pad_to(COPIES)
PENALTY = 5.0                         # a batch job's anti-affinity
SCORE_ATOL = 1e-5                     # as test_stack_slots.py


def _bench(name: str):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return __import__(name)


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def _config() -> dict:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "gcd2011-65k")
    with open(os.path.join(ROOT, entry["file"])) as fh:
        return json.load(fh)


def _traffic() -> dict:
    traffic = _load("traffic", "batch10x64.json")
    traffic["job"]["count"] = COPIES
    return traffic


def _job(k: int = 0) -> dict:
    run = _bench("run")
    traffic = _traffic()
    return run.load_module("generators", traffic["generator"]).job_spec(
        traffic["job"], 41, 0, k)


@pytest.fixture(scope="module")
def fleet():
    """(reference module, its fleet dict, the program's FleetStatics of
    the same nodes, the job, the program's mask [10, n] of each group,
    the distinct flags, the one ask [6])."""
    from nomad_tpu.models.constraints import compile_group_mask
    from nomad_tpu.models.fleet import build_fleet
    from nomad_tpu.scheduler.util import task_group_constraints

    reference, served = _bench("reference"), _bench("served")
    made = reference.make_fleet(_config(), 2 ** 31 + 41, N)
    statics = build_fleet([served.program_node(made, i) for i in range(N)])
    spec = _job()
    program = served.program_job(spec)
    masks, distinct = [], []
    for tg in program.task_groups:
        tc = task_group_constraints(tg)
        mask, dist = compile_group_mask(statics, program.datacenters,
                                        program.constraints, tc.constraints,
                                        tc.drivers)
        masks.append(mask)
        distinct.append(dist)
    ask = reference.group_ask(spec["groups"][0])
    return (reference, made, statics, spec, np.asarray(masks),
            np.asarray(distinct), ask)


def test_every_slot_mask_is_the_references(fleet):
    reference, made, statics, spec, masks, distinct, _ask = fleet
    assert len(set(np.bincount(made["class_of"]).nonzero()[0])) >= 6
    assert [s[4] for s in reference.job_slots(spec)] == distinct.tolist()
    assert distinct.sum() == DISTINCT and len(reference.job_slots(spec)) == 10
    for group, mask in zip(spec["groups"], masks):
        ours = reference.feasible(made, spec, group)
        assert np.array_equal(mask[:N], ours), group["name"]
        assert 0 < ours.sum() < N or group["name"] in ("not-a",
                                                        "platform-bc")
    # Every stanza bites somewhere but two, and those two only where
    # platform A (126 of 12,577 machines) has no row in the prefix.
    assert masks.sum() < SLOTS * N


def _window(masks, distinct, ask, usage, lanes: int):
    """The fused site's stacks: lane b still wants three copies a slot
    (a first plan) or, every third lane, a re-plan's remainder, with
    its job already on the first nodes the remainder's slots took."""
    b_pad = pad_lanes(lanes)
    feasible = np.zeros((b_pad, G_PAD, N), dtype=bool)
    asks = np.zeros((b_pad, G_PAD, 6), dtype=np.float32)
    dist = np.zeros((b_pad, G_PAD), dtype=bool)
    counts = np.zeros((b_pad, G_PAD), dtype=np.int32)
    jc = np.zeros((b_pad, N), dtype=np.int32)
    fullest = np.argsort(-usage[:, 0], kind="stable")
    for b in range(lanes):
        feasible[b, :SLOTS] = masks
        asks[b, :SLOTS] = ask
        dist[b, :SLOTS] = distinct
        counts[b, :SLOTS] = COPIES if b % 3 else [1, 2] * (SLOTS // 2)
        if b % 3 == 0:
            jc[b, fullest[b:b + 4]] = 1
    penalty = np.zeros(b_pad, dtype=np.float32)
    penalty[:lanes] = PENALTY
    return jc, feasible, asks, dist, counts, penalty


@pytest.mark.parametrize("lanes", [3, 8])
def test_kernel_twin_and_float64_reference_agree_slot_by_slot(fleet,
                                                              lanes):
    reference, made, _statics, _spec, masks, distinct, ask = fleet
    rng = np.random.default_rng(41)
    held = np.where(rng.random(N) < 0.3, rng.integers(1, 6, N), 0)
    usage = (held[:, None] * ask).astype(np.float32)
    capacity = made["capacity"].astype(np.float32)
    reserved = made["reserved"].astype(np.float32)
    assert ((usage + reserved) <= capacity).all()
    jc, feasible, asks, dist, counts, penalty = _window(
        masks, distinct, ask, usage, lanes)
    chosen, scores, _u = place_rounds_batch(
        capacity, reserved, usage, jc, feasible, asks, dist, counts,
        penalty, k_cap=K_CAP, rounds=1)
    chosen, scores = np.asarray(chosen), np.asarray(scores)
    assert chosen.shape == (pad_lanes(lanes), G_PAD, K_CAP)
    assert (chosen[lanes:] == -1).all() and (chosen[:, SLOTS:] == -1).all()

    scorer = reference.Scorer(made)
    widest = 0.0
    for b in range(lanes):
        twin_chosen, twin_scores, _tu = place_rounds_host(
            capacity, reserved, usage, jc[b], feasible[b], asks[b],
            dist[b], counts[b], PENALTY, k_cap=K_CAP, rounds=1, n_real=N)
        assert (twin_chosen == chosen[b]).all(), b
        placed = chosen[b] >= 0
        assert np.abs(twin_scores - scores[b])[placed].max() < SCORE_ATOL
        # Float64, slot by slot in job order: each slot is scored on the
        # usage and the job's counts the slots before it left, under its
        # mask and, where distinct, off every node the job holds.
        used = usage.astype(np.float64)
        mine = jc[b].astype(np.float64)
        for s in range(SLOTS):
            wanted = int(counts[b, s])
            picks = chosen[b, s, :wanted]
            assert (picks >= 0).all() and (chosen[b, s, wanted:] == -1).all()
            assert len(set(picks.tolist())) == wanted
            assert masks[s][picks].all()
            if distinct[s]:
                assert not mine[picks].any(), (b, s)
            want, _fits = scorer.scores(used, mine, ask, PENALTY,
                                        feasible=masks[s],
                                        distinct=bool(distinct[s]))
            kth = np.partition(want, N - wanted)[N - wanted]
            assert (want[picks] >= kth - SCORE_ATOL).all(), (b, s)
            widest = max(widest, float(np.abs(
                scores[b, s, :wanted].astype(np.float64)
                - want[picks]).max()))
            np.add.at(used, picks, ask)
            np.add.at(mine, picks, 1.0)
    assert widest < SCORE_ATOL, widest


# -- the served path, on both engines ----------------------------------------

CLIENTS, SECONDS = 4, 1.5
COUNTERS = ("feasible_rows", "feasible_rows_full", "distinct_slots")


@pytest.fixture(scope="module", params=["twin", "kernel"])
def served_run(request, tmp_path_factory):
    """One traced run of the cell's traffic through the benchmark's
    ``Served`` at 512 nodes, four clients, three copies a group:
    (engine, the reference's fleet, traffic, records, answers, the
    harness's counters, the spans, the runner's and the mask compiler's
    counters before and after)."""
    from nomad_tpu.obs import trace
    from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler
    from nomad_tpu.utils.metrics import metrics

    run = _bench("run")
    reference, served_mod = _bench("reference"), _bench("served")
    traffic = _traffic()
    traffic["clients"] = CLIENTS
    traffic["warmup"] = {"jobs_per_client": 1}
    seed = 2 ** 31 + 41
    made = reference.make_fleet(_config(), seed, N)
    patch = pytest.MonkeyPatch()
    if request.param == "kernel":
        patch.setattr(JaxBinPackScheduler, "HOST_SINGLE_SHOT_COST", 0)
        patch.setattr(JaxBinPackScheduler, "HOST_ALWAYS_COST", 0)
    served = served_mod.Served(_config(), made, seed, str(
        tmp_path_factory.mktemp(f"constrained-{request.param}")),
        lambda _msg: None)

    def counters() -> dict:
        got = served.api.agent_metrics()["providers"]
        out = {k: got[f"nomad.batch_runner.{k}"] for k in COUNTERS}
        inmem = metrics.inmem.snapshot()["counters"]
        for k in ("mask_compiles", "rows_judged"):
            out[k] = inmem.get(f"nomad.constraints.{k}", 0.0)
        return out

    try:
        before = counters()
        with trace.tracing(seed=41, ring=1 << 20) as tracer:
            out = run.load_module("generators", traffic["generator"]).run(
                traffic, seed, lambda: served.client(1.0), SECONDS,
                lambda: None, lambda: None, lambda _msg: None)
            records = out["records"]
            answers = served.read_back({r["spec"]["id"] for r in records})
            after = counters()
            spans = tracer.snapshot()
        answers["readback_mismatch"] = 0
        harness = served.counters()
    finally:
        served.shutdown()
        patch.undo()
    for r in records:
        r["in_window"] = out["t_open"] <= r["t_done"] <= out["t_close"]
    return (request.param, made, traffic, records, answers, harness, spans,
            before, after)


def test_served_batch_jobs_are_correct(served_run):
    check = _bench("check")
    engine, made, traffic, records, answers, harness, _s, _b, _a = \
        served_run
    assert len([r for r in records if r["in_window"]]) >= CLIENTS
    assert all(r["spec"]["asked"] == SLOTS * COPIES
               and r["spec"]["type"] == "batch"
               and r["status"] == "complete" for r in records)
    checks, info = check.compare(2 ** 31 + 41, traffic, made, records,
                                 answers, harness, None, lambda _msg: None)
    assert check.passed(checks), checks
    assert all(c["value"] == 0 for c in checks.values() if c["limit"] == 0)
    assert checks["score_gap"]["value"] < SCORE_ATOL
    assert checks["score_regret"]["value"] == 0.0
    assert info["picks"] >= SLOTS * COPIES * CLIENTS
    # A distinct group's copy lands on no node that holds a copy of its
    # job placed no later: in an earlier commit, or in the same plan at
    # an earlier slot (a later slot may join it, as the program allows);
    # and every copy on a node its group's mask admits.
    reference = _bench("reference")
    allocs = answers["allocs"]
    shared = 0
    for r in records:
        spec = r["spec"]
        order = [g["name"] for g in spec["groups"]]
        rows = [i for i, j in enumerate(allocs["job"]) if j == spec["id"]]
        key = {i: (int(allocs["create_index"][i]),
                   order.index(allocs["group"][i])) for i in rows}
        for i in rows:
            group = spec["groups"][key[i][1]]
            node = allocs["node"][i]
            assert reference.feasible(made, spec, group)[node]
            beside = [j for j in rows if j != i and allocs["node"][j] == node]
            shared += len(beside)
            if group.get("distinct_hosts"):
                assert all(key[j] > key[i] for j in beside), (i, beside)
    assert shared, "no two copies of a job met on a node: nothing checked"


def test_the_prep_tags_and_counters_carry_the_masks(served_run, fleet):
    """``sched.begin`` of a first plan says the ten slots' masks admit
    the rows they admit of 512 x 10, four slots distinct; re-plans
    (``sched.begin`` of a second fused round, ``sched.retry``) say the
    same of the slots they carried; ``nomad.batch_runner.*`` move by the
    spans' sums."""
    _e, _m, _t, _r, _a, _h, spans, before, after = served_run
    masks = fleet[4]
    begins = [s["tags"] for s in spans if s["name"] == "sched.begin"
              and "feasible_rows" in (s.get("tags") or {})]
    retries = [s["tags"] for s in spans if s["name"] == "sched.retry"]
    assert begins
    first = [t for t in begins if t["slots"] == SLOTS]
    assert first
    for t in first:
        assert t["feasible_rows"] == int(masks.sum())
        assert t["feasible_rows_full"] == N * SLOTS
        assert t["distinct_slots"] == DISTINCT
    for t in begins:
        assert t["feasible_rows_full"] == N * t["slots"]
        assert t["distinct_slots"] <= min(DISTINCT, t["slots"])
    for t in retries:
        assert t["feasible_rows"] <= t["feasible_rows_full"]
        assert t["feasible_rows_full"] % N == 0
    for k in COUNTERS:
        assert after[k] - before[k] == sum(t[k] for t in begins + retries)


def test_a_group_mask_miss_is_one_compile_span(served_run):
    """The first job's prep compiles a mask for each of its ten groups:
    one ``constraints.compile`` span a miss, with the rows its predicate
    walks judged (every row of a fleet of several classes: nothing is
    shared between rows) and its two stanzas (the job's and the
    group's); ``nomad.constraints.mask_compiles`` / ``.rows_judged``
    move by the spans' count and sum."""
    _e, _m, _t, _r, _a, _h, spans, before, after = served_run
    compiles = [s["tags"] for s in spans
                if s["name"] == "constraints.compile"]
    assert len(compiles) >= SLOTS
    assert all(t["stanzas"] == 2 and t["rows"] >= N and t["rows"] % N == 0
               for t in compiles)
    # The first miss also judges the datacenter, the driver and the
    # job's own stanza; the other groups share those.
    assert compiles[0]["rows"] == 4 * N
    assert after["mask_compiles"] - before["mask_compiles"] == len(compiles)
    assert after["rows_judged"] - before["rows_judged"] == \
        sum(t["rows"] for t in compiles)
