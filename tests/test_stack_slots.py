"""Lanes of three REAL kernel slots at the width of
``benchmarks/configs/fleet100k.json``: 100,000 nodes of the upstream
mock shape on a padded node axis of 131,072 (``n_real`` != ``n_pad``),
partly filled from a seed, and a fused window of 42 or of 64 lanes, each
the three-tier stack of ``benchmarks/traffic/stacks64.json`` ('web' x
10, 'frontend' x 5, 'cache' x 1: three asks that do not dedupe) on the
one snapshot — what ``fleet100k.stacks`` sends to the XLA kernel.

Three scorers that share no code are held to each other, lane by lane
and slot by slot: the XLA kernel (``ops/binpack.place_rounds_batch``,
here on the CPU backend), the numpy twin
(``ops/binpack_host.place_rounds_host``) and the benchmark's plain
reference in float64 (``benchmarks/reference.Scorer``).  What the
one-slot windows of ``test_fleet131k_window.py`` cannot show: the slot
scan's carry (slot 2 is scored against slot 1's picks: their usage AND
the job's anti-affinity counts), lanes that start with counts of their
own (a re-plan of what a partial commit left), and 31,072 padded rows
under every top-k of every slot.

Last, the served path at a size the CPU holds: the benchmark's own
``Served`` (a real server with its raft log on disk, the fleet over
``Node.Register``), the stack jobs from the cell's generator, and the
committed allocations through ``check.compare``: correct, and not
correct under either control.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

from nomad_tpu.models.fleet import _pad_to
from nomad_tpu.ops.binpack import place_rounds_batch
from nomad_tpu.ops.binpack_host import check_rounds_host, place_rounds_host
from nomad_tpu.scheduler.batch import pad_lanes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
N_REAL, N_PAD = 100000, 131072
G_PAD, K_CAP, SLOTS = 8, 16, 3
PENALTY = 10.0
# As in test_fleet131k_window.py: float32 rounding of BestFit's two 10^x
# terms on the CPU backend; bfloat16 is four orders further off.
SCORE_ATOL = 1e-5
BF16_FLOOR = 1e-2
# What a lane still has to place, slot by slot: a fresh stack, and two
# re-plans of what a partial commit left.
COUNTS = [(10, 5, 1), (6, 5, 1), (3, 2, 1)]


def _bench(name: str):
    """A module of the benchmark (``benchmarks/<name>.py``), as the
    harness imports it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return __import__(name)


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def fleet():
    """(reference module, its fleet dict, the three asks [3, 6], padded
    float32 capacity / reserved / usage [n_pad, 6]): every fourth node
    holds 1-6 copies of one of the three asks, so the best nodes are the
    fullest that still fit and the empty three quarters tie; the padded
    rows are all nought, as ``FleetStatics`` leaves them."""
    reference = _bench("reference")
    config = _load("configs", "fleet100k.json")
    assert config["nodes"] == N_REAL and _pad_to(N_REAL) == N_PAD
    made = reference.make_fleet(config, 35, N_REAL)
    tiers = _load("traffic", "stacks64.json")["job"]["tiers"]
    assert [t["name"] for t in tiers] == ["web", "frontend", "cache"]
    asks = np.stack([reference.group_ask(t) for t in tiers])
    rng = np.random.default_rng(35)
    held = np.where(rng.random(N_REAL) < 0.25,
                    rng.integers(1, 7, N_REAL), 0)
    usage = held[:, None] * asks[rng.integers(0, SLOTS, N_REAL)]

    def padded(x):
        out = np.zeros((N_PAD, 6), dtype=np.float32)
        out[:N_REAL] = x
        return out

    return (reference, made, asks, padded(made["capacity"]),
            padded(made["reserved"]), padded(usage))


def _window(lanes: int, asks: np.ndarray, usage: np.ndarray):
    """The fused site's lane stacks for ``lanes`` stacks.  Lane b still
    wants ``COUNTS[b % 3]``; a re-plan lane's job already holds a copy
    on each of the first ``10 - wanted`` of the snapshot's best nodes,
    which the anti-affinity penalty then keeps it off."""
    b_pad = pad_lanes(lanes)
    feasible = np.zeros((b_pad, G_PAD, N_PAD), dtype=bool)
    feasible[:lanes, :SLOTS, :N_REAL] = True
    stacked = np.zeros((b_pad, G_PAD, 6), dtype=np.float32)
    stacked[:lanes, :SLOTS] = asks
    counts = np.zeros((b_pad, G_PAD), dtype=np.int32)
    jc = np.zeros((b_pad, N_PAD), dtype=np.int32)
    fullest = np.argsort(-usage[:N_REAL, 0], kind="stable")
    for b in range(lanes):
        counts[b, :SLOTS] = COUNTS[b % len(COUNTS)]
        jc[b, fullest[:10 - counts[b, 0]]] = 1
    penalty = np.zeros(b_pad, dtype=np.float32)
    penalty[:lanes] = PENALTY
    return (jc, feasible, stacked, np.zeros((b_pad, G_PAD), dtype=bool),
            counts, penalty)


@pytest.mark.parametrize("lanes", [42, 64])
def test_kernel_twin_and_float64_reference_agree_slot_by_slot(fleet,
                                                              lanes):
    reference, made, asks, capacity, reserved, usage = fleet
    jc, feasible, stacked, distinct, counts, penalty = _window(
        lanes, asks, usage)
    chosen, scores, _usage = place_rounds_batch(
        capacity, reserved, usage, jc, feasible, stacked, distinct, counts,
        penalty, k_cap=K_CAP, rounds=1)
    chosen, scores = np.asarray(chosen), np.asarray(scores)
    assert chosen.shape == (pad_lanes(lanes), G_PAD, K_CAP)
    # Padded lanes and padded slots place nothing, and no pick of any
    # slot is a padded row.
    assert (chosen[lanes:] == -1).all() and (chosen[:, SLOTS:] == -1).all()
    assert chosen.max() < N_REAL

    scorer64 = reference.Scorer(made)
    scorer16 = reference.Scorer(made, dtype=_bfloat16())
    widest = widest_low = 0.0
    for b in range(lanes):
        wanted = counts[b, :SLOTS]
        picks = {s: chosen[b, s, :wanted[s]] for s in range(SLOTS)}
        for s in range(SLOTS):
            assert (chosen[b, s, wanted[s]:] == -1).all()
            assert picks[s].min() >= 0
            assert len(set(picks[s].tolist())) == wanted[s]
        # The twin would have ranked each pick among its best at the
        # step it was made, and on the CPU backend makes the same ones.
        assert check_rounds_host(
            capacity, reserved, usage, jc[b], feasible[b], stacked[b],
            distinct[b], counts[b], PENALTY, picks, K_CAP, 1,
            atol=SCORE_ATOL, n_real=N_REAL)
        twin_chosen, twin_scores, _u = place_rounds_host(
            capacity, reserved, usage, jc[b], feasible[b], stacked[b],
            distinct[b], counts[b], PENALTY, k_cap=K_CAP, rounds=1,
            n_real=N_REAL)
        assert (twin_chosen == chosen[b]).all()
        assert np.abs(twin_scores - scores[b])[chosen[b] >= 0].max() \
            < SCORE_ATOL
        # So would float64, walking the slots in job order: a slot's
        # picks are scored on the usage and the job's counts that the
        # slots before it left.
        used = usage[:N_REAL].astype(np.float64)
        mine = jc[b, :N_REAL].astype(np.float64)
        for s in range(SLOTS):
            want, _fits = scorer64.scores(used, mine, asks[s], PENALTY)
            low, _fits = scorer16.scores(used, mine, asks[s], PENALTY)
            kth = np.partition(want, N_REAL - wanted[s])[N_REAL - wanted[s]]
            assert (want[picks[s]] >= kth - SCORE_ATOL).all(), (b, s)
            recorded = scores[b, s, :wanted[s]].astype(np.float64)
            widest = max(widest,
                         float(np.abs(recorded - want[picks[s]]).max()))
            widest_low = max(widest_low, float(
                np.abs(low[picks[s]] - want[picks[s]]).max()))
            np.add.at(used, picks[s], asks[s])
            np.add.at(mine, picks[s], 1.0)
        # The carry: no later slot took a node an earlier one had taken,
        # nor one the job held before (the penalty is a whole BestFit).
        taken = np.concatenate(list(picks.values()))
        assert len(set(taken.tolist())) == len(taken)
        assert not jc[b, taken].any()
    assert widest < SCORE_ATOL, widest
    assert widest_low > BF16_FLOOR, widest_low


def _bfloat16():
    import ml_dtypes

    return ml_dtypes.bfloat16


# -- the served path ---------------------------------------------------------

SERVED_NODES, SERVED_CLIENTS, SERVED_SECONDS = 1024, 8, 1.5


@pytest.fixture(scope="module")
def served_run(tmp_path_factory):
    """One run of the stack traffic through the benchmark's ``Served``
    at 1,024 nodes and 8 clients: (reference's fleet, traffic, records,
    answers, counters at the end)."""
    run = _bench("run")
    reference, served_mod = _bench("reference"), _bench("served")
    config = _load("configs", "fleet100k.json")
    traffic = run.rehearsal_traffic(_load("traffic", "stacks64.json"))
    traffic["clients"] = SERVED_CLIENTS
    traffic["warmup"] = {"jobs_per_client": 1}
    assert [g["count"] for g in run.load_module(
        "generators", traffic["generator"]).job_spec(
            traffic["job"], 35, 0, 0)["groups"]] == [10, 5, 1]
    seed = 2 ** 31 + 35
    made = reference.make_fleet(config, seed, SERVED_NODES)
    served = served_mod.Served(config, made, seed, str(
        tmp_path_factory.mktemp("stack-raft")), lambda _msg: None)
    try:
        out = run.load_module("generators", traffic["generator"]).run(
            traffic, seed, lambda: served.client(1.0), SERVED_SECONDS,
            lambda: None, lambda: None, lambda _msg: None)
        records = out["records"]
        answers = served.read_back({r["spec"]["id"] for r in records})
        answers["readback_mismatch"] = 0
        counters = served.counters()
    finally:
        served.shutdown()
    for r in records:
        r["in_window"] = out["t_open"] <= r["t_done"] <= out["t_close"]
    return made, traffic, records, answers, counters


@pytest.mark.parametrize("control, outside", [
    (None, set()), ("bf16", {"score_gap"}),
    ("worst_first", {"score_regret"})])
def test_served_stacks_are_correct_and_the_controls_are_not(
        served_run, control, outside):
    check = _bench("check")
    made, traffic, records, answers, counters = served_run
    done = [r for r in records if r["in_window"]]
    assert len(done) >= 2 * SERVED_CLIENTS
    assert all(r["spec"]["asked"] == 16 and r["status"] == "complete"
               for r in records)
    # Three groups a job in the store, in the generator's order.
    by_group: dict = {}
    for job, group in zip(answers["allocs"]["job"],
                          answers["allocs"]["group"]):
        by_group.setdefault(job, []).append(group)
    assert all(sorted(g, key=["web", "frontend", "cache"].index)
               == ["web"] * 10 + ["frontend"] * 5 + ["cache"]
               for g in by_group.values())
    # Two dynamic ports from the front end's one network ask.
    assert {len(p) for p, g in zip(answers["allocs"]["ports"],
                                   answers["allocs"]["group"])
            if g == "frontend"} == {2}
    checks, info = check.compare(2 ** 31 + 35, traffic, made, records,
                                 answers, counters, control,
                                 lambda _msg: None)
    failed = {k for k, c in checks.items() if c["value"] > c["limit"]}
    assert failed == outside, checks
    assert check.passed(checks) is (control is None)
    assert info["picks"] >= 16 * 2 * SERVED_CLIENTS
    own = info if control else {
        "program_score_gap": checks["score_gap"]["value"],
        "program_score_regret": checks["score_regret"]["value"]}
    assert own["program_score_gap"] < SCORE_ATOL
    assert own["program_score_regret"] == 0.0
