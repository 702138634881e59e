"""Fused windows at the width of ``benchmarks/configs/fleet131k.json``:
131,072 nodes of the upstream mock shape, partly filled from a seed, and
a window of 33 or of 64 single-slot lanes (the upstream mock job: one
group x 10 copies of 500 MHz / 256 MB / 50 Mbit / one port) on the one
snapshot — what ``fleet131k.storm`` sends to the XLA kernel.

Three scorers that share no code are held to each other, lane by lane:
the XLA kernel (``ops/binpack.place_rounds_batch``, here on the CPU
backend), the numpy twin (``ops/binpack_host.place_rounds_host``) and
the benchmark's plain reference in float64
(``benchmarks/reference.Scorer``).  The width is the point: the top-k,
the ties of a homogeneous fleet and the padded lane and slot axes are
the ones the cell runs.
"""
from __future__ import annotations

import importlib.util
import json
import os

import numpy as np
import pytest

from nomad_tpu.ops.binpack import place_rounds_batch
from nomad_tpu.ops.binpack_host import check_rounds_host, place_rounds_host
from nomad_tpu.scheduler.batch import pad_lanes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_NODES = 131072
G_PAD, K_CAP, COPIES = 8, 16, 10
PENALTY = 10.0
# Every recorded score within this of the float64 one, on the CPU
# backend: float32 rounding of the two 10^x terms, each at most 10 with
# an ulp of 9.5e-7 there, plus the rounding of x itself (measured
# 1.5e-7 on this fleet, bfloat16 0.036; a TPU's 10^x is coarser,
# 4.1e-5 in the cell on a v5e: PERF.md section 2).
SCORE_ATOL = 1e-5
# What the kernels must never look like: the reference's scorer in
# bfloat16 is further than this from float64 on the same picks.
BF16_FLOOR = 1e-2


def _reference():
    spec = importlib.util.spec_from_file_location(
        "fleet131k_reference", os.path.join(ROOT, "benchmarks",
                                            "reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def fleet():
    """(reference module, its fleet dict, float32 capacity / reserved /
    usage [n, 6]): every fourth node holds 1-6 copies of the mock ask,
    so the best nodes are the fullest ones that still fit and the empty
    three quarters tie."""
    reference = _reference()
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "fleet131k.json")) as fh:
        config = json.load(fh)
    assert config["nodes"] == N_NODES
    made = reference.make_fleet(config, 33, N_NODES)
    rng = np.random.default_rng(33)
    ask = reference.group_ask({"cpu": 500, "memory_mb": 256, "mbits": 50,
                               "dynamic_ports": ["http"]})
    held = np.where(rng.random(N_NODES) < 0.25,
                    rng.integers(1, 7, N_NODES), 0)
    usage = held[:, None] * ask[None, :]
    return (reference, made, ask, made["capacity"].astype(np.float32),
            made["reserved"].astype(np.float32), usage.astype(np.float32))


def _window(lanes: int, ask: np.ndarray):
    """The fused site's lane stacks for ``lanes`` fresh mock jobs."""
    b_pad = pad_lanes(lanes)
    feasible = np.zeros((b_pad, G_PAD, N_NODES), dtype=bool)
    feasible[:lanes, 0] = True
    asks = np.zeros((b_pad, G_PAD, 6), dtype=np.float32)
    asks[:lanes, 0] = ask
    counts = np.zeros((b_pad, G_PAD), dtype=np.int32)
    counts[:lanes, 0] = COPIES
    penalty = np.zeros(b_pad, dtype=np.float32)
    penalty[:lanes] = PENALTY
    return (np.zeros((b_pad, N_NODES), dtype=np.int32), feasible, asks,
            np.zeros((b_pad, G_PAD), dtype=bool), counts, penalty)


@pytest.mark.parametrize("lanes", [33, 64])
def test_kernel_twin_and_float64_reference_agree(fleet, lanes):
    reference, made, ask, capacity, reserved, usage = fleet
    jc, feasible, asks, distinct, counts, penalty = _window(lanes, ask)
    chosen, scores, _usage = place_rounds_batch(
        capacity, reserved, usage, jc, feasible, asks, distinct, counts,
        penalty, k_cap=K_CAP, rounds=1)
    chosen, scores = np.asarray(chosen), np.asarray(scores)
    assert chosen.shape == (pad_lanes(lanes), G_PAD, K_CAP)
    # Padded lanes and padded slots place nothing.
    assert (chosen[lanes:] == -1).all() and (chosen[:, 1:] == -1).all()

    scorer64 = reference.Scorer(made)
    scorer16 = reference.Scorer(made, dtype=_bfloat16())
    none = np.zeros(N_NODES)
    want, _fits = scorer64.scores(usage.astype(np.float64), none, ask,
                                  PENALTY)
    low, _fits = scorer16.scores(usage.astype(np.float64), none, ask,
                                 PENALTY)
    kth = np.sort(want)[-COPIES]
    twin_chosen, twin_scores, _u = place_rounds_host(
        capacity, reserved, usage, jc[0], feasible[0], asks[0],
        distinct[0], counts[0], PENALTY, k_cap=K_CAP, rounds=1,
        n_real=N_NODES)
    widest = widest_low = 0.0
    for b in range(lanes):
        picks = chosen[b, 0, :COPIES]
        assert (chosen[b, 0, COPIES:] == -1).all()
        assert len(set(picks.tolist())) == COPIES and picks.min() >= 0
        # The twin would have ranked each pick among its best.
        assert check_rounds_host(
            capacity, reserved, usage, jc[b], feasible[b], asks[b],
            distinct[b], counts[b], PENALTY, {0: picks}, K_CAP, 1,
            atol=SCORE_ATOL, n_real=N_NODES)
        # So would float64, and the recorded scores are float64's.
        assert (want[picks] >= kth - SCORE_ATOL).all()
        recorded = scores[b, 0, :COPIES].astype(np.float64)
        widest = max(widest, float(np.abs(recorded - want[picks]).max()))
        widest_low = max(widest_low,
                         float(np.abs(low[picks] - want[picks]).max()))
        # Every lane plans on the one snapshot: the twin's one answer.
        assert (picks == twin_chosen[0, :COPIES]).all()
        assert np.abs(scores[b, 0, :COPIES]
                      - twin_scores[0, :COPIES]).max() < SCORE_ATOL
    assert widest < SCORE_ATOL, widest
    assert widest_low > BF16_FLOOR, widest_low


def _bfloat16():
    import ml_dtypes

    return ml_dtypes.bfloat16
