"""Host (numpy) executor for the bin-pack kernels.

Same math as nomad_tpu/ops/binpack.py (score_all_nodes / place_sequence /
place_rounds), evaluated eagerly with numpy on the host.  Exists because a
device dispatch has a fixed floor — the fenced round trip (enqueue +
run + device->host copy) — that dwarfs the compute for small
workloads: a 100-node fleet scores in a few microseconds of vectorized
numpy.  The scheduler picks the executor per dispatch
(nomad_tpu/scheduler/jax_binpack.py choose_host_executor): tiny
fleets/evals run here latency-optimal, large ones ride the device where
pipelining wins and the node axis can shard across a mesh.

This is the same engineering trade XLA itself makes with host callbacks:
don't ship work to an accelerator that costs more to reach than to run.
Semantics are kernel-for-kernel the same math, and on the CPU backend
the same choices (parity-tested in tests/test_jax_binpack.py; scores
agree to ~2e-6).  On a TPU 10^x rounds a few 1e-6 RELATIVE off numpy
and a BestFit term reaches 10, so a score sits 3-6e-5 off numpy's
(PERF.md, bring-up; 4.1e-5 off the float64 reference over the picks
sampled in fleet131k.storm, PR 33) and two nodes whose scores are
closer than that may be ordered differently by the two engines —
measured on a v5e, a handful of near-tie swaps per 1,000 placements on
a used fleet.  Either order is a valid plan; the contract between the
engines is check_sequence_host / check_rounds_host below.
Reference math AllocsFit/ScoreFit
(/root/reference/nomad/structs/funcs.go:48-124), anti-affinity
(/root/reference/scheduler/rank.go:243-302).
"""
from __future__ import annotations

import numpy as np

from nomad_tpu.structs.funcs import score_fit_vec

NEG_INF = -1.0e30
DIM_CPU = 0
DIM_MEM = 1


class _HostScorer:
    """Precomputes node-static pieces so per-step work is minimal."""

    def __init__(self, capacity, reserved) -> None:
        self.capacity = capacity
        self.base = reserved.astype(np.float32)
        node_cpu = capacity[:, DIM_CPU] - reserved[:, DIM_CPU]
        node_mem = capacity[:, DIM_MEM] - reserved[:, DIM_MEM]
        self.valid_node = (node_cpu > 0) & (node_mem > 0)
        self.safe_cpu = np.where(node_cpu > 0, node_cpu, 1.0
                                 ).astype(np.float32)
        self.safe_mem = np.where(node_mem > 0, node_mem, 1.0
                                 ).astype(np.float32)

    def masked_scores(self, usage, job_counts, ask, feasible, distinct,
                      penalty):
        util = self.base + usage + ask
        fit = (util <= self.capacity).all(axis=-1)
        score = score_fit_vec(
            util[:, DIM_CPU], util[:, DIM_MEM], None, None,
            valid=self.valid_node, safe_cpu=self.safe_cpu,
            safe_mem=self.safe_mem)
        score -= penalty * job_counts
        ok = feasible & fit
        if distinct:
            ok = ok & (job_counts == 0)
        return np.where(ok, score, np.float32(NEG_INF))


def place_sequence_host(capacity, reserved, usage0, job_counts0, feasible,
                        asks, distinct, group_idx, valid, penalty,
                        n_real: int = 0):
    """numpy twin of ops/binpack.place_sequence (same args/outputs).

    ``n_real``: number of real (non-padding) node rows.  The device needs
    the padded static shape; the host doesn't — scoring is sliced to the
    real rows (padding rows are never feasible, so results are identical).
    """
    capacity = np.asarray(capacity)
    n_pad = capacity.shape[0]
    n = n_real or n_pad
    scorer = _HostScorer(capacity[:n], np.asarray(reserved)[:n])
    usage_full = np.array(usage0, dtype=np.float32, copy=True)
    jc_full = np.array(job_counts0, dtype=np.float32, copy=True)
    usage, jc = usage_full[:n], jc_full[:n]
    P = len(group_idx)
    chosen = np.full(P, -1, dtype=np.int32)
    scores = np.zeros(P, dtype=np.float32)
    feasible = np.asarray(feasible)
    asks = np.asarray(asks, dtype=np.float32)
    for p in range(P):
        if not valid[p]:
            continue
        g = group_idx[p]
        ask = asks[g]
        masked = scorer.masked_scores(usage, jc, ask, feasible[g, :n],
                                      bool(distinct[g]), penalty)
        c = int(masked.argmax())
        best = masked[c]
        if best > NEG_INF / 2:
            usage[c] += ask
            jc[c] += 1
            chosen[p] = c
            scores[p] = best
    return chosen, scores, usage_full


def _topk_exact(masked: np.ndarray, k: int) -> np.ndarray:
    """Indices of the k largest scores, ties broken by LOWER index —
    exactly lax.top_k's contract — in O(n + k log k).

    A plain argpartition can't be used directly: when ties straddle the
    k boundary it picks an arbitrary subset (and homogeneous fleets tie
    constantly).  Packing the score and the inverted index into one
    int64 key makes the order total, so argpartition selects the same
    SET top_k would and a small sort of that slice gives the same
    ORDER.  The float->int map is the standard monotone transform
    (IEEE-754 totally ordered as sign-flipped integers)."""
    n = len(masked)
    if k <= 0:
        return np.empty(0, dtype=np.intp)
    if k >= n:
        return np.argsort(-masked, kind="stable")
    # -0.0 == +0.0 as floats (tie -> index order) but their bit
    # patterns differ; +0.0 normalizes both to one key.
    masked = masked + np.float32(0.0)
    bits = masked.view(np.int32).astype(np.int64)
    u = np.where(bits >= 0, bits + np.int64(0x80000000), ~bits)
    # Center the 32-bit ordered value into signed range BEFORE the
    # shift so the packed key cannot overflow int64.
    key = ((u - np.int64(0x80000000)) << np.int64(32)) \
        | np.arange(n - 1, -1, -1, dtype=np.int64)
    sel = np.argpartition(key, n - k)[n - k:]
    return sel[np.argsort(-key[sel])]


def place_rounds_host(capacity, reserved, usage0, jc0, feasible, asks,
                      distinct, counts, penalty, k_cap: int, rounds: int,
                      n_real: int = 0):
    """numpy twin of ops/binpack.place_rounds (same args/outputs):
    [G, rounds * k_cap] per-slot placement streams via top-k rounds.

    Host-only shortcuts (results identical): node rows sliced to
    ``n_real`` and padding slots (count 0 — they place nothing on the
    device too) skipped outright.
    """
    capacity = np.asarray(capacity)
    n = n_real or capacity.shape[0]
    scorer = _HostScorer(capacity[:n], np.asarray(reserved)[:n])
    usage_full = np.array(usage0, dtype=np.float32, copy=True)
    jc_full = np.array(jc0, dtype=np.float32, copy=True)
    usage, jc = usage_full[:n], jc_full[:n]
    feasible = np.asarray(feasible)
    asks = np.asarray(asks, dtype=np.float32)
    G = feasible.shape[0]
    chosen = np.full((G, rounds * k_cap), -1, dtype=np.int32)
    scores = np.zeros((G, rounds * k_cap), dtype=np.float32)
    pos = np.arange(k_cap)
    for s in range(G):
        ask = asks[s]
        remaining = int(counts[s])
        if remaining <= 0:
            continue
        for r in range(rounds):
            if remaining <= 0:
                break
            masked = scorer.masked_scores(usage, jc, ask,
                                          feasible[s, :n],
                                          bool(distinct[s]), penalty)
            order = _topk_exact(masked, k_cap)
            vals = masked[order]
            take = (pos[:len(order)] < remaining) & (vals > NEG_INF / 2)
            idx = order[take]
            usage[idx] += ask
            jc[idx] += 1
            placed = int(take.sum())
            remaining -= placed
            lo = r * k_cap
            chosen[s, lo:lo + len(order)][take] = idx.astype(np.int32)
            scores[s, lo:lo + len(order)][take] = vals[take]
    return chosen, scores, usage_full


# -- checking another engine's choices -----------------------------------
# On the CPU backend the XLA kernels and these twins make the same
# choices.  On a TPU they do not have to: 10^x rounds a few 1e-6
# relative off numpy there (3-6e-5 on a score), so two nodes whose
# scores are closer than that may be ranked differently, and once one
# choice differs the two engines walk different (equally valid) usage
# trajectories.  So the question one engine can soundly ask of the
# other is not "same nodes?" but "would I have ranked each of your
# picks best, within ``atol``, at the step you made it?" — answered by
# scoring along the OTHER engine's trajectory.

def _check_setup(capacity, reserved, usage0, jc0, feasible, asks,
                 n_real: int) -> tuple:
    """(n, scorer, usage, jc, feasible, asks): private real-row copies
    of the state a checker evolves along the other engine's picks."""
    capacity = np.asarray(capacity)
    n = n_real or capacity.shape[0]
    return (n, _HostScorer(capacity[:n], np.asarray(reserved)[:n]),
            np.array(usage0, dtype=np.float32, copy=True)[:n],
            np.array(jc0, dtype=np.float32, copy=True)[:n],
            np.asarray(feasible), np.asarray(asks, dtype=np.float32))


def check_sequence_host(capacity, reserved, usage0, job_counts0, feasible,
                        asks, distinct, group_idx, valid, penalty, chosen,
                        atol: float, n_real: int = 0) -> bool:
    """Is ``chosen`` (a place_sequence result) a greedy placement this
    scorer agrees with?  Every pick must be feasible, fit, and score
    within ``atol`` of the best node at its step; a placement left
    unplaced must have had no candidate."""
    n, scorer, usage, jc, feasible, asks = _check_setup(
        capacity, reserved, usage0, job_counts0, feasible, asks, n_real)
    for p in range(len(group_idx)):
        c = int(chosen[p])
        if not valid[p]:
            if c >= 0:
                return False
            continue
        g = group_idx[p]
        masked = scorer.masked_scores(usage, jc, asks[g], feasible[g, :n],
                                      bool(distinct[g]), penalty)
        best = masked.max()
        if c < 0:
            if best > NEG_INF / 2:
                return False
            continue
        if c >= n or masked[c] <= NEG_INF / 2 or masked[c] < best - atol:
            return False
        usage[c] += asks[g]
        jc[c] += 1
    return True


def check_rounds_host(capacity, reserved, usage0, jc0, feasible, asks,
                      distinct, counts, penalty, picks_by_slot, k_cap: int,
                      rounds: int, atol: float, n_real: int = 0) -> bool:
    """Is ``picks_by_slot`` (per slot, the nodes a place_rounds result
    gave its copies, in stream order) a top-k rounds placement this
    scorer agrees with?  Each round must place as many copies as this
    scorer could, on distinct candidate nodes each scoring within
    ``atol`` of the round's k-th best."""
    n, scorer, usage, jc, feasible, asks = _check_setup(
        capacity, reserved, usage0, jc0, feasible, asks, n_real)
    for s in range(feasible.shape[0]):
        picks = np.asarray(picks_by_slot.get(s, ()), dtype=np.int64)
        remaining = int(counts[s])
        if remaining <= 0:
            if len(picks):
                return False
            continue
        for _r in range(rounds):
            masked = scorer.masked_scores(usage, jc, asks[s],
                                          feasible[s, :n],
                                          bool(distinct[s]), penalty)
            m = min(remaining, k_cap, int((masked > NEG_INF / 2).sum()))
            take, picks = picks[:m], picks[m:]
            if len(take) != m:
                return False
            if m == 0:
                continue
            if take.min() < 0 or take.max() >= n or \
                    len(np.unique(take)) != m:
                return False
            kth = np.partition(masked, n - m)[n - m]
            got = masked[take]
            if (got <= NEG_INF / 2).any() or (got < kth - atol).any():
                return False
            usage[take] += asks[s]
            jc[take] += 1
            remaining -= m
        if len(picks):
            return False
    return True
