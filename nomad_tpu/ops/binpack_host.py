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

from functools import cached_property

import numpy as np

from nomad_tpu.structs.funcs import score_fit_vec

NEG_INF = -1.0e30
DIM_CPU = 0
DIM_MEM = 1


class _HostScorer:
    """The node-static pieces of scoring, so per-step work is minimal:
    built from the real rows of ``capacity`` / ``reserved`` once a
    fleet generation (``FleetStatics.host_scorer``), or per call where
    the caller owns no statics."""

    def __init__(self, capacity, reserved) -> None:
        self.capacity = capacity
        self.reserved = reserved
        self.base = reserved.astype(np.float32)
        node_cpu = capacity[:, DIM_CPU] - reserved[:, DIM_CPU]
        node_mem = capacity[:, DIM_MEM] - reserved[:, DIM_MEM]
        self.valid_node = (node_cpu > 0) & (node_mem > 0)
        self.safe_cpu = np.where(node_cpu > 0, node_cpu, 1.0
                                 ).astype(np.float32)
        self.safe_mem = np.where(node_mem > 0, node_mem, 1.0
                                 ).astype(np.float32)

    def take(self, rows) -> "_HostScorer":
        """The scorer of ``rows`` alone: ``masked_scores`` over their
        usage, counts and mask gives what the whole fleet's gives at
        those rows."""
        if self.shape_rows is not None and len(self.shape_rows) == 1:
            rows = slice(0, 1)      # every row is this row: broadcast
        sub = object.__new__(_HostScorer)
        sub.capacity = self.capacity[rows]
        sub.base = self.base[rows]
        sub.valid_node = self.valid_node[rows]
        sub.safe_cpu = self.safe_cpu[rows]
        sub.safe_mem = self.safe_mem[rows]
        return sub

    @cached_property
    def shape_rows(self) -> "list | None":
        """The node shapes: per class of identical ``(capacity,
        reserved)`` rows (compared as bytes: finer than by value, which
        is as sound) its rows in index order.  None where the fleet has
        more shapes than ``_candidate_rows`` could use at any
        ``k_cap``."""
        n = len(self.capacity)
        if n == 0:
            return None
        if (self.capacity == self.capacity[0]).all() \
                and (self.reserved == self.reserved[0]).all():
            return [np.arange(n)]
        both = np.ascontiguousarray(
            np.concatenate([self.capacity, self.reserved], axis=1))
        as_bytes = both.view(np.dtype((np.void, both.strides[0]))).ravel()
        shapes, inverse = np.unique(as_bytes, return_inverse=True)
        if len(shapes) > _TWIN_FULL_SHARE * n:
            return None
        return [np.flatnonzero(inverse == c) for c in range(len(shapes))]

    def fit(self, usage, ask) -> tuple:
        """(util, fit): what the rows would hold with ``ask`` on top,
        and whether that is within their capacity."""
        util = self.base + usage + ask
        return util, (util <= self.capacity).all(axis=-1)

    def masked_scores(self, usage, job_counts, ask, feasible, distinct,
                      penalty):
        util, fit = self.fit(usage, ask)
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


# Where the rows held and the empties to add (``k_cap`` a node shape)
# may pass this share of the real rows, a slot-round scores every row:
# the candidate pass gathers its rows, runs the fit test and gathers
# again what passed; the whole pass reads every array in place.  Fixed
# from a sweep on the chip's host (PR 38; one shape, ``k_cap`` 16,
# medians of 60-300 calls, ms, candidate pass / whole pass with that
# share of the rows held; every held row still fits the ask, the worst
# case for the fit test, which then drops nothing):
#   rows (slots)  0%         10%        20%        25%        30%        35%        40%        50%        100%
#   5,000         0.15/0.40  0.27/0.41  0.38/0.42  0.40/0.42  0.43/0.40  0.46/0.39  0.49/0.42  0.61/0.42  0.94/0.41
#   10,000        0.17/0.71  0.39/0.71  0.54/0.70  0.62/0.70  0.67/0.70  0.74/0.72  0.80/0.70  0.96/0.69  1.62/0.68
#   131,072       0.56/15.4  2.57/7.72  4.39/7.90  5.45/7.77  6.27/7.66  7.59/7.70  8.59/8.68  9.99/7.85  19.5/8.62
#   100,000 (3)   0.85/17.0  5.37/17.3  9.64/17.2  11.7/17.2  13.8/17.2  16.0/16.9  18.1/17.3  22.7/17.0  43.3/17.3
# (4,034 rows at ``k_cap`` 512 cross at 23-28% of the rows a candidate,
# 5,000 at 1,024 at 26-30%.)  The two meet between 25% and 40%; a call
# that falls back has paid ``_occupied`` for nothing (0.02-0.05 ms at
# 5,000-10,000 rows, 0.05-0.4 at 131,072), so the line sits at the low
# end.  Where the held rows are full nodes, as in a bin-packed fleet,
# the fit test drops them and the candidate pass wins well past it.
_TWIN_FULL_SHARE = 0.25
# ``_candidate_rows`` looks for a shape's first empty rows in blocks of
# its rows from the lowest, each next twice the last.
_EMPTY_BLOCK = 4096


def _occupied(usage, jc) -> np.ndarray:
    """bool[n]: the rows whose usage row or job count is not all
    nought, read as bits (a ``-0.0`` counts as held: any superset is as
    sound) and two columns a pass where the row's bytes allow."""
    words = usage.view(np.uint64 if usage.shape[1] % 2 == 0 else np.uint32)
    bits = words[:, 0].copy()
    for d in range(1, words.shape[1]):
        bits |= words[:, d]
    held = bits != 0
    if jc.any():
        held |= jc != 0
    return held


def _candidate_rows(held, feasible, shape_rows, k_cap: int,
                    budget: float) -> "np.ndarray | None":
    """The rows one slot-round has to score, in index order, or None
    for every row.  ``held`` (bool[n], a superset of ``_occupied``)
    gains, per node shape, the first ``k_cap`` rows in index order that
    ``feasible`` admits and ``held`` lacks: every empty row of a shape
    has one masked score and ties go to the lower index, so no other
    empty row of it can be among the slot-round's top ``k_cap``.  None
    once the rows held and those to add may pass ``budget``: the
    gathers then cost more than the rows they save."""
    if np.count_nonzero(held) + k_cap * len(shape_rows) > budget:
        return None
    for rows in shape_rows:
        want, lo, block = k_cap, 0, _EMPTY_BLOCK
        while want and lo < len(rows):
            part = rows[lo:lo + block]
            free = part[feasible[part] & ~held[part]][:want]
            held[free] = True
            want -= len(free)
            lo, block = lo + block, 2 * block
    return np.flatnonzero(held)


def _place_rounds(capacity, reserved, usage0, jc0, feasible, asks,
                  distinct, counts, penalty, k_cap: int, rounds: int,
                  n_real: int, scorer, tally, full_share: float):
    capacity = np.asarray(capacity)
    n = n_real or capacity.shape[0]
    if scorer is None:
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
    # The candidate set: ``held`` while a slot-round may still score
    # fewer rows than the fleet has, None from the first that may not
    # (the set only grows through a call).
    budget = full_share * n
    shape_rows = scorer.shape_rows if k_cap <= budget else None
    held = _occupied(usage, jc) if shape_rows is not None else None
    scored = slot_rounds = 0
    for s in range(G):
        ask = asks[s]
        remaining = int(counts[s])
        if remaining <= 0:
            continue
        for r in range(rounds):
            if remaining <= 0:
                break
            rows = None if held is None else _candidate_rows(
                held, feasible[s, :n], shape_rows, k_cap, budget)
            if rows is None:
                held = None
                scored += n
                masked = scorer.masked_scores(usage, jc, ask,
                                              feasible[s, :n],
                                              bool(distinct[s]), penalty)
            else:
                scored += len(rows)
                # Most rows that hold something are full: only those
                # the ask still fits go on to be scored (the others are
                # NEG_INF in the whole pass too, and never taken).
                _util, fits = scorer.take(rows).fit(usage[rows], ask)
                rows = rows[fits]
                masked = scorer.take(rows).masked_scores(
                    usage[rows], jc[rows], ask, feasible[s, rows],
                    bool(distinct[s]), penalty)
            order = _topk_exact(masked, k_cap)
            vals = masked[order]
            if rows is not None:
                order = rows[order]
            slot_rounds += 1
            take = (pos[:len(order)] < remaining) & (vals > NEG_INF / 2)
            idx = order[take]
            usage[idx] += ask
            jc[idx] += 1
            placed = int(take.sum())
            remaining -= placed
            lo = r * k_cap
            chosen[s, lo:lo + len(order)][take] = idx.astype(np.int32)
            scores[s, lo:lo + len(order)][take] = vals[take]
    if tally is not None:
        tally.twin_rows += scored
        tally.twin_rows_full += n * slot_rounds
    return chosen, scores, usage_full


def place_rounds_host(capacity, reserved, usage0, jc0, feasible, asks,
                      distinct, counts, penalty, k_cap: int, rounds: int,
                      n_real: int = 0, *, scorer=None, tally=None):
    """numpy twin of ops/binpack.place_rounds (same args/outputs):
    [G, rounds * k_cap] per-slot placement streams via top-k rounds.

    Host-only shortcuts (results identical): node rows sliced to
    ``n_real``; padding slots (count 0 — they place nothing on the
    device too) skipped outright; and a slot-round scores a candidate
    set, not the fleet: the rows that hold something and the first
    ``k_cap`` empty rows of each node shape that its mask admits
    (``_candidate_rows`` says why no other row can be picked), with
    the same expressions in the same order as the whole pass: the fit
    test first, and the score for the rows that pass it.  Where that
    set may pass ``_TWIN_FULL_SHARE`` of the real rows it is every
    row: the whole pass, as ``place_rounds_full_host`` runs it.

    ``scorer``: the fleet generation's ``FleetStatics.host_scorer``,
    for a caller that owns statics (``capacity`` / ``reserved`` are
    then not read); derived here without it.  ``tally`` gains
    ``twin_rows``, the rows scored summed over slots and rounds, and
    ``twin_rows_full``, ``n_real`` a slot-round: what the whole pass
    scores.
    """
    return _place_rounds(capacity, reserved, usage0, jc0, feasible, asks,
                         distinct, counts, penalty, k_cap, rounds, n_real,
                         scorer, tally, _TWIN_FULL_SHARE)


def place_rounds_full_host(capacity, reserved, usage0, jc0, feasible,
                           asks, distinct, counts, penalty, k_cap: int,
                           rounds: int, n_real: int = 0):
    """``place_rounds_host`` with every row a candidate in every
    slot-round: what its candidate pass has to return."""
    return _place_rounds(capacity, reserved, usage0, jc0, feasible, asks,
                         distinct, counts, penalty, k_cap, rounds, n_real,
                         None, None, 0.0)


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
