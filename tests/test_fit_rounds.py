"""``_fit_rounds`` examines rows only until its answer is fixed: the
bounded walk against the whole walk, kept here as the reference, on
seeded random fleets and on shaped ones, and the rows it reports."""
from types import SimpleNamespace

import numpy as np
import pytest

from nomad_tpu.scheduler.jax_binpack import _FIT_BLOCK, _fit_rounds

NDIMS = 6
CAPACITY = (4000.0, 8192.0, 100000.0, 150.0, 1000.0, 40000.0)
RESERVED = (100.0, 256.0, 4096.0, 0.0, 0.0, 1.0)
ASK = (500.0, 256.0, 0.0, 0.0, 50.0, 1.0)      # 7 copies fit an empty node
FULL = (3900.0, 0.0, 0.0, 0.0, 0.0, 0.0)       # usage that leaves no cpu


def _whole_walk(statics, view, feasible_h, asks, slot_placements, k_cap,
                rounds):
    """The walk as it was: every real row, once a slot."""
    n = statics.n_real
    if n == 0 or not slot_placements:
        return rounds, True
    if max(len(ps) for ps in slot_placements.values()) <= rounds:
        return rounds, True
    for slot, ps in slot_placements.items():
        fit = ((view.usage[:n] + statics.reserved[:n] + asks[slot])
               <= statics.capacity[:n]).all(axis=-1)
        fit_count = int((fit & feasible_h[slot, :n]).sum())
        if fit_count == 0:
            continue
        need = -(-len(ps) // min(fit_count, k_cap))
        if need > 16:
            return rounds, False
        rounds = max(rounds, need)
    if rounds > 1:
        rounds = 1 << (rounds - 1).bit_length()
    return min(rounds, 16), True


def _fleet(n_real, full_rows=(), n_pad=None, asks=(ASK,), copies=(10,),
           infeasible=None):
    """A fleet of one machine shape whose ``full_rows`` have no cpu
    left; slot g asks ``asks[g]`` ``copies[g]`` times.  The padded rows
    hold what a row that fits everything would: were one counted, the
    counts below would show it."""
    n_pad = n_pad or n_real
    capacity = np.zeros((n_pad, NDIMS), dtype=np.float32)
    capacity[:] = CAPACITY
    reserved = np.zeros((n_pad, NDIMS), dtype=np.float32)
    reserved[:n_real] = RESERVED
    usage = np.zeros((n_pad, NDIMS), dtype=np.float32)
    usage[np.asarray(full_rows, dtype=np.int64)] = FULL
    feasible_h = np.zeros((8, n_pad), dtype=bool)
    feasible_h[:len(asks)] = True
    if infeasible is not None:
        slot, rows = infeasible
        feasible_h[slot, rows] = False
    asks_h = np.zeros((8, NDIMS), dtype=np.float32)
    asks_h[:len(asks)] = asks
    slot_placements, p = {}, 0
    for g, count in enumerate(copies):
        slot_placements[g] = list(range(p, p + count))
        p += count
    return (SimpleNamespace(n_real=n_real, capacity=capacity,
                            reserved=reserved),
            SimpleNamespace(usage=usage), feasible_h, asks_h,
            slot_placements)


def _both(fleet, k_cap=16, rounds=1):
    tally = SimpleNamespace(fit_rows=0, fit_rows_full=0)
    got = _fit_rounds(*fleet, k_cap, rounds, tally)
    assert got == _whole_walk(*fleet, k_cap, rounds)
    return got, tally


def _all_but(n, free, where):
    """Rows of an ``n``-row fleet that are full, leaving ``free`` rows
    with room at the low end, at the high end, or evenly spread."""
    if where == "low":
        return np.arange(free, n)
    if where == "high":
        return np.arange(n - free)
    keep = np.linspace(0, n - 1, free).astype(np.int64)
    return np.setdiff1d(np.arange(n), keep)


N = 5 * _FIT_BLOCK + 1234      # not a multiple of a block, four blocks

SHAPED = {
    # name: (fleet, k_cap, rounds) -> the pair wanted
    "room_everywhere": (lambda: _fleet(N), 16, 1, (1, True)),
    "full_low_rows": (
        lambda: _fleet(N, full_rows=np.arange(_FIT_BLOCK + 7)),
        16, 1, (1, True)),
    "full_high_rows": (
        lambda: _fleet(N, full_rows=np.arange(N - 3 * _FIT_BLOCK, N)),
        16, 1, (1, True)),
    "full_interleaved": (
        lambda: _fleet(N, full_rows=np.arange(0, N, 2)), 16, 1, (1, True)),
    "five_fit_rounds_2": (
        lambda: _fleet(N, full_rows=_all_but(N, 5, "high")),
        16, 1, (2, True)),
    "three_fit_rounds_4": (
        lambda: _fleet(N, full_rows=_all_but(N, 3, "spread")),
        16, 1, (4, True)),
    "two_fit_rounds_8": (
        lambda: _fleet(N, full_rows=_all_but(N, 2, "low")),
        16, 1, (8, True)),
    "one_fits_rounds_16": (
        lambda: _fleet(N, full_rows=_all_but(N, 1, "high")),
        16, 1, (16, True)),
    "need_over_16_not_eligible": (
        lambda: _fleet(N, full_rows=_all_but(N, 2, "spread"),
                       copies=(40,)), 64, 1, (1, False)),
    "k_cap_under_the_fit_count": (
        lambda: _fleet(N, copies=(100,)), 8, 1, (16, True)),
    "empty_slot_beside_one_with_room": (
        lambda: _fleet(N, asks=((5000.0, 1, 0, 0, 0, 0), ASK),
                       copies=(10, 10)), 16, 1, (1, True)),
    "masked_by_feasible": (
        lambda: _fleet(N, infeasible=(0, np.arange(4, N))),
        16, 1, (4, True)),
    "under_one_block": (lambda: _fleet(1000), 16, 1, (1, True)),
    "under_one_block_short_of_room": (
        lambda: _fleet(1000, full_rows=_all_but(1000, 4, "spread")),
        16, 1, (4, True)),
    "padded_rows_never_counted": (
        lambda: _fleet(3, n_pad=1024), 16, 1, (4, True)),
    "padded_rows_past_a_block": (
        lambda: _fleet(_FIT_BLOCK + 5, n_pad=2 * _FIT_BLOCK,
                       full_rows=np.arange(_FIT_BLOCK + 3)),
        16, 1, (8, True)),
    "three_slots_three_asks": (
        lambda: _fleet(N, full_rows=_all_but(N, 6, "high"),
                       asks=(ASK, (500.0, 128, 0, 0, 100, 2),
                             (3900.0, 256, 0, 0, 10, 1)),
                       copies=(10, 5, 3)), 16, 1, (2, True)),
    "rounds_already_raised": (
        lambda: _fleet(N, full_rows=_all_but(N, 3, "high")),
        16, 4, (4, True)),
    # ``rounds`` given over 16 (no caller does): the walk may stop only
    # once ``need`` is under 16 too, or it would report a scan shape
    # that the later rows take back.
    "rounds_given_over_16": (
        lambda: _fleet(N, full_rows=np.arange(2, N - 2), copies=(60,)),
        64, 48, (16, True)),
    "need_over_16_under_rounds_given": (
        lambda: _fleet(N, full_rows=_all_but(N, 2, "low"), copies=(60,)),
        64, 48, (48, False)),
}


@pytest.mark.parametrize("name", sorted(SHAPED))
def test_shaped_fleets_read_as_the_whole_walk(name):
    make, k_cap, rounds, wanted = SHAPED[name]
    got, tally = _both(make(), k_cap, rounds)
    assert got == wanted
    assert 0 < tally.fit_rows <= tally.fit_rows_full


@pytest.mark.parametrize("seed", range(8))
def test_seeded_random_fleets_read_as_the_whole_walk(seed):
    rng = np.random.default_rng(3600 + seed)
    n_real = int(rng.integers(1, 6 * _FIT_BLOCK))
    n_slots = int(rng.integers(1, 4))
    statics, view, feasible_h, asks, slot_placements = _fleet(
        n_real, n_pad=n_real + int(rng.integers(0, 2000)),
        asks=[(float(rng.choice([100, 500, 2000, 3900])),
               float(rng.choice([64, 256, 4096])), 0, 0,
               float(rng.choice([0, 50, 600])), 1)
              for _ in range(n_slots)],
        copies=[int(rng.choice([1, 2, 10, 30, 200]))
                for _ in range(n_slots)])
    # Usage from empty to full a row, dense or sparse a fleet.
    fill = rng.random(n_real) < rng.choice([0.0, 0.5, 0.999, 1.0])
    view.usage[:n_real, 0] = np.where(
        fill, 3900.0, rng.choice([0.0, 1500.0, 3500.0], n_real))
    feasible_h[:n_slots, :n_real] = \
        rng.random((n_slots, n_real)) < rng.choice([0.001, 0.5, 1.0])
    k_cap = int(rng.choice([1, 8, 16, 256]))
    for rounds in (1, 2, 16):
        _both((statics, view, feasible_h, asks, slot_placements),
              k_cap, rounds)


def test_room_in_the_first_block_reads_one_block_a_slot():
    fleet = _fleet(N, asks=(ASK, (500.0, 128, 0, 0, 100, 2)),
                   copies=(10, 5))
    _got, tally = _both(fleet)
    assert tally.fit_rows == 2 * _FIT_BLOCK
    assert tally.fit_rows_full == 2 * N


def test_full_low_rows_read_one_block_more():
    """The first block holds nine nodes with room for ten copies: the
    walk goes on into the second block, of twice the rows, and no
    further."""
    _got, tally = _both(_fleet(N, full_rows=np.arange(_FIT_BLOCK - 9)))
    assert (tally.fit_rows, tally.fit_rows_full) == (3 * _FIT_BLOCK, N)


def test_a_full_fleet_reads_every_real_row_a_slot():
    fleet = _fleet(N, n_pad=N + 500, full_rows=np.arange(N),
                   asks=(ASK, ASK[:1] + (1.0,) + ASK[2:]), copies=(10, 3))
    got, tally = _both(fleet)
    assert got == (1, True)     # nothing fits: one cheap dispatch
    assert tally.fit_rows == tally.fit_rows_full == 2 * N


@pytest.mark.parametrize("n_real", [1, 4034, _FIT_BLOCK])
def test_a_fleet_of_at_most_one_block_runs_the_one_pass(n_real):
    """With room or without, the walk's one block is the whole fleet."""
    for full_rows in ((), np.arange(n_real)):
        _got, tally = _both(_fleet(n_real, full_rows=full_rows))
        assert tally.fit_rows == tally.fit_rows_full == n_real


@pytest.mark.parametrize("fleet,rounds", [
    (lambda: _fleet(0, n_pad=8), 1),
    (lambda: _fleet(N, copies=()), 1),
    (lambda: _fleet(N, copies=(1, 1)), 1),      # no slot over ``rounds``
    (lambda: _fleet(N, copies=(4, 2)), 4),
], ids=["no_nodes", "no_slots", "one_copy_a_slot", "copies_within_rounds"])
def test_the_early_return_reads_no_rows(fleet, rounds):
    got, tally = _both(fleet(), rounds=rounds)
    assert got == (rounds, True)
    assert (tally.fit_rows, tally.fit_rows_full) == (0, 0)
