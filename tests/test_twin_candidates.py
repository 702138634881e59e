"""``place_rounds_host`` scores a candidate set, not the fleet: the rows
that hold something and the first ``k_cap`` empty rows of each node
shape.  What it returns is held, case by case, to the pass over every
row (``place_rounds_full_host``): the same picks and scores bit for
bit, the same usage."""
from types import SimpleNamespace

import numpy as np
import pytest

from nomad_tpu.ops.binpack_host import (_EMPTY_BLOCK, _TWIN_FULL_SHARE,
                                        _HostScorer, place_rounds_full_host,
                                        place_rounds_host)

NDIMS = 6
SHAPES = (  # (capacity, reserved)
    ((4000.0, 8192.0, 100000.0, 150.0, 1000.0, 100.0),
     (100.0, 256.0, 4096.0, 0.0, 0.0, 0.0)),
    ((8000.0, 16384.0, 200000.0, 150.0, 1000.0, 100.0),
     (100.0, 256.0, 4096.0, 0.0, 0.0, 0.0)),
    ((2000.0, 4096.0, 50000.0, 150.0, 1000.0, 100.0),
     (0.0, 0.0, 0.0, 0.0, 0.0, 0.0)),
)
ASK = (500.0, 256.0, 150.0, 0.0, 50.0, 1.0)   # 7 copies fit shape 0
BIG = (3000.0, 6000.0, 150.0, 0.0, 50.0, 1.0)   # fits shapes 0 and 1 only
SMALL = (250.0, 128.0, 10.0, 0.0, 10.0, 1.0)


def _case(n, *, n_pad=None, shape_of=None, occupied=(), copies_held=None,
          job_rows=(), masked_out=(), asks=(ASK,), counts=(10,),
          distinct=(), k_cap=16, rounds=1, penalty=10.0, seed=0,
          narrow=True):
    """One input of the twin.  ``shape_of``: row -> index into SHAPES
    (one shape without it); ``occupied`` rows hold 1-6 copies of ASK
    (seeded, or ``copies_held``); ``job_rows`` carry a job count of 1;
    ``masked_out`` is (slot, rows) pairs no mask admits; ``narrow``:
    whether the candidate pass must have engaged (None: either)."""
    rng = np.random.default_rng(seed)
    n_pad = n_pad or n
    capacity = np.zeros((n_pad, NDIMS), dtype=np.float32)
    reserved = np.zeros((n_pad, NDIMS), dtype=np.float32)
    which = np.zeros(n, dtype=np.int64) if shape_of is None \
        else np.asarray([int(shape_of(i)) for i in range(n)])
    shapes = np.asarray(SHAPES, dtype=np.float32)
    capacity[:n] = shapes[which, 0]
    reserved[:n] = shapes[which, 1]
    usage = np.zeros((n_pad, NDIMS), dtype=np.float32)
    occupied = np.asarray(occupied, dtype=np.int64)
    held = rng.integers(1, 7, len(occupied)) if copies_held is None \
        else np.full(len(occupied), copies_held)
    usage[occupied] = held[:, None] * np.asarray(ASK, dtype=np.float32)
    jc = np.zeros(n_pad, dtype=np.float32)
    jc[np.asarray(job_rows, dtype=np.int64)] = 1
    g = len(asks)
    feasible = np.zeros((g, n_pad), dtype=bool)
    feasible[:, :n] = True
    for slot, rows in masked_out:
        feasible[slot, np.asarray(rows, dtype=np.int64)] = False
    flags = np.zeros(g, dtype=bool)
    flags[list(distinct)] = True
    args = (capacity, reserved, usage, jc, feasible,
            np.asarray(asks, dtype=np.float32), flags,
            np.asarray(counts, dtype=np.int32), penalty)
    return args, {"k_cap": k_cap, "rounds": rounds, "n_real": n}, narrow


def _spread(n, share, seed=0):
    """``share`` of ``n`` rows, drawn without order."""
    return np.random.default_rng(seed).choice(
        n, int(round(n * share)), replace=False)


def _line(n, k_cap, over):
    """Occupied rows that put held + ``k_cap`` one row under (or over)
    ``_TWIN_FULL_SHARE`` of ``n``."""
    return _spread(n, 1.0)[:int(_TWIN_FULL_SHARE * n) - k_cap + over]


CASES = {
    "no-row-occupied": lambda: _case(200),
    "every-row-occupied": lambda: _case(
        200, occupied=range(200), narrow=False),
    "every-row-full": lambda: _case(
        64, occupied=range(64), copies_held=7, narrow=False),
    "ties-straddle-k-cap": lambda: _case(300, k_cap=4, counts=(10,)),
    "ties-straddle-k-cap-rounds": lambda: _case(
        300, k_cap=4, counts=(10,), rounds=3),
    "few-occupied-win": lambda: _case(2000, occupied=_spread(2000, 0.02)),
    "occupied-full-empties-win": lambda: _case(
        500, occupied=range(0, 500, 9), copies_held=7),
    "occupied-and-empties-mix": lambda: _case(
        400, occupied=(3, 50, 51, 399), copies_held=6, counts=(12,)),
    "two-shapes-blocks": lambda: _case(
        600, shape_of=lambda i: i >= 300, occupied=_spread(600, 0.05)),
    "two-shapes-interleaved": lambda: _case(
        600, shape_of=lambda i: i % 2, occupied=_spread(600, 0.05, 1),
        counts=(14,)),
    "two-shapes-one-too-small": lambda: _case(
        400, shape_of=lambda i: 2 * (i % 2), asks=(BIG,), counts=(9,)),
    "three-shapes": lambda: _case(
        900, shape_of=lambda i: i % 3, occupied=_spread(900, 0.1, 2),
        asks=(ASK, SMALL), counts=(10, 12)),
    "three-shapes-big-ask": lambda: _case(
        900, shape_of=lambda i: (i // 7) % 3, asks=(BIG, ASK),
        counts=(16, 16), occupied=_spread(900, 0.08, 3)),
    "every-row-its-own-shape": lambda: _every_row_a_shape(),
    "mask-hides-lowest-empties": lambda: _case(
        500, masked_out=((0, range(0, 100)),), occupied=(120, 130)),
    "mask-hides-all-but-a-few": lambda: _case(
        500, masked_out=((0, range(0, 495)),), counts=(10,)),
    "mask-hides-everything": lambda: _case(
        100, masked_out=((0, range(100)),)),
    "empties-past-the-first-block": lambda: _case(
        _EMPTY_BLOCK * 4, masked_out=((0, range(_EMPTY_BLOCK * 3 + 5)),),
        occupied=(1, _EMPTY_BLOCK * 3 + 7)),
    "empties-across-two-blocks": lambda: _case(
        _EMPTY_BLOCK * 2, k_cap=32, counts=(30,),
        masked_out=((0, range(_EMPTY_BLOCK - 10)),)),
    "distinct-job-counts-on-empties": lambda: _case(
        300, job_rows=(0, 1, 2, 5), distinct=(0,), counts=(8,)),
    "distinct-two-slots": lambda: _case(
        300, job_rows=(0, 4), distinct=(0, 1), asks=(ASK, SMALL),
        counts=(5, 5), occupied=_spread(300, 0.1, 4)),
    "penalty-on-job-rows": lambda: _case(
        300, job_rows=(10, 11, 12), occupied=(10, 11, 12, 13),
        copies_held=5, counts=(6,)),
    "rounds-3-k-cap-under-count": lambda: _case(
        400, k_cap=4, counts=(11,), rounds=3,
        occupied=_spread(400, 0.1, 5)),
    "rounds-3-fleet-runs-out": lambda: _case(
        12, k_cap=4, counts=(100,), rounds=3, narrow=None),
    "three-slots-land-on-just-filled": lambda: _case(
        1000, asks=(ASK, ASK, SMALL), counts=(10, 5, 1),
        occupied=_spread(1000, 0.03, 6)),
    "three-slots-empty-fleet": lambda: _case(
        1000, asks=(ASK, BIG, SMALL), counts=(10, 5, 1)),
    "padding-slots-between": lambda: _case(
        300, asks=(ASK, ASK, SMALL, SMALL), counts=(4, 0, 0, 6),
        occupied=(7, 8)),
    "n-real-under-n-pad": lambda: _case(
        300, n_pad=512, occupied=_spread(300, 0.05, 7)),
    "n-real-under-n-pad-three-slots": lambda: _case(
        1000, n_pad=1024, asks=(ASK, BIG, SMALL), counts=(10, 5, 1),
        shape_of=lambda i: i % 2, occupied=_spread(1000, 0.04, 8)),
    "minus-zero-usage-row": lambda: _minus_zero(),
    "k-cap-over-feasible-rows": lambda: _case(
        400, k_cap=64, counts=(60,), masked_out=((0, range(20, 400)),),
        occupied=(1, 2)),
    "k-cap-over-the-fleet": lambda: _case(10, k_cap=16, narrow=False),
    "just-under-the-line": lambda: _case(
        4000, occupied=_line(4000, 16, 0)),
    "just-over-the-line": lambda: _case(
        4000, occupied=_line(4000, 16, 1), narrow=False),
    "second-slot-crosses-the-line": lambda: _case(
        4000, asks=(ASK, SMALL), counts=(10, 10),
        occupied=_line(4000, 16, -15), narrow=None),
    "random-masks-1": lambda: _random(1),
    "random-masks-2": lambda: _random(2),
    "random-masks-3": lambda: _random(3),
    "random-masks-4": lambda: _random(4),
}


def _every_row_a_shape():
    args, kw, _ = _case(200, occupied=(4, 9))
    args[0][:200, 0] += np.arange(200, dtype=np.float32)
    return args, kw, False


def _minus_zero():
    args, kw, narrow = _case(200, occupied=(50,), counts=(12,))
    args[2][3] = -0.0
    args[2][7, 2] = -0.0
    return args, kw, narrow


def _random(seed):
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(200, 3000))
    hidden = [(s, np.flatnonzero(rng.random(n) < 0.3)) for s in range(3)]
    return _case(n, n_pad=n + int(rng.integers(0, 64)),
                 shape_of=lambda i: (i * 7 + seed) % 3,
                 occupied=_spread(n, 0.12, seed),
                 job_rows=_spread(n, 0.02, seed + 50), masked_out=hidden,
                 asks=(ASK, BIG, SMALL), counts=rng.integers(1, 40, 3),
                 distinct=(seed % 3,), k_cap=8, rounds=3, seed=seed)


@pytest.mark.parametrize("name", list(CASES))
def test_candidate_pass_returns_the_whole_pass(name):
    args, kw, narrow = CASES[name]()
    want = place_rounds_full_host(*args, **kw)
    tally = SimpleNamespace(twin_rows=0, twin_rows_full=0)
    got = place_rounds_host(*args, **kw, tally=tally)
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])
    np.testing.assert_allclose(got[2], want[2])
    # The scorer a fleet generation keeps gives what a derived one does.
    n = kw["n_real"]
    kept = place_rounds_host(*args, **kw,
                             scorer=_HostScorer(args[0][:n], args[1][:n]))
    assert all(np.array_equal(a, b) for a, b in zip(kept, got))
    assert 0 <= tally.twin_rows <= tally.twin_rows_full
    assert tally.twin_rows_full % n == 0
    if narrow is not None:
        assert (tally.twin_rows < tally.twin_rows_full) == narrow


def test_whole_pass_reports_every_row():
    args, kw, _ = CASES["three-slots-empty-fleet"]()
    tally = SimpleNamespace(twin_rows=0, twin_rows_full=0)
    place_rounds_host(*args, **kw, tally=tally)
    # Three slot-rounds of an empty 1,000-row fleet: 16 rows, then the
    # 16 held and 16 more a slot.
    assert (tally.twin_rows, tally.twin_rows_full) == (16 + 32 + 48, 3000)


def test_node_shapes():
    args, _kw, _ = CASES["three-shapes"]()
    rows = _HostScorer(args[0], args[1]).shape_rows
    assert sorted(r[0] for r in rows) == [0, 1, 2]
    assert all((np.diff(r) == 3).all() for r in rows)
    one = _case(50)[0]
    assert [r.tolist() for r in _HostScorer(one[0], one[1]).shape_rows] \
        == [list(range(50))]
    args, _kw, _ = _every_row_a_shape()
    assert _HostScorer(args[0], args[1]).shape_rows is None
