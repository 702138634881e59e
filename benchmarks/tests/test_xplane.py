"""The reduction from a profiler trace to numbers, on a hand-built
trace (every number worked out by hand below) and on a small trace
recorded on a TPU v5e (cell c1m-5k.jobs1000, PR 24: ten row-scatter
programs in six seconds)."""
import os

import pytest

import xplane
from run import load_module

HERE = os.path.dirname(os.path.abspath(__file__))


def reducer(name):
    return load_module("reducers", name)


# One device plane, times in ns.  Ops: [0, 4e8) and [2e8, 6e8) overlap ->
# busy 0.6 s; then [1.0e9, 1.1e9) -> 0.1 s.  Busy union 0.7 s of a 2 s
# slice: idle share 65 %.  Gaps: 0.4 s (0.6 -> 1.0).
HAND = {"planes": {"/device:TPU:0": {
    "XLA Ops": [("%fusion.1", 0.0, 4e8), ("%copy.2", 2e8, 4e8),
                ("%fusion.1", 1.0e9, 1e8)],
    "XLA Modules": [("jit__place_rounds_batched(1)", 0.0, 6e8),
                    ("jit__scatter_jit_impl(2)", 1.0e9, 1e8)],
}}, "plane_names": ["/device:TPU:0", "/host:CPU"]}


def test_hand_built_trace():
    r = xplane.reduce(HAND, 2.0)
    assert r["busy_s"] == pytest.approx(0.7)
    assert r["chips_busy"] == 1
    assert r["modules"] == [
        ("jit__place_rounds_batched(1)", 0.0, pytest.approx(0.6)),
        ("jit__scatter_jit_impl(2)", pytest.approx(1.0), pytest.approx(0.1))]
    ops = dict(r["device_ops"])
    assert ops["%fusion.1"] == pytest.approx(0.5)
    assert ops["%copy.2"] == pytest.approx(0.4)
    assert r["idle_gaps"][0][1] == pytest.approx(0.4)
    idle = reducer("device_idle_share").reduce({}, {"trace": r})
    assert idle == pytest.approx(65.0)


def test_idle_share_of_an_empty_trace_is_nothing():
    r = xplane.reduce({"planes": {}, "plane_names": []}, 2.0)
    assert r["busy_s"] == 0.0
    assert reducer("device_idle_share").reduce({}, {"trace": r}) is None


def test_two_chips_are_averaged():
    two = {"planes": {
        "/device:TPU:0": {"XLA Ops": [("a", 0.0, 1e9)]},
        "/device:TPU:1": {"XLA Ops": [("a", 0.0, 5e8)]}}, "plane_names": []}
    assert xplane.reduce(two, 2.0)["busy_s"] == pytest.approx(0.75)


def test_recorded_v5e_trace():
    path = os.path.join(HERE, "fixtures", "v5e_scatter.xplane.pb")
    loaded = xplane.load(path)
    assert list(loaded["planes"]) == ["/device:TPU:0"]
    assert "/host:CPU" in loaded["plane_names"]
    r = xplane.reduce(loaded, 6.004533569)
    assert len(r["modules"]) == 10
    assert all(name.startswith("jit__scatter_jit_impl")
               for name, _s, _d in r["modules"])
    assert r["busy_s"] == pytest.approx(1.16085e-4, rel=1e-6)
    assert sum(d for _n, _s, d in r["modules"]) == pytest.approx(
        1.16204e-4, rel=1e-6)
    assert r["idle_gaps"][0][1] == pytest.approx(0.873644474)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    idle = reducer("device_idle_share").reduce({}, {"trace": r})
    assert idle == pytest.approx(99.998067, abs=1e-5)
