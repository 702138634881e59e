"""The span and counter readers: the number worked out by
hand where there is something to read, nothing (never 0) where not."""
import pytest

from test_xplane import reducer

SPANS = [{"name": "raft.apply", "t0": 1.0, "dur": 0.010, "tags": {}},
         {"name": "raft.apply", "t0": 2.0, "dur": 0.030, "tags": {"x": 1}},
         {"name": "broker.wait", "t0": 2.0, "dur": 0.500}]
CTX = {"spans": SPANS,
       "counters_open": {"a": 10, "b": 5, "c": 7},
       "counters_close": {"a": 13, "b": 6, "c": 7}}


@pytest.mark.parametrize("name, params, want", [
    ("span_mean_ms", {"span": "raft.apply"}, 20.0),
    ("span_mean_ms", {"span": "raft.apply", "tag": "x"}, 30.0),
    ("span_mean_ms", {"span": "sched.finish"}, None),
    ("counter_ratio", {"numerator": ["a"], "denominator": ["a", "b"],
                       "scale": 100}, 75.0),
    ("counter_ratio", {"numerator": ["b"], "denominator": ["a"]}, 1 / 3),
    ("counter_ratio", {"numerator": ["a"], "denominator": ["c"]}, None),
])
def test_reader(name, params, want):
    got = reducer(name).reduce(params, CTX)
    assert got is None if want is None else got == pytest.approx(want)
