"""The reader of ``sched.dispatch``'s kernel tags, on hand-built spans:
the share worked out by hand where the tags are there, nothing (never
0) where a program does not write them."""
from test_xplane import reducer


def lane(rounds=1, mode="rounds", engine="host"):
    return {"name": "sched.dispatch", "t0": 0.0, "dur": 0.01, "tags": {
        "rounds": rounds, "mode": mode, "engine": engine}}


TAGGED = [lane(), lane(engine="device"), lane(rounds=2),
          lane(rounds=0, mode="sequence"),
          {"name": "sched.begin", "t0": 0.0, "dur": 1.0,
           "tags": {"mode": "sequence"}}]
# The parent's spans: ``host`` / ``fused`` and no more.
UNTAGGED = [{"name": "sched.dispatch", "t0": 0.0, "dur": 0.01,
             "tags": {"host": True}},
            {"name": "sched.dispatch", "t0": 0.1, "dur": 0.01,
             "tags": {"fused": 4}},
            {"name": "sched.dispatch", "t0": 0.2, "dur": 0.01}]


def read(spans):
    return reducer("multi_round_lanes").reduce({}, {"spans": spans,
                                                    "notes": []})


def test_untagged_spans_read_nothing():
    assert read(UNTAGGED) is None
    assert read([]) is None


def test_multi_round_share():
    assert read(TAGGED) == 50.0
    # Untagged lanes beside tagged ones are left out, not counted as 0.
    assert read(TAGGED[:2] + UNTAGGED) == 0.0
