"""Resident work: a configuration's ``resident`` jobs, placed on the fleet
before the window (``reference.resident_layout``), written through the
FSM's own entries (``served.Served.load_resident``) and held to the
layout after it (``check.resident_lost``).

The layout is a function of the configuration and the seed, fits every
node and is refused where it would not; the load writes exactly the
layout and owes no evaluation; a configuration without ``resident``
lays out nothing, makes the fleet it always made and writes no entry
but its nodes'.  The fixture (``fixtures/resident3.json``) is a test's,
not a deployment."""
import json
import os

import numpy as np
import pytest

import check
import reference
import served
from conftest import FIXTURES, ROOT

SEED = 2 ** 31 + 44


def load(name):
    with open(os.path.join(FIXTURES, f"{name}.json")) as fh:
        return json.load(fh)


def layout(config, seed=SEED, n=512):
    fleet = reference.make_fleet(config, seed, n)
    return fleet, reference.resident_layout(config, fleet, seed)


def live_columns(answers):
    allocs = answers["allocs"]
    keep = allocs["running"]
    return {k: (v[keep] if isinstance(v, np.ndarray)
                else [x for x, k2 in zip(v, keep) if k2])
            for k, v in allocs.items()}


def over(fleet, made):
    return reference.oversubscribed_nodes(
        fleet, {"node": made["row"], "vec": made["vec"],
                "ports": made["ports"]})


def test_the_layout_is_a_function_of_the_configuration_and_the_seed():
    config = load("resident3")
    _fleet, one = layout(config)
    _fleet, again = layout(config)
    for key in ("id", "job", "group", "name", "ports"):
        assert one[key] == again[key], key
    assert np.array_equal(one["row"], again["row"])
    assert np.array_equal(one["vec"], again["vec"])
    _fleet, other = layout(config, seed=SEED + 1)
    # The sampled half of a class's rows moves with the seed; the job
    # that holds every row of its share does not.
    assert not np.array_equal(one["row"], other["row"])
    assert set(one["name"]) == set(other["name"])
    assert not set(one["id"]) & set(other["id"])


def test_the_layout_fits_every_node_and_names_every_copy():
    config = load("resident3")
    fleet, made = layout(config)
    used = fleet["reserved"].copy()
    np.add.at(used, made["row"], made["vec"])
    assert (used <= fleet["capacity"]).all()
    assert len(set(made["id"])) == len(made["id"])
    assert len(set(zip(made["job"], made["name"]))) == len(made["name"])
    classes = [c["name"] for c in fleet["classes"]]
    for spec, stated in zip(made["jobs"], config["resident"]):
        rows = made["row"][np.asarray(made["job"]) == spec["id"]]
        allowed = [classes.index(c) for c in stated.get("classes", classes)]
        held = np.isin(fleet["class_of"], allowed)
        assert np.isin(fleet["class_of"][rows], allowed).all()
        distinct = np.unique(rows)
        assert len(distinct) == round(stated["node_share"] * held.sum())
        assert len(rows) == stated["per_node"] * len(distinct)
        group = spec["groups"][0]
        assert group["count"] == len(rows)
        assert spec["type"] == stated["type"]
        assert spec["priority"] == stated["priority"]
        names = [n for n, j in zip(made["name"], made["job"])
                 if j == spec["id"]]
        assert names == [f"{spec['id']}.{group['name']}[{i}]"
                         for i in range(len(rows))]
    # Ports: from 20000 up on each node, none twice, none reserved.
    seen = {}
    for row, ports in zip(made["row"].tolist(), made["ports"]):
        for p in ports:
            assert p not in seen.setdefault(row, set())
            assert p not in fleet["class_reserved_ports"][
                fleet["class_of"][row]]
            seen[row].add(p)
    assert sorted(seen[int(made["row"][-1])])[0] == 20000
    assert over(fleet, made) == (0, [])


def test_a_node_reserved_port_is_skipped():
    config = load("resident3")
    config["node_classes"][0]["node"]["reserved"]["ports"] = [22, 20001]
    fleet, made = layout(config)
    row = next(r for r, j in zip(made["row"].tolist(), made["job"])
               if j == "resident-web" and fleet["class_of"][r] == 0)
    ports = sorted(p for r, ps in zip(made["row"].tolist(), made["ports"])
                   if r == row for p in ps)
    assert ports == [20000, 20002, 20003, 20004]


@pytest.mark.parametrize("key, value", [
    ("cpu", 3600),          # with resident-web's two copies: 4,100 MHz
    ("memory_mb", 7000),
    ("per_node", 2),
])
def test_a_stanza_that_would_overfill_a_node_is_refused(key, value):
    config = load("resident3")
    config["resident"][0][key] = value
    with pytest.raises(ValueError, match="overfills node rows"):
        layout(config)


def test_ports_past_the_dynamic_range_are_refused():
    config = load("resident3")
    config["resident"][1].update(cpu=1, memory_mb=1, mbits=0, per_node=1,
                                 dynamic_ports=["p"] * 40001)
    with pytest.raises(ValueError, match="overfills node rows"):
        layout(config, n=4)


@pytest.mark.parametrize("entry", [
    {"classes": ["no-such-class"]}, {"node_share": 0.0},
    {"node_share": 1.5}, {"per_node": 0}, {"type": "system"}])
def test_a_stanza_outside_its_keys_is_refused(entry):
    config = load("resident3")
    config["resident"][0].update(entry)
    with pytest.raises(ValueError):
        layout(config)


def configurations():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    out = []
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as fh:
            out.append(json.load(fh))
    return out + [load("classes3")]


@pytest.mark.parametrize("config", configurations(),
                         ids=lambda c: c["name"])
def test_a_configuration_without_resident_lays_out_nothing(config):
    assert "resident" not in config
    fleet, made = layout(config, n=1024)
    assert made["jobs"] == [] and made["id"] == [] and made["ports"] == []
    assert made["row"].shape == (0,) and made["vec"].shape == (0, 6)
    # The fleet is the one the configuration made before it could hold
    # resident work, and a resident stanza does not change it.
    again = reference.make_fleet(dict(config, resident=load(
        "resident3")["resident"][1:]), SEED, 1024)
    assert again["ids"] == fleet["ids"]
    for key in ("class_of", "capacity", "reserved"):
        assert np.array_equal(again[key], fleet[key])
    assert check.resident_lost(made, {"id": []}) == 0


def bring_up(name, tmp_path_factory):
    """256 nodes of the fixture ``name`` brought up by ``Served`` with
    their layout, every raft entry written on the way recorded:
    (fleet, layout, answers, [message types], the store's jobs by id,
    its evaluations)."""
    from nomad_tpu.server.server import Server

    config = load(name)
    fleet, made = layout(config, n=256)
    entries = []
    sound = Server.raft_apply

    def record(self, msg_type, payload):
        entries.append(msg_type)
        return sound(self, msg_type, payload)

    Server.raft_apply = record
    try:
        up = served.Served(config, fleet, SEED, str(
            tmp_path_factory.mktemp(f"{name}-raft")), lambda _msg: None,
            made)
    finally:
        Server.raft_apply = sound
    try:
        answers = up.read_back(set())
        state = up.agent.server.fsm.state
        jobs = {j.id: j for j in state.jobs()}
        evals = list(state.evals())
    finally:
        up.shutdown()
    return fleet, made, answers, entries, jobs, evals


@pytest.fixture(scope="module")
def with_resident(tmp_path_factory):
    return bring_up("resident3", tmp_path_factory)


@pytest.fixture(scope="module")
def without_resident(tmp_path_factory):
    return bring_up("classes3", tmp_path_factory)


def test_the_load_writes_exactly_the_layout(with_resident):
    from nomad_tpu.structs import codec

    fleet, made, answers, entries, jobs, evals = with_resident
    live = live_columns(answers)
    assert len(live["id"]) == len(made["id"]) == len(answers["allocs"]["id"])
    assert check.resident_lost(made, live) == 0
    at = {aid: i for i, aid in enumerate(live["id"])}
    i = [at[aid] for aid in made["id"]]
    assert np.array_equal(live["node"][i], made["row"])
    assert np.array_equal(live["vec"][i], made["vec"])
    assert [live["ports"][k] for k in i] == made["ports"]
    assert [live["name"][k] for k in i] == made["name"]
    assert [live["job"][k] for k in i] == made["job"]
    assert reference.oversubscribed_nodes(fleet, live) == (0, [])
    # One register a job, whose group's count is its allocations; no
    # evaluation owed, none made.
    assert evals == []
    assert {j: (jobs[j].type, jobs[j].priority,
                jobs[j].task_groups[0].count) for j in jobs} == {
        s["id"]: (s["type"], s["priority"], s["groups"][0]["count"])
        for s in made["jobs"]}
    chunks = sum(-(-s["groups"][0]["count"] // served.RESIDENT_CHUNK)
                 for s in made["jobs"])
    assert entries.count(codec.JOB_REGISTER_REQUEST) == len(made["jobs"])
    assert entries.count(codec.ALLOC_UPDATE_REQUEST) == chunks
    assert codec.EVAL_UPDATE_REQUEST not in entries


def test_a_lost_a_moved_and_a_renumbered_allocation_count_once_each(
        with_resident):
    _fleet, made, answers, _entries, _jobs, _evals = with_resident
    live = live_columns(answers)
    at = {aid: i for i, aid in enumerate(live["id"])}
    web = [at[aid] for aid, p in zip(made["id"], made["ports"]) if p]
    store = [at[aid] for aid, p in zip(made["id"], made["ports"]) if not p]
    broken = dict(live, id=list(live["id"]), node=live["node"].copy(),
                  ports=list(live["ports"]))
    broken["id"][store[0]] = "lost"
    broken["node"][store[1]] = (broken["node"][store[1]] + 1) \
        % len(answers["allocs"]["id"])
    broken["ports"][web[0]] = [p + 40 for p in broken["ports"][web[0]]]
    assert check.resident_lost(made, broken) == 3


def test_without_resident_only_the_nodes_are_written(without_resident):
    """No job, no allocation, no evaluation and no entry but the nodes'
    before the window: what a configuration without ``resident`` wrote
    before there was such a key."""
    from nomad_tpu.structs import codec

    _fleet, made, answers, entries, jobs, evals = without_resident
    assert made["jobs"] == [] and answers["allocs"]["id"] == []
    assert jobs == {} and evals == []
    assert entries and not {codec.JOB_REGISTER_REQUEST,
                            codec.ALLOC_UPDATE_REQUEST,
                            codec.EVAL_UPDATE_REQUEST} & set(entries)


def test_a_job_whose_share_rounds_to_no_row_is_left_out():
    """A rehearsal's fleet is a prefix of the configuration's: a class
    of three machines in 12,583 holds no row of 1,024, and its resident
    job would ask no copy (a count the program refuses)."""
    config = load("resident3")
    config["resident"][0]["node_share"] = 0.001
    _fleet, made = layout(config, n=512)
    assert [s["id"] for s in made["jobs"]] == ["resident-web"]
    assert set(made["job"]) == {"resident-web"}


def test_every_resident_allocation_is_lost_from_an_empty_store():
    config = load("resident3")
    _fleet, made = layout(config)
    empty = {"id": [], "job": [], "name": [], "ports": [],
             "node": np.zeros(0, dtype=np.int64),
             "vec": np.zeros((0, 6))}
    assert check.resident_lost(made, empty) == len(made["id"]) > 0


def test_a_rehearsal_without_resident_keeps_its_last_line(monkeypatch):
    """The result line of a configuration without ``resident``: the keys
    and metrics it had, and the checks it had plus ``resident_lost``,
    which reads 0."""
    import io
    from contextlib import redirect_stdout

    import run as bench_run
    from conftest import FIXTURE_CELL, fixture_cell

    monkeypatch.setattr(bench_run, "find_cell", fixture_cell)
    out = io.StringIO()
    with redirect_stdout(out):
        assert bench_run.main(
            ["--workload", FIXTURE_CELL, "--seed", str(SEED), "--seconds",
             "1", "--trace", "0", "--rehearse"]) == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert set(result["metrics"]) == {"placements_per_s",
                                      "job_commit_p50_ms", "setup_s"}
    assert list(result["checks"]) == [
        "failed_jobs", "placement_mismatch", "oversubscribed_nodes",
        "constraint_violations", "distinct_violations", "late_commits",
        "readback_mismatch", "resident_lost", "redeliveries", "score_gap",
        "score_regret", "sampled_jobs", "plans", "picks"]
    assert result["checks"]["resident_lost"] == {"value": 0, "limit": 0}
