"""Node classes, constraint stanzas and ``distinct_hosts`` in the
yardstick: the fleet a configuration without classes gets is the one it
always got, classes hold their shares in every prefix, the plain
reference judges constraints as the program's own predicates do, slots
dedupe as the program's do, and the comparison catches a pick on a row
its constraints exclude and a ``distinct_hosts`` repeat.  The fixture
(``fixtures/``) is a test's, not a deployment."""
import io
import json
import os
import random
import uuid
from contextlib import redirect_stderr, redirect_stdout

import numpy as np
import pytest

import check
import reference
import run as bench_run
import served
from conftest import BENCH, FIXTURE_CELL, FIXTURES, fixture_cell

def load(path):
    with open(path) as fh:
        return json.load(fh)


# The configurations of one machine class: a file of ``configs/`` may
# state ``node_classes`` (and resident work) too.
CONFIGS = sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "configs"))
    if "node_classes" not in load(os.path.join(BENCH, "configs", f)))


def parent_make_fleet(config: dict, seed: int, n_nodes: int) -> dict:
    """``reference.make_fleet`` as it stood before node classes (PR 38)."""
    rng = random.Random(f"{seed}:fleet")
    shape = config["node"]
    res = shape["reserved"]
    cap = [shape["cpu"], shape["memory_mb"], shape["disk_mb"],
           shape["iops"], shape["mbits"], reference.PORT_SLOTS]
    rsv = [res.get("cpu", 0), res.get("memory_mb", 0),
           res.get("disk_mb", 0), res.get("iops", 0), res.get("mbits", 0),
           float(len(res.get("ports", [])))]
    return {
        "ids": [str(uuid.UUID(int=rng.getrandbits(128), version=4))
                for _ in range(n_nodes)],
        "capacity": np.tile(np.asarray(cap, dtype=np.float64), (n_nodes, 1)),
        "reserved": np.tile(np.asarray(rsv, dtype=np.float64), (n_nodes, 1)),
        "reserved_ports": list(res.get("ports", [])),
    }


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 40, 987654321])
@pytest.mark.parametrize("name", CONFIGS)
def test_a_fleet_without_classes_is_the_one_it_always_was(name, seed):
    config = load(os.path.join(BENCH, "configs", f"{name}.json"))
    assert "node_classes" not in config
    n = int(config["nodes"])
    got, want = reference.make_fleet(config, seed, n), \
        parent_make_fleet(config, seed, n)
    assert got["ids"] == want["ids"]
    for key in ("capacity", "reserved"):
        assert got[key].dtype == want[key].dtype
        assert np.array_equal(got[key], want[key])
    assert not got["class_of"].any()
    assert got["class_reserved_ports"] == [want["reserved_ports"]]
    node = served.program_node(got, n - 1)
    assert (node.datacenter, node.node_class, node.attributes) == \
        ("dc1", "linux-medium", reference.MOCK_ATTRIBUTES)


@pytest.mark.parametrize("n", [512, 1024, 4034, 100000])
@pytest.mark.parametrize("shares", [[5, 3, 2], [1, 1, 1], [7, 2, 1],
                                    [3, 3], [1, 9, 4, 2]])
def test_every_prefix_holds_each_class_at_its_share(shares, n):
    class_of = reference.class_rows(shares, 2 ** 31 + 40, n)
    prefix = np.arange(1, n + 1)[:, None]
    held = np.cumsum(class_of[:, None] == np.arange(len(shares)), axis=0)
    owed = prefix * np.asarray(shares) / sum(shares)
    assert np.abs(held - owed).max() <= 1.0


def test_the_fixture_fleet_is_three_classes():
    config = load(os.path.join(FIXTURES, "classes3.json"))
    fleet = reference.make_fleet(config, 40, 512)
    assert np.bincount(fleet["class_of"]).tolist() == [256, 154, 102]
    big = fleet["class_of"] == 2
    assert (fleet["capacity"][big, 0] == 16000).all()
    assert (fleet["capacity"][~big, 0] == 4000).all()
    rows = [int(np.nonzero(fleet["class_of"] == k)[0][0]) for k in range(3)]
    nodes = [served.program_node(fleet, i) for i in rows]
    assert [n.datacenter for n in nodes] == ["dc1", "dc2", "dc1"]
    assert [n.attributes["arch"] for n in nodes] == ["x86", "x86", "arm64"]
    assert nodes[1].meta == {"team": "storage"}
    assert nodes[1].reserved.networks[0].reserved_ports == [22, 20080]
    # The second class reserves a dynamic port: handed out there, it
    # collides; on the first class it does not.
    allocs = {"node": np.asarray(rows[:2]), "vec": np.zeros((2, 6)),
              "ports": [[20080], [20080]]}
    assert reference.oversubscribed_nodes(fleet, allocs) == (1, [rows[1]])


# ---------------------------------------------------------------------------
# constraints: the reference against the program's own predicates
# ---------------------------------------------------------------------------

TARGETS = ["$node.id", "$node.datacenter", "$node.name", "$attr.arch",
           "$attr.version", "$attr.kernel.version", "$attr.missing",
           "$meta.team", "$meta.missing", "$node.class", "x86"]
RIGHT = {"=": ["x86", "dc2", "arm64"], "==": ["dc1"], "is": ["arm64"],
         "!=": ["x86"], "not": ["dc2"], "<": ["5", "node-3"],
         "<=": ["x86"], ">": ["4", "dc1"], ">=": ["5"],
         "version": [">= 0.1.0, < 0.2", "~> 0.2", "= 0.1.5", "> 0.1",
                     "!= 0.2.1", "~> 0.1.0", "<= 0.1.5", "bogus",
                     ">= 0.1.0,<0.3"],
         "regexp": ["^x", "arm", "[02468]$", "^(dc|node)-?1", "("],
         "bogus": ["x86"]}
# A node id is a uuid, which go-version (and the program) reads as a
# pre-release version where it starts with a digit; the reference refuses
# pre-releases, so ``version`` is not asked of ``$node.id``.
CASES = [(left, op, right) for left in TARGETS
         for op, rights in RIGHT.items() for right in rights
         if (left, op) != ("$node.id", "version")]


def test_the_reference_judges_constraints_as_the_program_does():
    from nomad_tpu.models.constraints import _MaskCtx
    from nomad_tpu.utils.predicates import (check_constraint_values,
                                            resolve_constraint_target)

    config = load(os.path.join(FIXTURES, "classes3.json"))
    fleet = reference.make_fleet(config, 40, 24)
    nodes = [served.program_node(fleet, i) for i in range(24)]
    ctx = _MaskCtx()
    wrong = []
    meets = 0
    for triple in CASES:
        for i, node in enumerate(nodes):
            left, ok_l = resolve_constraint_target(triple[0], node)
            right, ok_r = resolve_constraint_target(triple[2], node)
            program = ok_l and ok_r and \
                check_constraint_values(ctx, triple[1], left, right)
            cls = fleet["classes"][fleet["class_of"][i]]
            ours = reference.meets(fleet, triple, cls, i)
            meets += ours
            if ours != program:
                wrong.append((triple, i))
    assert not wrong, wrong[:10]
    assert 0.1 < meets / (len(CASES) * len(nodes)) < 0.9


def test_every_group_mask_is_the_one_the_program_compiles():
    """``reference.feasible`` against ``compile_group_mask`` over the
    program's own ``FleetStatics`` of the fixture's nodes: datacenters,
    the exec driver and each stanza, on every row."""
    from nomad_tpu.models.constraints import compile_group_mask
    from nomad_tpu.models.fleet import build_fleet
    from nomad_tpu.scheduler.util import task_group_constraints

    config = load(os.path.join(FIXTURES, "classes3.json"))
    fleet = reference.make_fleet(config, 40, 300)
    statics = build_fleet([served.program_node(fleet, i)
                           for i in range(300)])
    spec = fixture_job()
    job = served.program_job(spec)
    sizes = []
    for group, tg in zip(spec["groups"], job.task_groups):
        tc = task_group_constraints(tg)
        mask, distinct = compile_group_mask(
            statics, job.datacenters, job.constraints, tc.constraints,
            tc.drivers)
        ours = reference.feasible(fleet, spec, group)
        assert np.array_equal(mask[:300], ours), group["name"]
        assert distinct is reference.group_constraints(spec, group)[1]
        sizes.append(int(ours.sum()))
    assert 0 < min(sizes) and max(sizes) < 300
    # A datacenter no node is in, and a driver no class runs, leave none.
    assert not reference.feasible(fleet, dict(spec, datacenters=["dc9"]),
                                  spec["groups"][0]).any()
    fleet["classes"] = [dict(c, attributes=dict(c["attributes"],
                                                **{"driver.exec": "0"}))
                        for c in fleet["classes"]]
    fleet["masks"] = {}
    assert not reference.feasible(fleet, spec, spec["groups"][0]).any()


@pytest.mark.parametrize("version, spec, want", [
    ("0.1.0", ">= 0.1.0, < 0.2", True), ("0.2.1", "~> 0.2", True),
    ("1.0.0", "~> 0.2", False), ("0.2.1", "~> 0.2.0", True),
    ("0.3.0", "~> 0.2.0", False), ("v1.2", "= 1.2.0", True),
    ("1.2.3", "1.2.3", True), ("1.2.3.4", "~> 1.2.3.0", True),
    ("1", ">=0.9", True), ("1.3", "~> 1.2.3", False),
    ("banana", ">= 0", False), ("1.0", ">= banana", False),
    ("1.0", ">= 1.0, ", False),
])
def test_version_constraints_read_as_go_version_does(version, spec, want):
    assert reference.version_meets(version, spec) is want


@pytest.mark.parametrize("version, spec", [
    ("1.2.3-beta", "< 1.2.3"), ("1.2.3beta", "< 1.2.3"),
    ("1.2.3", "< 1.2.3-beta"), ("1.2.3+build", "= 1.2.3"),
])
def test_a_pre_release_or_build_suffix_is_refused(version, spec):
    with pytest.raises(ValueError):
        reference.version_meets(version, spec)


def program_slots(spec: dict) -> int:
    """How many kernel slots the program's own dedupe key makes of a
    job spec (``jax_binpack._derive_sem``'s general path)."""
    from nomad_tpu.models.constraints import group_mask_key
    from nomad_tpu.scheduler.util import task_group_constraints
    from nomad_tpu.structs import CONSTRAINT_DISTINCT_HOSTS

    job = served.program_job(spec)
    job_dist = any(c.operand == CONSTRAINT_DISTINCT_HOSTS
                   for c in job.constraints)
    keys = set()
    for tg in job.task_groups:
        tc = task_group_constraints(tg)
        dist = job_dist or any(c.operand == CONSTRAINT_DISTINCT_HOSTS
                               for c in tc.constraints)
        keys.add((group_mask_key(job.datacenters, job.constraints,
                                 tc.constraints, tc.drivers),
                  tuple(tc.size.as_vector()), dist))
    return len(keys)


def fixture_job(k: int = 0) -> dict:
    traffic = load(os.path.join(FIXTURES, "constrained10.json"))
    return bench_run.load_module("generators", traffic["generator"]) \
        .job_spec(traffic["job"], 40, 0, k)


def test_slots_dedupe_as_the_program_does():
    spec = fixture_job()
    assert len(spec["groups"]) == 10
    assert len({json.dumps(reference.group_ask(g).tolist())
                for g in spec["groups"]}) == 1
    assert len(reference.job_slots(spec)) == program_slots(spec) == 10
    assert [s[4] for s in reference.job_slots(spec)].count(True) == 4
    # Without stanzas the same ten groups are one slot to both.
    bare = dict(spec, groups=[{k: v for k, v in g.items()
                               if k not in ("constraints", "distinct_hosts")}
                              for g in spec["groups"]])
    bare.pop("constraints")
    assert len(reference.job_slots(bare)) == program_slots(bare) == 1
    # Two groups alike in every stanza share a slot; distinct_hosts splits.
    twin = dict(spec, groups=[spec["groups"][0], dict(spec["groups"][0],
                                                      name="again")])
    assert len(reference.job_slots(twin)) == program_slots(twin) == 1
    twin["groups"][1] = dict(twin["groups"][1], distinct_hosts=True)
    assert len(reference.job_slots(twin)) == program_slots(twin) == 2


# ---------------------------------------------------------------------------
# the comparison catches an infeasible pick and a distinct_hosts repeat
# ---------------------------------------------------------------------------

def synthetic_run(fleet: dict, spec: dict, move=None):
    """One job committed in one plan, planned by the reference itself
    (each slot's best feasible nodes on an empty fleet), as the answers
    ``check.compare`` reads; ``move(slot, picks)`` alters a slot's picks
    where they are produced, their recorded scores kept."""
    n = len(fleet["ids"])
    scorer = reference.Scorer(fleet)
    usage, jc = np.zeros((n, 6)), np.zeros(n)
    slots = reference.job_slots(spec)
    rows = []
    for s, (ask, names, copies, _t, distinct) in enumerate(slots):
        masked, _f = scorer.scores(usage, jc, ask, 10.0,
                                   feasible=reference.slot_masks(
                                       fleet, spec, slots)[s],
                                   distinct=distinct)
        picks = np.argsort(-masked, kind="stable")[:copies]
        scores = masked[picks]
        if move is not None:
            picks = move(s, picks)
        for j, node in enumerate(picks):
            rows.append((names[0], int(node), float(scores[j]), j))
        np.add.at(usage, picks, ask)
        np.add.at(jc, picks, 1.0)
    vec = [reference.group_ask(next(g for g in spec["groups"]
                                    if g["name"] == r[0])) for r in rows]
    ports = [[20000 + i] for i in range(len(rows))]
    allocs = {
        "id": [f"a{i}" for i in range(len(rows))],
        "name": [f"{spec['name']}.{g}[{j}]" for g, _n, _s, j in rows],
        "job": [spec["id"]] * len(rows), "eval": ["e"] * len(rows),
        "group": [r[0] for r in rows],
        "node": np.asarray([r[1] for r in rows], dtype=np.int64),
        "vec": np.asarray(vec, dtype=np.float64),
        "create_index": np.full(len(rows), 10, dtype=np.int64),
        "score": np.asarray([r[2] for r in rows]),
        "running": np.ones(len(rows), dtype=bool), "ports": ports,
    }
    answers = {"allocs": allocs, "nodes_ready": n, "readback_mismatch": 0,
               "jobs": {spec["id"]: {"register_index": 5,
                                     "evals": [("e", "complete", 10)]}}}
    records = [{"spec": spec, "eval": "e", "status": "complete",
                "in_window": True, "t_submit": 0.0, "t_done": 1.0}]
    traffic = {"check": {"sample_jobs": 1}}
    counters = {k: 0 for k in check.REDELIVERY_COUNTERS}
    checks, _info = check.compare(40, traffic, fleet, records, answers,
                                  counters, None, lambda _msg: None)
    return {k: c["value"] for k, c in checks.items()
            if c["value"] > c["limit"]}


@pytest.fixture(scope="module")
def small_fleet():
    config = load(os.path.join(FIXTURES, "classes3.json"))
    return reference.make_fleet(config, 40, 60)


def test_a_plan_the_reference_would_make_is_correct(small_fleet):
    assert synthetic_run(small_fleet, fixture_job()) == {}


def test_a_pick_on_an_excluded_row_is_caught(small_fleet):
    spec = fixture_job()
    slots = reference.job_slots(spec)
    arm_only = next(s for s, slot in enumerate(slots)
                    if slot[1] == ["kernel"])
    x86 = int(np.nonzero(small_fleet["class_of"] == 0)[0][-1])

    def move(s, picks):
        return np.r_[picks[:-1], x86] if s == arm_only else picks

    outside = synthetic_run(small_fleet, spec, move)
    assert outside["constraint_violations"] == 1
    assert {"score_gap", "score_regret"} <= set(outside)


def test_a_distinct_hosts_repeat_is_caught(small_fleet):
    spec = fixture_job()
    slots = reference.job_slots(spec)
    first = next(s for s, slot in enumerate(slots) if slot[4])
    taken = {}

    def move(s, picks):
        taken[s] = picks
        if s == first:
            return np.r_[picks[:1], picks[:1], picks[2:]]
        return picks

    outside = synthetic_run(small_fleet, spec, move)
    assert outside["distinct_violations"] == 1
    assert "constraint_violations" not in outside
    assert "score_gap" in outside


def test_a_later_non_distinct_copy_beside_a_distinct_one_is_sound():
    fleet = reference.make_fleet(
        load(os.path.join(FIXTURES, "classes3.json")), 40, 60)
    x86 = int(np.nonzero(fleet["class_of"] == 0)[0][0])
    allocs = {"node": np.full(3, x86),
              "group": ["x86", "not-arm", "not-arm"],
              "create_index": np.asarray([10, 10, 12])}
    spec = fixture_job()
    # x86 (slot 0) then not-arm (slot 1, distinct) on one node: broken,
    # and once more at a later commit.
    assert reference.placement_violations(
        fleet, {spec["id"]: spec}, allocs, {spec["id"]: [0, 1, 2]})[:2] \
        == (0, 2)
    # not-arm first, then x86 beside it at a later commit: what the
    # program allows (in one plan, x86's slot comes first).
    allocs["group"] = ["not-arm", "x86", "x86"]
    allocs["create_index"] = np.asarray([10, 12, 12])
    assert reference.placement_violations(
        fleet, {spec["id"]: spec}, allocs, {spec["id"]: [0, 1, 2]})[:2] \
        == (0, 0)


# ---------------------------------------------------------------------------
# the fixture on the XLA kernel's windows: the ``distinct`` rows and the
# masks of ``ops/binpack.py`` with a g_pad of 16
# ---------------------------------------------------------------------------

@pytest.fixture
def device_windows(monkeypatch):
    from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler

    monkeypatch.setattr(JaxBinPackScheduler, "HOST_SINGLE_SHOT_COST", 0)
    monkeypatch.setattr(JaxBinPackScheduler, "HOST_ALWAYS_COST", 0)
    monkeypatch.setattr(bench_run, "find_cell", fixture_cell)


@pytest.mark.parametrize("fault, caught_by", [
    (None, None), ("distinct_ignored", "distinct_violations"),
    ("constraints_ignored", "constraint_violations")])
def test_the_fixture_on_the_kernel(device_windows, fault, caught_by):
    from faults import FAULTS

    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        if fault:
            # ``distinct_ignored`` shows once the fleet has filled
            # unevenly: after more jobs than a loaded CPU completes in
            # 2 s (``tests/test_bench_node_classes.py``).
            seconds = "10" if fault == "distinct_ignored" else "2"
            with FAULTS[fault]():
                rc = bench_run.main(
                    ["--workload", FIXTURE_CELL, "--seed", "41",
                     "--seconds", seconds, "--trace", "0", "--rehearse"],
                    rehearsal_is_never_correct=False)
        else:
            rc = bench_run.main(
                ["--workload", FIXTURE_CELL, "--seed", "41", "--seconds",
                 "2", "--trace", "0", "--rehearse"],
                rehearsal_is_never_correct=False)
    assert rc == 0, err.getvalue()[-2000:]
    assert '"host_dispatches": 0' in err.getvalue()
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    outside = {k for k, c in result["checks"].items()
               if c["limit"] is not None and c["value"] > c["limit"]}
    if fault is None:
        assert result["correct"] is True and not outside
    else:
        assert result["correct"] is False and caught_by in outside
