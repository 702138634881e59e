"""Batch DAG jobs on machines of the Alibaba cluster-trace-v2018 shape
(benchmarks/configs/alibaba2018-4k.json: 96 cores as cpu 9600, memory
normalised to 100000, nothing reserved) at a size the CPU holds: 128
nodes and a table of eight ``batch`` jobs of 1-16 task groups, each
group with its own width and ask and no network.  The table is made up
for this test (the benchmark's cell offers the trace's mean job: groups
that share one ask and dedupe to one slot); what it holds is that no
two groups of a job share an ask, so every group keeps a kernel slot.

Three engines commit the same counts and fit every node: (a) the fused
runner on the numpy twin, (b) the fused runner with the XLA kernels
forced, (c) the sequential ``batch`` scheduler (scheduler/generic.py).
(a) and (b) are also held, pick by pick, to a BestFit written here in
float64 that shares no code with either.  One more case gives a slot
more copies than nodes, so that it needs a second round.
"""
from __future__ import annotations

import random
import uuid

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.obs import trace
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.batch import BatchEvalRunner
from nomad_tpu.scheduler.executor import executor_override
from nomad_tpu.scheduler.harness import VerifyingPlanner
from nomad_tpu.scheduler.pipeline import PROBE_SCORE_ATOL
from nomad_tpu.structs import (EVAL_TRIGGER_JOB_REGISTER, Evaluation,
                               Resources, Task, TaskGroup, allocs_fit,
                               generate_uuid)

N_NODES = 128
NODE_CPU, NODE_MEM = 9600, 100000
BATCH_PENALTY = 5.0
# (copies, cpu, memory) of each task group; cpu 100 = one core, memory
# in 0.001% of a machine.
TABLE = [
    [(50, 100, 390)],
    [(100, 200, 300)],
    [(100, 100, 300), (20, 50, 390)],
    [(100, 50, 200), (10, 100, 780), (5, 200, 500)],
    [(100, 100, 300), (20, 100, 780), (10, 50, 390), (5, 100, 1000)],
    [(100, 100, 200), (20, 50, 500), (10, 100, 300), (5, 100, 500),
     (2, 50, 780), (2, 50, 390)],
    [(100, 100, 300), (50, 200, 200), (10, 200, 390), (5, 50, 300),
     (2, 50, 500), (2, 50, 780), (1, 100, 500), (1, 100, 1000),
     (1, 100, 1560), (1, 100, 780)],
    [(100, 100, 1000), (50, 100, 200), (10, 100, 300), (5, 200, 500),
     (2, 100, 780), (2, 50, 300), (1, 100, 1560), (1, 100, 500),
     (1, 100, 390), (1, 50, 780), (1, 50, 500), (1, 200, 780),
     (1, 400, 500), (1, 50, 390), (1, 50, 200), (1, 50, 1560)],
]
SLOTS = sum(len(groups) for groups in TABLE)
COPIES = sum(n for groups in TABLE for n, _cpu, _mem in groups)


def _spec(rng: random.Random, name: str, groups: list) -> dict:
    return {"id": str(uuid.UUID(int=rng.getrandbits(128), version=4)),
            "name": name, "type": "batch",
            "groups": [{"name": f"t{g:02d}", "count": n, "cpu": cpu,
                        "memory_mb": mem}
                       for g, (n, cpu, mem) in enumerate(groups)],
            "asked": sum(n for n, _cpu, _mem in groups)}


def _job(spec: dict):
    job = mock.job()
    job.id, job.name, job.type = spec["id"], spec["name"], spec["type"]
    job.task_groups = [TaskGroup(
        name=g["name"], count=g["count"],
        tasks=[Task(name="web", driver="exec", resources=Resources(
            cpu=g["cpu"], memory_mb=g["memory_mb"]))])
        for g in spec["groups"]]
    return job


def _cluster(seed: int, rounds: tuple = (0,), widest: int = 0):
    """(harness with the fleet and the table's jobs registered once per
    round, their plain specs by round, the fleet's ids and capacity).
    ``widest`` overrides the copies of the 16-group job's first group."""
    rng = random.Random(seed)
    h = Harness()
    ids = []
    for i in range(N_NODES):
        node = mock.node(i)
        node.resources.cpu, node.resources.memory_mb = NODE_CPU, NODE_MEM
        node.reserved = Resources()
        h.state.upsert_node(h.next_index(), node)
        ids.append(node.id)
    fleet = {"ids": ids, "capacity": np.tile(
        np.asarray([NODE_CPU, NODE_MEM], dtype=np.float64), (N_NODES, 1))}
    table = [list(groups) for groups in TABLE]
    if widest:
        table[-1][0] = (widest,) + table[-1][0][1:]
    specs = {}
    for b in rounds:
        specs[b] = [_spec(rng, f"dag-{b}-{j}", groups)
                    for j, groups in enumerate(table)]
        for spec in specs[b]:
            h.state.upsert_job(h.next_index(), _job(spec))
    return h, specs, fleet


def _eval(spec: dict) -> Evaluation:
    return Evaluation(id=generate_uuid(), priority=50, type=spec["type"],
                      triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                      job_id=spec["id"])


def _running(allocs) -> list:
    return [a for a in allocs if a.node_id and not a.terminal_status()]


def _committed(h, specs: list) -> dict:
    return {s["id"]: len(_running(h.state.allocs_by_job(s["id"])))
            for s in specs}


def _assert_every_node_fits(h) -> None:
    for node in h.state.nodes():
        allocs = _running(h.state.allocs_by_node(node.id))
        fit, dim, _used = allocs_fit(node, allocs)
        assert fit, (node.name, dim)


def _usage(h, fleet: dict) -> np.ndarray:
    """Committed [n, 2] cpu / memory by node row, float64."""
    row = {nid: i for i, nid in enumerate(fleet["ids"])}
    used = np.zeros((N_NODES, 2), dtype=np.float64)
    for a in _running(h.state.allocs()):
        used[row[a.node_id]] += (a.resources.cpu, a.resources.memory_mb)
    return used


def _best_fit64(fleet: dict, usage, job_counts, cpu: float, mem: float):
    """BestFit v3 of one ask on every node, float64, with the batch
    anti-affinity penalty; nodes it does not fit score -inf."""
    cap = fleet["capacity"]
    util = usage + (cpu, mem)
    score = np.clip(20.0 - (10.0 ** (1.0 - util[:, 0] / cap[:, 0])
                            + 10.0 ** (1.0 - util[:, 1] / cap[:, 1])),
                    0.0, 18.0) - BATCH_PENALTY * job_counts
    return np.where((util <= cap).all(axis=1), score, -np.inf)


def _assert_first_plans_are_best_fit(h, specs: list, fleet: dict,
                                     usage0: np.ndarray) -> int:
    """Every lane of the first fused window planned on ``usage0``: hold
    its plan (the first one the harness recorded for the job) to the
    float64 BestFit, slot by slot in job order, pick by pick.

    Tolerance ``PROBE_SCORE_ATOL`` (1e-3), the one ``check_*_host``
    holds the two engines to: the kernels score in float32, whose two
    10^x terms sit ~2e-6 off float64 here (5e-5 on a TPU), while a
    wrong node costs a whole step of the packing score or a penalty of
    5 — so a pick may differ from float64's only among nodes tied
    within the tolerance, and its recorded score may not differ more."""
    row = {nid: i for i, nid in enumerate(fleet["ids"])}
    first_plan = {}
    for plan in h.plans:
        placed = [a for allocs in plan.node_allocation.values()
                  for a in allocs]
        if placed:
            first_plan.setdefault(placed[0].job_id, plan)
    picks = 0
    for spec in specs:
        plan = first_plan[spec["id"]]
        by_group: dict = {}
        for allocs in plan.node_allocation.values():
            for a in allocs:
                by_group.setdefault(a.task_group, []).append(a)
        assert not plan.failed_allocs
        usage, jc = usage0.copy(), np.zeros(N_NODES)
        for g in spec["groups"]:
            placed = by_group[g["name"]]
            assert len(placed) == g["count"]
            nodes = np.asarray([row[a.node_id] for a in placed])
            assert len(set(nodes.tolist())) == len(nodes)   # one a node
            want = _best_fit64(fleet, usage, jc, g["cpu"], g["memory_mb"])
            kth = np.sort(want)[-len(nodes)]
            assert (want[nodes] >= kth - PROBE_SCORE_ATOL).all(), g
            recorded = np.asarray(
                [next(iter(a.metrics.scores.values())) for a in placed])
            assert np.abs(recorded - want[nodes]).max() < PROBE_SCORE_ATOL
            np.add.at(usage, nodes, (g["cpu"], g["memory_mb"]))
            np.add.at(jc, nodes, 1.0)
            picks += len(nodes)
    return picks


def _run_fused(executor: str, seed: int):
    """Round 0 of the table fills the fleet a little (sequential
    scheduler, so the snapshot the window plans on is a used one),
    round 1 is the fused window under the executor."""
    h, specs, fleet = _cluster(seed, rounds=(0, 1))
    for spec in specs[0]:
        h.process("batch", _eval(spec))
    h.plans.clear()
    usage0 = _usage(h, fleet)
    assert usage0.sum() > 0
    h.planner = VerifyingPlanner(h)
    runner = BatchEvalRunner(h.state.snapshot(), h,
                             state_refresh=h.snapshot)
    with executor_override(executor), trace.tracing(seed=28) as tracer:
        runner.process([_eval(s) for s in specs[1]])
        lanes = [s["tags"] for s in tracer.snapshot()
                 if s["name"] == "sched.dispatch"]
    return h, specs[1], fleet, usage0, runner, lanes


@pytest.mark.parametrize("executor, engine", [("host", "host_dispatches"),
                                              ("device",
                                               "device_dispatches")])
def test_fused_runner_places_the_table_as_float64_best_fit(executor,
                                                           engine):
    h, specs, fleet, usage0, runner, lanes = _run_fused(executor,
                                                        seed=2018)
    asked = {s["id"]: s["asked"] for s in specs}
    assert sum(asked.values()) == COPIES and len(specs) == len(TABLE)
    assert _committed(h, specs) == asked
    _assert_every_node_fits(h)
    assert all(e.status == "complete" for e in h.evals)
    assert _assert_first_plans_are_best_fit(h, specs, fleet,
                                            usage0) == COPIES
    mix = runner.stats()
    other = ({"host_dispatches", "device_dispatches"} - {engine}).pop()
    assert mix[engine] >= 1 and mix[other] == 0
    # Every lane's span says which kernel it rode and who ran it: one
    # top-k round each (retried lanes too), never the sequence kernel.
    assert len(lanes) >= len(specs)
    assert {(t["mode"], t["rounds"]) for t in lanes} == {("rounds", 1)}
    # (conftest's eight virtual devices make a mesh: "sharded".)
    assert {t["engine"] for t in lanes} <= (
        {"host"} if executor == "host" else {"device", "sharded"})


def test_sequential_batch_scheduler_commits_the_same_counts():
    h, specs, _fleet = _cluster(2018, rounds=(0, 1))
    for b in (0, 1):
        for spec in specs[b]:
            h.process("batch", _eval(spec))
    both = specs[0] + specs[1]
    assert _committed(h, both) == {s["id"]: s["asked"] for s in both}
    _assert_every_node_fits(h)


def test_a_slot_with_more_copies_than_nodes_takes_a_second_round():
    """The 16-group job with its widest task at 200 copies on 128
    nodes: ``_fit_rounds`` plans that slot over two top-k rounds.  The
    benchmark's plain reference (``reference.check_plan``) scores all
    picks of a slot before it applies any, so it would read the second
    round's picks one batch penalty (5.0) off and cannot score such a
    plan; here it is held against the sequential scheduler instead:
    the same count committed, every node fitting, and no node given a
    third copy while another has one."""
    placed = {}
    for engine in ("fused", "sequential"):
        h, specs, _fleet = _cluster(7, widest=200)
        spec = specs[0][-1]
        assert len(spec["groups"]) == 16
        assert spec["groups"][0]["count"] == 200
        if engine == "fused":
            runner = BatchEvalRunner(h.state.snapshot(), h,
                                     state_refresh=h.snapshot)
            with trace.tracing(seed=28) as tracer:
                runner.process([_eval(spec)])
                lanes = [s["tags"] for s in tracer.snapshot()
                         if s["name"] == "sched.dispatch"]
            # One lane of 16 real slots (no two groups share an ask; a
            # padded axis of 16), two rounds, 128 nodes: the estimate
            # the choice was made on; beside it what the runner's thread
            # computed and chose to wait over the stage.
            assert all(lane.pop("cpu_s") >= 0.0 and
                       lane.pop("blocked_s") >= 0.0 for lane in lanes)
            # ... and the rows the twin scored: 15 slots of one round
            # and one of two, on a fleet too small for a candidate set.
            assert all(lane.pop("twin_rows") == lane.pop("twin_rows_full")
                       == 17 * N_NODES for lane in lanes)
            assert lanes == [{
                "eval_id": lanes[0]["eval_id"], "host": True,
                "mode": "rounds", "rounds": 2, "engine": "host",
                "cost": 1 * 2 * 16 * N_NODES, "lanes": 1, "slots": 16}]
        else:
            h.process("batch", _eval(spec))
        _assert_every_node_fits(h)
        allocs = _running(h.state.allocs_by_job(spec["id"]))
        per_node: dict = {}
        for a in allocs:
            if a.task_group == "t00":
                per_node[a.node_id] = per_node.get(a.node_id, 0) + 1
        placed[engine] = (len(allocs), sum(per_node.values()),
                          max(per_node.values()))
    assert placed["fused"] == (spec["asked"], 200, 2)
    assert placed["sequential"][:2] == (spec["asked"], 200)
