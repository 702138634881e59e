"""The engine choice under the default policy, pinned as it stands.

``auto`` sends a dispatch to the numpy twin while lanes x steps x nodes
stays within ``HOST_SINGLE_SHOT_COST`` (2^25), and a fused window counts
the PADDED slot axis its kernel scans (``g_pad``, at least 8).  So at
the 131,072 nodes of ``benchmarks/configs/fleet131k.json`` a window of
single-group lanes leaves the twin above 32 lanes, and no window of the
other three cells' shapes (at most 64 lanes of at most 10,000 nodes)
ever does.  At the 100,000 nodes of ``benchmarks/configs/fleet100k.json``
a window leaves the twin at 42 lanes, whatever its lanes' REAL slots
(the three of a ``fleet100k.stacks`` job sit on the same ``g_pad`` 8),
and a lone three-slot re-plan, which counts its real slots, stays.
Whoever moves the break-even (ROADMAP D2) moves these numbers with it,
and ``fleet131k.storm``, ``fleet100k.stacks`` and ``baseline4-10k.small``
are the cells that show what it did.

Driven through the fused runner itself, on real fleets of the cells'
widths and the upstream mock job, one chip's layout (no mesh); the lane
spans say which engine ran and on what estimate.
"""
from __future__ import annotations

import json
import os

import pytest

from nomad_tpu import mock
from nomad_tpu.obs import trace
from nomad_tpu.parallel.mesh import mesh_override
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.batch import BatchEvalRunner
from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler
from nomad_tpu.structs import Evaluation, generate_uuid

G_PAD = 8   # models/fleet._pad_to(1): one slot on the padded axis

_fleets: dict = {}


def _fleet(n_nodes: int) -> Harness:
    """One harness per width for the module (131,072 nodes take 5 s).
    The harness commits every plan unverified; a later window of the
    same width plans on that and minds nothing of it."""
    if n_nodes not in _fleets:
        h = Harness()
        for i in range(n_nodes):
            h.state.upsert_node(h.next_index(), mock.node(i))
        _fleets[n_nodes] = h
    return _fleets[n_nodes]


def _stack_job():
    """A ``fleet100k.stacks`` job: the three tiers of
    benchmarks/traffic/stacks64.json, three asks that do not dedupe."""
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "traffic",
            "stacks64.json")) as fh:
        tiers = json.load(fh)["job"]["tiers"]
    job = mock.job()
    first = job.task_groups[0]
    job.task_groups = []
    for tier in tiers:
        tg = first.copy()
        tg.name, tg.count = tier["name"], tier["count"]
        res = tg.tasks[0].resources
        res.cpu, res.memory_mb = tier["cpu"], tier["memory_mb"]
        res.networks[0].mbits = tier["mbits"]
        res.networks[0].dynamic_ports = list(tier["dynamic_ports"])
        job.task_groups.append(tg)
    assert len(tiers) == 3
    return job


def _registered(h: Harness, job) -> Evaluation:
    h.state.upsert_job(h.next_index(), job)
    return Evaluation(
        id=generate_uuid(), priority=job.priority, type="service",
        triggered_by="job-register", job_id=job.id, status="pending")


def _window(n_nodes: int, lanes: int, groups: int = 1, count: int = 10,
            stack: bool = False):
    """(runner's mix, lane tags) of one fused window of ``lanes`` fresh
    jobs of ``groups`` same-ask groups x ``count`` copies, or of
    ``lanes`` three-tier stacks."""
    h = _fleet(n_nodes)
    evals = []
    for _ in range(lanes):
        job = _stack_job() if stack else mock.job()
        if not stack:
            first = job.task_groups[0]
            first.count = count
            job.task_groups = [first] + [
                first.copy() for _ in range(groups - 1)]
            for g, tg in enumerate(job.task_groups):
                tg.name = f"tg-{g}"
        evals.append(_registered(h, job))
    runner = BatchEvalRunner(h.state.snapshot(), h)
    with mesh_override("off"), trace.tracing(seed=33) as tracer:
        runner.process(evals)
        tags = [s["tags"] for s in tracer.snapshot()
                if s["name"] == "sched.dispatch"]
    h.plans.clear()
    return runner.stats(), tags


@pytest.mark.parametrize("lanes, engine", [
    (1, "host"), (32, "host"), (33, "device"), (64, "device")])
def test_at_131072_nodes_a_window_over_32_lanes_leaves_the_twin(lanes,
                                                                engine):
    mix, tags = _window(131072, lanes)
    cost = lanes * G_PAD * 131072
    assert (cost > JaxBinPackScheduler.HOST_SINGLE_SHOT_COST) == \
        (engine == "device")
    assert len(tags) == lanes
    assert {(t["engine"], t["cost"], t["lanes"], t["mode"], t["rounds"])
            for t in tags} == {(engine, cost, lanes, "rounds", 1)}
    if engine == "device":
        assert mix["device_dispatches"] == 1 and mix["host_dispatches"] == 0
        assert mix["device_lanes"] == lanes and mix["host_lanes"] == 0
    else:
        assert mix["host_dispatches"] == lanes == mix["host_lanes"]
        assert mix["device_dispatches"] == 0 == mix["device_lanes"]


@pytest.mark.parametrize("lanes, engine", [(41, "host"), (42, "device")])
def test_at_100000_nodes_a_window_of_stacks_leaves_the_twin_at_42_lanes(
        lanes, engine):
    mix, tags = _window(100000, lanes, stack=True)
    cost = lanes * G_PAD * 100000
    assert (cost > JaxBinPackScheduler.HOST_SINGLE_SHOT_COST) == \
        (engine == "device")
    assert len(tags) == lanes
    # Three real slots a lane, and the estimate counts the padded eight.
    assert {(t["engine"], t["cost"], t["lanes"], t["slots"], t["rounds"])
            for t in tags} == {(engine, cost, lanes, 3, 1)}
    assert mix["slots"] == 3 * lanes
    if engine == "device":
        assert mix["device_dispatches"] == 1 and mix["host_lanes"] == 0
        assert mix["padded_slots"] == 64 * G_PAD    # the lane bucket's
    else:
        assert mix["host_dispatches"] == lanes == mix["host_lanes"]
        assert mix["device_dispatches"] == 0 == mix["device_lanes"]
        assert mix["padded_slots"] == lanes * G_PAD


def test_at_100000_nodes_a_lone_three_slot_replan_stays_on_the_twin():
    """A one-by-one re-plan counts its REAL slots: 3 x 100,000."""
    h = _fleet(100000)
    ev = _registered(h, _stack_job())
    runner = BatchEvalRunner(h.state.snapshot(), h)
    with mesh_override("off"), trace.tracing(seed=35) as tracer:
        ev.trace = tracer.anchor("eval.created", eval_id=ev.id)
        runner._retry_sequential(runner.state, ev)
        (retry,) = [s["tags"] for s in tracer.snapshot()
                    if s["name"] == "sched.retry"]
    h.plans.clear()
    assert JaxBinPackScheduler.host_wins(3 * 100000)
    assert (retry["host_calls"], retry["device_calls"]) == (1, 0)
    assert retry["twin_slots"] == 3 and retry["twin_s"] > 0.0
    mix = runner.stats()
    assert (mix["host_dispatches"], mix["device_dispatches"]) == (1, 0)
    assert (mix["slots"], mix["padded_slots"]) == (3, G_PAD)


@pytest.mark.parametrize("n_nodes, lanes, groups, count", [
    (10000, 32, 1, 10),     # baseline4-10k.small: every client in a batch
    (10000, 64, 1, 10),     # ... and the most the runner fuses
    (4034, 1, 4, 94),       # alibaba2018-4k.dagbatch: a lone job, one slot
    (5000, 8, 1, 1000),     # c1m-5k.jobs1000: 6.7-7 lanes a window
    (5000, 16, 1, 1000),    # ... and every client at once
])
def test_the_other_cells_windows_stay_on_the_twin(n_nodes, lanes, groups,
                                                  count):
    mix, tags = _window(n_nodes, lanes, groups, count)
    assert {t["engine"] for t in tags} == {"host"}
    assert {t["cost"] for t in tags} == {lanes * G_PAD * n_nodes}
    assert mix["device_dispatches"] == 0 == mix["device_lanes"]
    assert mix["host_lanes"] >= lanes


def test_the_one_comparison():
    """``host_wins`` is what both sites read: the twin keeps a cost up
    to and including the threshold; a pipelined caller (its round trip
    hidden behind host work) gives the twin only the small one."""
    always = JaxBinPackScheduler.HOST_ALWAYS_COST
    single = JaxBinPackScheduler.HOST_SINGLE_SHOT_COST
    assert (always, single) == (1 << 18, 1 << 25)
    assert JaxBinPackScheduler.host_wins(single)
    assert not JaxBinPackScheduler.host_wins(single + 1)
    assert JaxBinPackScheduler.host_wins(always, pipelined=True)
    assert not JaxBinPackScheduler.host_wins(always + 1, pipelined=True)
    # A lone single-group eval at 131,072 nodes counts its one real slot.
    assert JaxBinPackScheduler.host_wins(1 * 1 * 131072, pipelined=True)
