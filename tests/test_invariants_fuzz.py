"""Randomized invariant tests for the device-backed scheduler.

Deterministically-seeded random fleets and jobs run through the full
jax-binpack path (host/native executors, rounds or scan mode, network
assignment) and every committed plan is checked against the hard
invariants the reference guarantees: exact resource fit, per-node port
uniqueness, bandwidth bounds, distinct_hosts, and conservation of
requested placements.  This is the property-test net under the
fast paths (template construction, C bulk finish, rounds mode).
"""
from __future__ import annotations

import numpy as np
import pytest

import nomad_tpu.mock as mock
from nomad_tpu.scheduler import Harness
from nomad_tpu.structs import (
    CONSTRAINT_DISTINCT_HOSTS,
    EVAL_TRIGGER_JOB_REGISTER,
    Constraint,
    Evaluation,
    NetworkResource,
    Resources,
    Task,
    TaskGroup,
    allocs_fit,
    generate_uuid,
)


def make_eval(job):
    return Evaluation(id=generate_uuid(), priority=job.priority,
                      type=job.type or "service",
                      triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                      job_id=job.id)


def random_fleet(rng, n):
    nodes = []
    for i in range(n):
        node = mock.node(i)
        node.resources.cpu = int(rng.integers(500, 6000))
        node.resources.memory_mb = int(rng.integers(512, 16384))
        if rng.random() < 0.1:
            node.attributes["kernel.name"] = "windows"
        if rng.random() < 0.05:
            node.drain = True
        nodes.append(node)
    return nodes


def random_job(rng, tag):
    job = mock.job()
    job.id = f"fuzz-{tag}"
    job.name = job.id
    job.type = "service" if rng.random() < 0.7 else "batch"
    groups = []
    for g in range(int(rng.integers(1, 5))):
        n_ports = int(rng.integers(0, 3))
        networks = []
        if n_ports or rng.random() < 0.5:
            networks = [NetworkResource(
                mbits=int(rng.integers(1, 120)),
                dynamic_ports=[f"p{j}" for j in range(n_ports)])]
        res = Resources(
            cpu=int(rng.integers(20, 900)) *
            (100 if rng.random() < 0.05 else 1),  # occasional giant ask
            memory_mb=int(rng.integers(16, 1200)),
            networks=networks)
        constraints = []
        if rng.random() < 0.25:
            constraints.append(Constraint(
                hard=True, operand=CONSTRAINT_DISTINCT_HOSTS))
        groups.append(TaskGroup(
            name=f"tg-{g}", count=int(rng.integers(1, 14)),
            constraints=constraints,
            tasks=[Task(name="t0", driver="exec", resources=res)]))
    job.task_groups = groups
    return job


def check_invariants(h: Harness, nodes, jobs, conservation=True):
    by_id = {n.id: n for n in nodes}
    state_allocs = [a for a in h.state.allocs()
                    if not a.terminal_status()]
    per_node: dict = {}
    for a in state_allocs:
        per_node.setdefault(a.node_id, []).append(a)

    for node_id, allocs in per_node.items():
        node = by_id[node_id]
        # 1. Exact fit, every dimension, via the golden scalar math.
        fit, dim, _ = allocs_fit(node, allocs)
        assert fit, f"node {node_id} oversubscribed on {dim}"
        # 2. Port uniqueness + bandwidth bound per node.
        ports: list = []
        bw = 0
        for a in allocs:
            for tr in a.task_resources.values():
                for net in tr.networks:
                    ports.extend(net.reserved_ports)
                    bw += net.mbits
        assert len(ports) == len(set(ports)), f"port clash on {node_id}"
        cap = sum(n.mbits for n in node.resources.networks if n.device)
        reserved_bw = sum(
            n.mbits for n in (node.reserved.networks
                              if node.reserved else []))
        assert bw + reserved_bw <= cap, f"bandwidth blown on {node_id}"
        # 3. Never placed on drained/incompatible nodes.
        assert not node.drain, f"placed on drained node {node_id}"
        assert node.attributes.get("kernel.name") == "linux"

    # 4. distinct_hosts: the constraint gates the CONSTRAINED group's
    # placements at placement time (same as the sequential chain), so the
    # state-level guarantee is that a constrained group's own copies
    # never share a node (an unconstrained sibling group may still join
    # the node afterwards).
    for job in jobs:
        for tg in job.task_groups:
            if not any(c.operand == CONSTRAINT_DISTINCT_HOSTS
                       for c in tg.constraints + job.constraints):
                continue
            seen: set = set()
            for a in state_allocs:
                if a.job_id == job.id and a.task_group == tg.name:
                    assert a.node_id not in seen, \
                        f"distinct_hosts violated for {job.id}/{tg.name}"
                    seen.add(a.node_id)

    # 5. Conservation: every requested instance is placed, failed, or
    # coalesced onto a failed alloc.  (Skipped for optimistic-conflict
    # rigs where retries submit several plans per job — state-level
    # conservation is asserted by the caller instead.)
    if not conservation:
        return
    for job, plan in zip(jobs, h.plans):
        requested = sum(tg.count for tg in job.task_groups)
        placed = sum(len(v) for v in plan.node_allocation.values())
        failed = len(plan.failed_allocs)
        coalesced = sum(a.metrics.coalesced_failures
                        for a in plan.failed_allocs)
        assert placed + failed + coalesced == requested, (
            job.id, requested, placed, failed, coalesced)


@pytest.mark.parametrize("seed", [3, 17, 42, 99, 2026])
def test_fuzz_invariants(seed):
    rng = np.random.default_rng(seed)
    h = Harness()
    nodes = random_fleet(rng, int(rng.integers(12, 120)))
    for n in nodes:
        h.state.upsert_node(h.next_index(), n)
    jobs = [random_job(rng, t) for t in range(4)]
    for job in jobs:
        h.state.upsert_job(h.next_index(), job)
        h.process("jax-binpack", make_eval(job))
    assert len(h.plans) == len(jobs)
    check_invariants(h, nodes, jobs)


@pytest.mark.parametrize("seed", [7, 1234])
def test_fuzz_invariants_native_off(seed, monkeypatch):
    """Same invariants with the native path disabled: the pure-Python
    fallback must hold them too."""
    import nomad_tpu.scheduler.jax_binpack as jb

    monkeypatch.setattr(jb, "_native_bulk", lambda: None)
    rng = np.random.default_rng(seed)
    h = Harness()
    nodes = random_fleet(rng, 40)
    for n in nodes:
        h.state.upsert_node(h.next_index(), n)
    jobs = [random_job(rng, t) for t in range(3)]
    for job in jobs:
        h.state.upsert_job(h.next_index(), job)
        h.process("jax-binpack", make_eval(job))
    check_invariants(h, nodes, jobs)


@pytest.mark.parametrize("seed", [5, 58])
def test_fuzz_invariants_fused_mesh_storm(seed, monkeypatch):
    """The fused BatchEvalRunner with the device executor forced, so
    the dispatch rides the runtime-selected mesh on the 8-device test
    host (parallel/mesh.py dispatch_mesh).  Lanes plan optimistically
    against one snapshot; a plan-applier-semantics planner serializes
    commits (partial accept + refresh), and the hard invariants must
    hold on the committed state — the multi-chip storm path gets the
    same property net as the single-eval paths."""
    from nomad_tpu.scheduler.batch import BatchEvalRunner
    from nomad_tpu.scheduler.harness import VerifyingPlanner
    from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler

    monkeypatch.setattr(JaxBinPackScheduler, "HOST_SINGLE_SHOT_COST", 0)
    monkeypatch.setattr(JaxBinPackScheduler, "HOST_ALWAYS_COST", 0)
    rng = np.random.default_rng(seed)
    h = Harness()
    h.planner = VerifyingPlanner(h)
    nodes = random_fleet(rng, int(rng.integers(16, 80)))
    for n in nodes:
        h.state.upsert_node(h.next_index(), n)
    jobs = [random_job(rng, t) for t in range(4)]
    for job in jobs:
        h.state.upsert_job(h.next_index(), job)
    runner = BatchEvalRunner(h.state.snapshot(), h.planner)
    runner.process([make_eval(j) for j in jobs])
    check_invariants(h, nodes, jobs, conservation=False)
    # State-level conservation: per job, committed non-terminal
    # placements never exceed the request, and everything requested is
    # accounted placed or failed/coalesced.
    for job in jobs:
        requested = sum(tg.count for tg in job.task_groups)
        allocs = h.state.allocs_by_job(job.id)
        placed = len([a for a in allocs
                      if a.node_id and not a.terminal_status()])
        failed = [a for a in allocs if a.desired_status == "failed"]
        coalesced = sum(a.metrics.coalesced_failures for a in failed)
        assert placed <= requested, (job.id, placed, requested)
        assert placed + len(failed) + coalesced >= requested, (
            job.id, placed, len(failed), coalesced, requested)
