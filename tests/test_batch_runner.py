"""Batched optimistic scheduling: many evals fused into one dispatch."""
from __future__ import annotations

import nomad_tpu.mock as mock
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.batch import BatchEvalRunner
from nomad_tpu.structs import (
    EVAL_TRIGGER_JOB_REGISTER,
    JOB_TYPE_SERVICE,
    Evaluation,
    allocs_fit,
    generate_uuid,
)


def make_eval(job):
    return Evaluation(
        id=generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
    )


def test_batch_runner_schedules_many_jobs():
    h = Harness()
    nodes = [mock.node(i) for i in range(16)]
    for n in nodes:
        h.state.upsert_node(h.next_index(), n)

    jobs = []
    for _ in range(6):
        j = mock.job()
        j.task_groups[0].count = 4
        h.state.upsert_job(h.next_index(), j)
        jobs.append(j)

    runner = BatchEvalRunner(h.state.snapshot(), h)
    runner.process([make_eval(j) for j in jobs])

    assert len(h.plans) == 6
    by_node = {n.id: n for n in nodes}
    for plan, job in zip(h.plans, jobs):
        placed = [a for v in plan.node_allocation.values() for a in v]
        assert len(placed) == 4
        assert all(a.job_id == job.id for a in placed)
        # Anti-affinity spreads each job's allocs.
        assert len(plan.node_allocation) == 4
    # Each eval marked complete.
    assert len(h.evals) == 6
    assert all(e.status == "complete" for e in h.evals)


def test_batch_runner_mixed_service_and_batch():
    h = Harness()
    for i in range(8):
        h.state.upsert_node(h.next_index(), mock.node(i))
    j1 = mock.job()
    j1.task_groups[0].count = 3
    j2 = mock.job()
    j2.type = "batch"
    j2.task_groups[0].count = 3
    for j in (j1, j2):
        h.state.upsert_job(h.next_index(), j)

    runner = BatchEvalRunner(h.state.snapshot(), h)
    runner.process([make_eval(j1), make_eval(j2)])
    assert len(h.plans) == 2
    for plan in h.plans:
        assert sum(len(v) for v in plan.node_allocation.values()) == 3


def test_batch_runner_noop_and_invalid_trigger():
    h = Harness()
    for i in range(4):
        h.state.upsert_node(h.next_index(), mock.node(i))
    job = mock.job()
    h.state.upsert_job(h.next_index(), job)

    good = make_eval(job)
    bad = make_eval(job)
    bad.triggered_by = "bogus-trigger"
    missing_job = make_eval(job)
    missing_job.job_id = "no-such-job"

    runner = BatchEvalRunner(h.state.snapshot(), h)
    runner.process([good, bad, missing_job])

    statuses = {e.id: e.status for e in h.evals}
    assert statuses[good.id] == "complete"
    assert statuses[bad.id] == "failed"
    assert statuses[missing_job.id] == "complete"  # noop plan


def test_batch_runner_plans_all_fit():
    """Fused lanes plan optimistically against the same snapshot; each
    individual plan must still fit on an empty fleet."""
    h = Harness()
    nodes = [mock.node(i) for i in range(4)]
    for n in nodes:
        h.state.upsert_node(h.next_index(), n)
    jobs = []
    for _ in range(3):
        j = mock.job()
        j.task_groups[0].count = 2
        j.task_groups[0].tasks[0].resources.cpu = 1000
        h.state.upsert_job(h.next_index(), j)
        jobs.append(j)

    runner = BatchEvalRunner(h.state.snapshot(), h)
    runner.process([make_eval(j) for j in jobs])

    by_node = {n.id: n for n in nodes}
    for plan in h.plans:
        for node_id, allocs in plan.node_allocation.items():
            fit, dim, _ = allocs_fit(by_node[node_id], allocs)
            assert fit, dim


def test_batch_runner_serializes_same_job_evals():
    """Two evals for the same job in one call must not double-place
    (code-review regression): the second runs against refreshed state."""
    h = Harness()
    for i in range(8):
        h.state.upsert_node(h.next_index(), mock.node(i))
    job = mock.job()
    job.task_groups[0].count = 4
    h.state.upsert_job(h.next_index(), job)

    runner = BatchEvalRunner(h.state.snapshot(), h,
                             state_refresh=lambda: h.state.snapshot())
    runner.process([make_eval(job), make_eval(job)])

    live = [a for a in h.state.allocs_by_job(job.id)
            if not a.terminal_status()]
    assert len(live) == 4, f"expected 4 allocs, got {len(live)}"


def test_batch_runner_same_job_without_refresh_fails_safe():
    h = Harness()
    for i in range(8):
        h.state.upsert_node(h.next_index(), mock.node(i))
    job = mock.job()
    job.task_groups[0].count = 2
    h.state.upsert_job(h.next_index(), job)

    runner = BatchEvalRunner(h.state.snapshot(), h)
    e1, e2 = make_eval(job), make_eval(job)
    runner.process([e1, e2])
    statuses = {e.id: e.status for e in h.evals}
    assert statuses[e1.id] == "complete"
    assert statuses[e2.id] == "failed"
    live = [a for a in h.state.allocs_by_job(job.id)
            if not a.terminal_status()]
    assert len(live) == 2


def test_fused_dispatch_rides_the_mesh_on_multi_device(monkeypatch):
    """On a multi-device host the fused dispatch routes through the
    mesh-sharded kernels (storm layout when the lane count splits), and
    the plans match a single-device run lane for lane."""
    import nomad_tpu.parallel.mesh as mesh_mod

    def build(runner_patch=None):
        h = Harness()
        for i in range(16):
            h.state.upsert_node(h.next_index(), mock.node(i))
        jobs = []
        for _ in range(4):
            j = mock.job()
            j.task_groups[0].count = 4
            h.state.upsert_job(h.next_index(), j)
            jobs.append(j)
        return h, jobs

    # Force the device executor (the tiny fleet would otherwise take
    # the host twins) and record which mesh the dispatch used.
    from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler

    monkeypatch.setattr(JaxBinPackScheduler, "HOST_SINGLE_SHOT_COST", 0)
    monkeypatch.setattr(JaxBinPackScheduler, "HOST_ALWAYS_COST", 0)
    used = []
    orig = mesh_mod.dispatch_mesh

    def spy(n_lanes, n_pad):
        mesh = orig(n_lanes, n_pad)
        used.append(mesh)
        return mesh
    monkeypatch.setattr(mesh_mod, "dispatch_mesh", spy)

    h, jobs = build()
    BatchEvalRunner(h.state.snapshot(), h).process(
        [make_eval(j) for j in jobs])
    assert used and used[-1] is not None, "mesh not used on 8 devices"
    assert "lanes" in used[-1].axis_names  # storm layout chosen
    mesh_counts = [sum(len(v) for v in p.node_allocation.values())
                   for p in h.plans]

    # Same workload forced down the single-device path (the
    # NOMAD_TPU_MESH="off" lever, here via its process override).
    monkeypatch.setattr(mesh_mod, "dispatch_mesh", orig)
    h2, jobs2 = build()
    with mesh_mod.mesh_override("off"):
        BatchEvalRunner(h2.state.snapshot(), h2).process(
            [make_eval(j) for j in jobs2])
    single_counts = [sum(len(v) for v in p.node_allocation.values())
                     for p in h2.plans]
    assert mesh_counts == single_counts == [4, 4, 4, 4]
    assert all(e.status == "complete" for e in h.evals)
