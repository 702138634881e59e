"""Batched optimistic scheduling: many evals fused into one dispatch."""
from __future__ import annotations

import pytest

import nomad_tpu.mock as mock
from nomad_tpu.models import fleet
from nomad_tpu.obs import trace
from nomad_tpu.scheduler import Harness, jax_binpack
from nomad_tpu.scheduler.batch import BatchEvalRunner
from nomad_tpu.scheduler.harness import VerifyingPlanner
from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler
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


# ---------------------------------------------------------------------------
# One-by-one re-plans: each starts from the store as it is by then.
# ---------------------------------------------------------------------------

def _contended_storm(n_jobs: int = 8, n_nodes: int = 12):
    """A real store behind the applier's own verify
    (``VerifyingPlanner``: partial accept, refresh index, a fresh
    snapshot with every partial result) and jobs of 2 copies of
    1,500 MHz on ``mock.node``s that hold two such copies each: every
    lane of a fused round bin-packs the same nodes, two plans a node
    commit, and the rest come back partial."""
    h = Harness()
    h.planner = VerifyingPlanner(h)
    nodes = [mock.node(i) for i in range(n_nodes)]
    for n in nodes:
        h.state.upsert_node(h.next_index(), n)
    jobs = []
    for _ in range(n_jobs):
        j = mock.job()
        j.task_groups[0].count = 2
        j.task_groups[0].tasks[0].resources.cpu = 1500
        h.state.upsert_job(h.next_index(), j)
        jobs.append(j)
    return h, nodes, jobs


def _assert_placed_exactly(h, nodes, jobs) -> None:
    """Every eval complete, every job's count placed, every node's
    committed allocations fit it."""
    assert [e.status for e in h.evals] == ["complete"] * len(jobs)
    for j in jobs:
        live = [a for a in h.state.allocs_by_job(j.id)
                if a.node_id and not a.terminal_status()]
        assert len(live) == j.task_groups[0].count, j.id
    for n in nodes:
        live = [a for a in h.state.allocs_by_node(n.id)
                if not a.terminal_status()]
        fit, dim, _ = allocs_fit(n, live)
        assert fit, (n.id, dim)


def _no_usage_walk(monkeypatch) -> None:
    def boom(*_a, **_kw):
        raise AssertionError("build_usage walked the whole store")
    monkeypatch.setattr(fleet, "build_usage", boom)
    monkeypatch.setattr(jax_binpack, "build_usage", boom)


def test_stragglers_plan_once_from_the_store_as_it_is(monkeypatch):
    """With a refresh hook the evals still partial after the fused
    rounds re-plan one by one, each on a snapshot that holds the
    re-plans before it: one attempt each, the usage mirror serves every
    view, and nobody walks the store."""
    _no_usage_walk(monkeypatch)
    h, nodes, jobs = _contended_storm()
    with trace.tracing(seed=34) as tracer:
        runner = BatchEvalRunner(h.state.snapshot(), h,
                                 state_refresh=h.snapshot)
        runner.process([make_eval(j) for j in jobs])
        retries = [s["tags"] for s in tracer.snapshot()
                   if s["name"] == "sched.retry"]
    assert len(retries) >= 3, "the storm left too few stragglers"
    assert {t["attempts"] for t in retries} == {1}
    assert {t["usage_walks"] for t in retries} == {0}
    assert {t["host_calls"] + t["device_calls"] for t in retries} == {1}
    stats = runner.stats()
    assert stats["replans"] == stats["replan_attempts"] == len(retries)
    assert stats["usage_walks"] == 0
    assert stats["fused_batches"] == BatchEvalRunner.FUSED_RETRY_ROUNDS
    _assert_placed_exactly(h, nodes, jobs)


def test_storm_without_a_refresh_hook_ends_as_before():
    """No hook (the harness, the graft entry's dry run): one fused
    round, then every partial lane re-plans at once on the snapshot
    its own submit handed back, through upstream's attempt limit, to
    the same end."""
    h, nodes, jobs = _contended_storm()
    runner = BatchEvalRunner(h.state.snapshot(), h)
    runner.process([make_eval(j) for j in jobs])
    stats = runner.stats()
    assert stats["fused_batches"] == 1
    assert stats["replan_attempts"] >= stats["replans"] >= 3
    _assert_placed_exactly(h, nodes, jobs)


def test_refresh_hook_is_taken_once_a_round_and_once_a_straggler():
    h, nodes, jobs = _contended_storm()
    taken = []

    def refresh():
        taken.append(h.state.latest_index())
        return h.state.snapshot()

    runner = BatchEvalRunner(h.state.snapshot(), h, state_refresh=refresh)
    runner.process([make_eval(j) for j in jobs])
    stragglers = runner.stats()["replans"]
    assert stragglers >= 3
    assert len(taken) == BatchEvalRunner.FUSED_RETRY_ROUNDS + stragglers
    # Each straggler's snapshot holds the commit of the one before it.
    per_straggler = taken[BatchEvalRunner.FUSED_RETRY_ROUNDS:]
    assert per_straggler == sorted(set(per_straggler))
    _assert_placed_exactly(h, nodes, jobs)


def test_a_quiet_batch_takes_no_refresh_and_no_replan():
    """Nothing partial: the hook is never called, no counter moves."""
    h = Harness()
    h.planner = VerifyingPlanner(h)
    nodes = [mock.node(i) for i in range(8)]
    for n in nodes:
        h.state.upsert_node(h.next_index(), n)
    job = mock.job()
    job.task_groups[0].count = 2
    h.state.upsert_job(h.next_index(), job)
    taken = []
    runner = BatchEvalRunner(
        h.state.snapshot(), h,
        state_refresh=lambda: taken.append(1) or h.state.snapshot())
    runner.process([make_eval(job)])
    assert taken == []
    stats = runner.stats()
    assert (stats["replans"], stats["replan_attempts"],
            stats["usage_walks"], stats["fused_batches"]) == (0, 0, 0, 1)
    _assert_placed_exactly(h, nodes, [job])


@pytest.mark.parametrize("through_runner", [False, True],
                         ids=["scheduler", "runner"])
def test_snapshot_older_than_the_mirror_walks_the_store(through_runner):
    """The walk is still there for whom it is meant: a scheduler whose
    snapshot another worker's sync has passed builds its view from
    every allocation of ITS snapshot (one ``usage_walks``), plans what
    fits there, and ends placed once the applier has refreshed it."""
    h, nodes, jobs = _contended_storm(n_jobs=4)
    first, second, third, late = jobs
    h.process("jax-binpack", make_eval(first))
    old = h.state.snapshot()
    # The mirror syncs to the snapshot a plan is MADE on: the third
    # job's holds the second's commit, which ``old`` lacks.
    h.process("jax-binpack", make_eval(second))
    h.process("jax-binpack", make_eval(third))
    by_id = {n.id: n for n in nodes}
    n_plans = len(h.plans)
    if through_runner:
        runner = BatchEvalRunner(old, h)
        runner.process([make_eval(late)])
        assert runner.stats()["usage_walks"] == 1
    else:
        sched = JaxBinPackScheduler(old, h, batch=False)
        sched.process(make_eval(late))
        assert sched.usage_walks == 1
        assert sched.attempts == len(h.plans) - n_plans
    # The plan made on the old snapshot fits the old snapshot.
    plan = h.plans[n_plans]
    assert sum(len(v) for v in plan.node_allocation.values()) == 2
    for node_id, placed in plan.node_allocation.items():
        held = [a for a in old.allocs_by_node(node_id)
                if not a.terminal_status()]
        fit, dim, _ = allocs_fit(by_id[node_id], held + placed)
        assert fit, (node_id, dim)
    _assert_placed_exactly(h, nodes, jobs)


# ---------------------------------------------------------------------------
# The prep's fit walk: rows examined, as the spans and the runner say.
# ---------------------------------------------------------------------------

def test_fit_walk_rows_on_the_prep_spans_and_in_the_stats(monkeypatch):
    """``sched.begin`` (a fused round's lane) and ``sched.retry`` (a
    one-by-one re-plan) carry ``fit_rows`` of ``fit_rows_full``, and
    the runner's stats hold their sum.  With the walk's first block cut
    to four rows, a lane of the first round, on an empty fleet of
    twelve, stops after that block; once the fleet fills a walk goes
    further, never past the fleet, and the plans end as ever."""
    monkeypatch.setattr(jax_binpack, "_FIT_BLOCK", 4)
    h, nodes, jobs = _contended_storm()
    with trace.tracing(seed=36) as tracer:
        runner = BatchEvalRunner(h.state.snapshot(), h,
                                 state_refresh=h.snapshot)
        runner.process([make_eval(j) for j in jobs])
        spans = tracer.snapshot()
    begins = [s["tags"] for s in spans if s["name"] == "sched.begin"]
    retries = [s["tags"] for s in spans if s["name"] == "sched.retry"]
    assert len(begins) > len(jobs) and len(retries) >= 3
    assert [t["fit_rows"] for t in begins[:len(jobs)]] == [4] * len(jobs)
    for t in begins + retries:
        assert t["fit_rows_full"] == len(nodes) * t.get("attempts", 1)
        assert 4 <= t["fit_rows"] <= t["fit_rows_full"]
    assert any(t["fit_rows"] > 4 for t in begins[len(jobs):] + retries)
    stats = runner.stats()
    for name in ("fit_rows", "fit_rows_full"):
        assert stats[name] == sum(t[name] for t in begins + retries)
    _assert_placed_exactly(h, nodes, jobs)


def test_fleet_minima_are_read_once_a_fleet_generation():
    """The least available cpu and memory the prep's gain bound divides
    by are a constant of the fleet generation: kept on its statics,
    equal to the recomputed ones, and a new generation has its own."""
    h = Harness()
    nodes = [mock.node(i) for i in range(6)]
    nodes[2].resources.cpu = 2500
    nodes[4].reserved.memory_mb = 1024
    for n in nodes:
        h.state.upsert_node(h.next_index(), n)

    def recomputed(statics) -> tuple:
        avail = statics.capacity[:statics.n_real] - \
            statics.reserved[:statics.n_real]
        return float(avail[:, 0].min()), float(avail[:, 1].min())

    snap = h.state.snapshot()
    statics = fleet.fleet_cache.statics_for(snap)
    assert statics.min_available == recomputed(statics) == (
        2500.0 - nodes[2].reserved.cpu,
        nodes[4].resources.memory_mb - 1024.0)
    assert statics.min_available is statics.min_available   # kept
    assert fleet.fleet_cache.statics_for(snap) is statics

    small = mock.node(6)
    small.resources.cpu, small.resources.memory_mb = 1000, 2048
    h.state.upsert_node(h.next_index(), small)
    grown = fleet.fleet_cache.statics_for(h.state.snapshot())
    assert grown.gen != statics.gen
    assert grown.min_available == recomputed(grown) == (
        1000.0 - small.reserved.cpu, 2048.0 - small.reserved.memory_mb)
    assert statics.min_available[0] == 2500.0 - nodes[2].reserved.cpu
    assert fleet.build_fleet([]).min_available == (1.0, 1.0)
