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


# ---------------------------------------------------------------------------
# The numpy twin's candidate sets: rows scored, as the spans and the
# runner say.
# ---------------------------------------------------------------------------

def _twin_rows_of(n_nodes: int, hold_something: bool):
    """The contended storm on ``n_nodes`` under the tracer: the tags of
    its host-engine ``sched.dispatch`` spans, of its ``sched.retry``
    spans and of their ``retry.dispatch`` children, and the runner's
    stats.  ``hold_something``: every node starts with an allocation
    of another job on it."""
    h, nodes, jobs = _contended_storm(n_nodes=n_nodes)
    if hold_something:
        held = []
        for n in nodes:
            a = mock.alloc()
            a.node_id = n.id
            held.append(a)
        h.state.upsert_allocs(h.next_index(), held)
    with trace.tracing(seed=38) as tracer:
        runner = BatchEvalRunner(h.state.snapshot(), h,
                                 state_refresh=h.snapshot)
        runner.process([make_eval(j) for j in jobs])
        spans = tracer.snapshot()
    lanes = [s["tags"] for s in spans if s["name"] == "sched.dispatch"]
    assert lanes and all(t["engine"] == "host" for t in lanes)
    retries = [s["tags"] for s in spans if s["name"] == "sched.retry"]
    attempts = [s["tags"] for s in spans if s["name"] == "retry.dispatch"]
    assert len(retries) >= 3 and len(attempts) >= len(retries)
    assert [e.status for e in h.evals] == ["complete"] * len(jobs)
    return lanes, retries, attempts, runner.stats()


def test_twin_rows_on_an_empty_fleet_are_a_few_of_its_rows():
    """A host ``sched.dispatch``, a ``sched.retry`` and its
    ``retry.dispatch`` carry ``twin_rows`` of ``twin_rows_full``, and
    the runner's stats hold their sum.  On an empty fleet of 2,048
    nodes the twin scores the rows the batch has filled and sixteen
    empty ones: under a tenth of what whole passes score."""
    lanes, retries, attempts, stats = _twin_rows_of(2048, False)
    for t in lanes + retries + attempts:
        assert 0 < t["twin_rows"] <= t["twin_rows_full"]
        assert t["twin_rows_full"] == 2048 * t.get("attempts", 1)
    for name in ("twin_rows", "twin_rows_full"):
        assert sum(t[name] for t in attempts) == \
            sum(t[name] for t in retries)
        assert stats[name] == sum(t[name] for t in lanes + retries)
    assert stats["twin_rows"] < 0.10 * stats["twin_rows_full"]


def test_twin_rows_on_a_full_fleet_are_all_of_its_rows():
    """Where every node holds something the candidate set is every row:
    the share reads 100%."""
    lanes, retries, attempts, stats = _twin_rows_of(64, True)
    for t in lanes + retries + attempts:
        assert t["twin_rows"] == t["twin_rows_full"] \
            == 64 * t.get("attempts", 1)
    assert stats["twin_rows"] == stats["twin_rows_full"] > 0


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


# ---------------------------------------------------------------------------
# The runner's cycle, opened (ISSUE 37): a re-plan's stages, a kernel
# window's stack and upload, and cpu_s / blocked_s on every stage span.
# ---------------------------------------------------------------------------

RETRY_STAGES = ["retry.begin", "retry.dispatch", "retry.finish",
                "retry.submit"]
NEW_NAMES = set(RETRY_STAGES) | {"retry.refresh", "window.stack",
                                 "window.upload", "plan.encode"}
STAGE_NAMES = ("sched.begin", "sched.dispatch", "sched.finish",
               "sched.submit", "sched.retry", "retry.")


def _traced_storm(planner_of=None, device: bool = False,
                  traced: bool = True, **storm):
    """The contended storm under the tracer (``traced`` off: with none):
    (harness, nodes, jobs, runner, spans).  ``device`` sends every
    window and re-plan to the XLA kernels on one device (warm first: a
    compile is no stage)."""
    from contextlib import ExitStack, nullcontext

    from nomad_tpu.parallel.mesh import mesh_override
    from nomad_tpu.scheduler.executor import executor_override

    with ExitStack() as stack:
        if device:
            stack.enter_context(executor_override("device"))
            stack.enter_context(mesh_override("off"))
            h, _nodes, jobs = _contended_storm(**storm)
            BatchEvalRunner(h.state.snapshot(), h,
                            state_refresh=h.snapshot).process(
                [make_eval(j) for j in jobs])
        h, nodes, jobs = _contended_storm(**storm)
        if planner_of is not None:
            h.planner = planner_of(h.planner)
        with (trace.tracing(seed=37) if traced else nullcontext()) as tracer:
            runner = BatchEvalRunner(h.state.snapshot(), h,
                                     state_refresh=h.snapshot)
            runner.process([make_eval(j) for j in jobs])
            spans = tracer.snapshot() if traced else []
    return h, nodes, jobs, runner, spans


def _children(spans: list, parent: dict) -> list:
    return sorted((s for s in spans
                   if s["parent_id"] == parent["span_id"]),
                  key=lambda s: s["t0"])


def test_a_replan_has_its_four_stages_in_order_inside_its_extent():
    """``sched.retry`` keeps its name, extent and every tag it had and
    is the parent of ``retry.begin`` / ``.dispatch`` / ``.finish`` /
    ``.submit``, one set an attempt, in that order, inside its extent
    and (in the median: one preemption is not the code's) within 5% of
    it; ``retry.dispatch`` IS the twin's seconds on the host engine;
    ``retry.refresh`` is a leaf under the eval's anchor just before."""
    h, nodes, jobs, _runner, spans = _traced_storm()
    retries = [s for s in spans if s["name"] == "sched.retry"]
    assert len(retries) >= 3, "the storm left too few stragglers"
    shares = []
    for retry in retries:
        tags = retry["tags"]
        assert {"host_calls", "device_calls", "attempts", "usage_walks",
                "twin_s", "twin_slots", "fit_rows", "fit_rows_full",
                "eval_id", "cpu_s", "blocked_s"} <= set(tags)
        kids = _children(spans, retry)
        assert [k["name"] for k in kids] == RETRY_STAGES * tags["attempts"]
        assert [k["tags"]["attempt"] for k in kids] == [
            a for a in range(1, tags["attempts"] + 1) for _ in RETRY_STAGES]
        end = retry["t0"]
        for kid in kids:
            assert kid["t0"] >= end - 1e-9          # in order, no overlap
            end = kid["t0"] + kid["dur"]
            assert kid["tags"]["eval_id"] == tags["eval_id"]
            assert {"cpu_s", "blocked_s"} <= set(kid["tags"])
        assert end <= retry["t0"] + retry["dur"] + 1e-9
        shares.append(sum(k["dur"] for k in kids) / retry["dur"])
        dispatch = kids[1]["tags"]
        assert (dispatch["engine"], dispatch["slots"], dispatch["mode"],
                dispatch["rounds"], dispatch["lanes"]) == \
            ("host", 1, "rounds", 1, 1)
        assert kids[1]["dur"] == pytest.approx(tags["twin_s"], rel=1e-6,
                                               abs=1e-9)
        assert kids[2]["tags"]["node_inits"] >= 1
        assert kids[2]["tags"]["walked"] == 0
        # The snapshot it planned on: a sibling, just before.
        refresh = [s for s in spans if s["name"] == "retry.refresh"
                   and s["tags"]["eval_id"] == tags["eval_id"]]
        assert len(refresh) == 1
        assert refresh[0]["parent_id"] == retry["parent_id"]
        assert refresh[0]["t0"] + refresh[0]["dur"] <= retry["t0"] + 1e-9
    shares.sort()
    assert 0.95 <= shares[len(shares) // 2] <= 1.0 + 1e-9, shares
    # The fused lanes' stages keep their names: no ``retry.*`` is a
    # ``sched.*`` again, and the twin's path has no window spans.
    assert not {s["name"] for s in spans} & {"window.stack",
                                             "window.upload"}
    assert all("fetch_s" not in (s.get("tags") or {}) for s in spans)
    _assert_placed_exactly(h, nodes, jobs)


def _rest_of(spans: list) -> list:
    """[(name, dur, dur - cpu_s - blocked_s)] of the runner's stage
    spans, a fused window once."""
    seen, out = set(), []
    for s in spans:
        tags = s.get("tags") or {}
        if s["name"].startswith(STAGE_NAMES) and \
                (s["name"], s["t0"], s["dur"]) not in seen:
            seen.add((s["name"], s["t0"], s["dur"]))
            out.append((s["name"], s["dur"],
                        s["dur"] - tags["cpu_s"] - tags["blocked_s"]))
    return out


class _ParkedPlanner:
    """The verifying planner behind ``Worker._wait_plan`` and a future
    that answers after ``PARK`` seconds: the runner's real parking
    place, with nothing of a server around it."""

    PARK = 0.02

    def __init__(self, inner) -> None:
        from nomad_tpu.server.worker import Worker

        self.inner = inner
        self.worker = object.__new__(Worker)
        self.parked = 0

    def _park(self, out):
        import time

        class _Future:
            def wait(_self, _timeout):
                time.sleep(self.PARK)  # sleep-ok: the parked wait itself
                return out

        self.parked += 1
        return self.worker._wait_plan(_Future())

    def submit_plan(self, plan):
        return self._park(self.inner.submit_plan(plan))

    def submit_plans(self, plans):
        return self._park(self.inner.submit_plans(plans))


def test_quiet_process_accounts_for_every_stage_second():
    """No other busy thread: what a stage span lasts is what its thread
    computed (``cpu_s``) plus what it chose to wait (``blocked_s``),
    within 10% or 1 ms, for every stage span of the storm; and a plan
    result parked in ``_wait_plan`` is ``blocked_s``, not the rest.
    (A loaded machine preempts: the best of three storms is held.)"""
    worst = None
    for _ in range(3):
        planner = []
        _h, _n, _j, _r, spans = _traced_storm(
            lambda inner: planner.append(_ParkedPlanner(inner))
            or planner[0])
        rows = _rest_of(spans)
        assert {n for n, _d, _r in rows} >= {
            "sched.begin", "sched.dispatch", "sched.finish",
            "sched.submit", "sched.retry", *RETRY_STAGES}
        off = [(n, d, r) for n, d, r in rows
               if abs(r) > max(0.1 * d, 1e-3)]
        waits = [s for s in spans
                 if s["name"] in ("sched.submit", "retry.submit")]
        parked = {(s["name"], s["t0"]): s["tags"]["blocked_s"]
                  for s in waits}
        assert len(parked) == planner[0].parked >= 4
        # Every parked wait, whole, in ``blocked_s`` (sleep never
        # returns early), and none of it in the rest.
        assert all(b >= 0.95 * _ParkedPlanner.PARK
                   for b in parked.values()), parked
        worst = off
        if not off:
            break
    assert not worst, worst


def test_a_spinning_thread_shows_as_the_rest_of_a_cpu_bound_stage():
    """A pure-Python thread that never parks holds the interpreter lock
    half the time: the runner's CPU-bound stages then last well over
    what they computed and chose to wait, and the rest says so."""
    import sys
    import threading

    stop = threading.Event()

    def spin() -> None:
        while not stop.is_set():
            pass

    was = sys.getswitchinterval()
    spinner = threading.Thread(target=spin, daemon=True)
    sys.setswitchinterval(0.0005)
    spinner.start()
    try:
        _h, _n, _j, _r, spans = _traced_storm(n_jobs=12, n_nodes=16)
    finally:
        stop.set()
        spinner.join(5.0)
        sys.setswitchinterval(was)
    rows = [(d, r) for n, d, r in _rest_of(spans)
            if n in ("sched.begin", "sched.retry")]
    assert len(rows) >= 12
    whole, rest = sum(d for d, _r in rows), sum(r for _d, r in rows)
    assert rest >= 0.15 * whole, (rest, whole)


def test_a_kernel_window_says_its_stack_upload_and_fetch_once():
    """On the device path every fused window records ``window.stack``
    and ``window.upload`` ONCE (not a lane), in the trace its
    ``device.dispatch`` joins, and that span says ``fetch_s``; a
    one-by-one re-plan on the kernel is ``retry.dispatch`` with
    ``engine`` device.  The window's lanes share one interval and one
    ``cpu_s`` / ``blocked_s`` pair."""
    from nomad_tpu.ops import binpack

    h, nodes, jobs, runner, spans = _traced_storm(device=True)
    fused = [s for s in spans if s["name"] == "device.dispatch" and
             s["tags"]["program"] == binpack.place_rounds_batch.__name__]
    stacks = [s for s in spans if s["name"] == "window.stack"]
    uploads = [s for s in spans if s["name"] == "window.upload"]
    assert len(fused) == len(stacks) == len(uploads) == \
        BatchEvalRunner.FUSED_RETRY_ROUNDS == runner.stats()["fused_batches"]
    for stack, upload, disp in zip(stacks, uploads, fused):
        assert stack["parent_id"] == upload["parent_id"] == \
            disp["parent_id"]
        assert stack["t0"] + stack["dur"] <= upload["t0"] + 1e-9
        assert upload["t0"] + upload["dur"] <= disp["t0"] + 1e-9
        for key in ("lanes", "b_pad", "g_pad", "n_pad"):
            assert stack["tags"][key] == disp["tags"][key], key
        # Every byte stacked is uploaded, and nothing else but the
        # snapshot's usage where it was not resident.
        assert 0 < stack["tags"]["bytes"] <= upload["tags"]["h2d_bytes"] \
            <= disp["tags"]["h2d_bytes"]
        assert 0.0 < disp["tags"]["fetch_s"] <= disp["dur"]
        lanes = [s for s in spans if s["name"] == "sched.dispatch"
                 and s["t0"] <= stack["t0"]
                 and s["t0"] + s["dur"] >= disp["t0"] + disp["dur"]]
        assert len(lanes) == disp["tags"]["lanes"]
        assert len({(s["t0"], s["dur"], s["tags"]["cpu_s"],
                     s["tags"]["blocked_s"]) for s in lanes}) == 1
    retries = [s for s in spans if s["name"] == "sched.retry"]
    assert retries
    for retry in retries:
        kids = _children(spans, retry)
        assert [k["name"] for k in kids] == RETRY_STAGES
        assert kids[1]["tags"]["engine"] == "device"
        assert retry["tags"]["twin_s"] == 0.0
    _assert_placed_exactly(h, nodes, jobs)


@pytest.mark.parametrize("device", [False, True], ids=["twin", "kernel"])
def test_tracing_off_builds_no_stage_clock(monkeypatch, device):
    """Tracing off: the storm takes no thread CPU clock, builds no
    stage clock, brackets no wait and keeps no stage; nothing of it is
    there when tracing comes on afterwards."""
    import time

    def boom(*_a, **_kw):
        raise AssertionError("a tracing-only call ran with tracing off")

    assert trace.ENABLED is False
    monkeypatch.setattr(time, "thread_time", boom)
    monkeypatch.setattr(trace, "StageClock", boom)
    monkeypatch.setattr(trace, "chosen_wait", boom)
    monkeypatch.setattr(trace.Tracer, "record", boom)
    h, nodes, jobs, _runner, _spans = _traced_storm(
        _ParkedPlanner, device=device, traced=False)
    assert trace.ENABLED is False and trace.tracer() is None
    with trace.tracing(seed=37) as tracer:
        assert tracer.snapshot() == []
    _assert_placed_exactly(h, nodes, jobs)
