"""The fused device window's kept lane stacks (``scheduler/batch.py``
``_WindowStaging``): a runner keeps each window shape's feasibility stack
and job counts from window to window and rewrites only the lane positions
whose slot keys or job counts changed; the device copy of the
feasibility stack is passed again while no position was rewritten, and a
device stack of nought job counts while no real lane holds a count.

Each window the real runner sends to the kernel is held, lane by lane
and slot by slot, to the same kernel on a fresh stack of the same lanes
(the stacking as it was before the staging): the picks and scores of
every real lane and real slot are bit-identical, the staged job counts
are the fresh ones, and the window's call hits the jit cache entry the
fresh stack's call made.
"""
from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import nomad_tpu.mock as mock
from nomad_tpu.obs import trace
from nomad_tpu.ops import binpack
from nomad_tpu.parallel import devices
from nomad_tpu.parallel.devices import put_counted
from nomad_tpu.parallel.mesh import mesh_override
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler import batch as batch_mod
from nomad_tpu.scheduler.batch import BatchEvalRunner, STAGED_SHAPES
from nomad_tpu.scheduler.executor import executor_override
from nomad_tpu.structs import (
    EVAL_TRIGGER_JOB_REGISTER,
    Constraint,
    Evaluation,
    Resources,
    Task,
    TaskGroup,
    generate_uuid,
)

N_NODES = 48   # n_pad 64; every third node on rack r1
TAGS = ("lanes_restaged", "feas_resident", "jc_resident")


def _job(kind: str):
    """``a``: the mock job, two copies (one slot); ``b``: the same under
    a stanza that admits rack r1 only (another slot key, another mask);
    ``wide``: nine groups whose asks differ (nine slots: ``g_pad`` 16);
    ``big``: one copy of an ask too large for the rounds kernel (the
    window goes to the sequence kernel)."""
    job = mock.job()
    tg = job.task_groups[0]
    tg.count = 2
    if kind == "b":
        job.constraints = list(job.constraints) + [Constraint(
            hard=True, l_target="$meta.rack", operand="=", r_target="r1")]
    elif kind == "wide":
        job.task_groups = [
            TaskGroup(name=f"tg-{g}", count=1, tasks=[Task(
                name="web", driver="exec",
                resources=Resources(cpu=100, memory_mb=32 * (g + 1)))])
            for g in range(9)]
    elif kind == "big":
        tg.count = 1
        tg.tasks[0].resources.cpu = 3000
        tg.tasks[0].resources.memory_mb = 4000
    return job


class _Windows:
    """Every fused device window of the runner: its lanes, the kernel's
    answer on the staged operands, and its answer on a fresh stack of
    the same lanes, computed first (so the staged call must hit the jit
    cache entry the fresh one made)."""

    def __init__(self, monkeypatch) -> None:
        self.seen: list = []
        self._lanes = None
        stage = batch_mod._WindowStaging.stage

        def staged(staging, pending):
            self._lanes = list(pending)
            return stage(staging, pending)

        monkeypatch.setattr(batch_mod._WindowStaging, "stage", staged)
        for name in ("place_rounds_batch", "place_sequence_batch"):
            monkeypatch.setattr(binpack, name,
                                self._wrap(getattr(binpack, name), name))

    def _wrap(self, program, name: str):
        @functools.wraps(program)
        def call(*ops, **kw):
            lanes = self._lanes
            b_pad, g_pad, n_pad = ops[4].shape
            feasible = np.zeros((b_pad, g_pad, n_pad), dtype=bool)
            job_counts = np.zeros((b_pad, n_pad), dtype=np.int32)
            for b, (_s, _p, a) in enumerate(lanes):
                feasible[b, :a.g_pad] = a.feasible_h
                job_counts[b] = a.view.job_counts
            fresh = list(ops)
            fresh[3], fresh[4] = put_counted(job_counts), put_counted(feasible)
            want = jax.device_get(program(*fresh, **kw)[:2])
            entries = program._cache_size()
            out = program(*ops, **kw)
            self.seen.append(SimpleNamespace(
                kernel=name, lanes=lanes, want=want,
                got=jax.device_get(out[:2]),
                new_entries=program._cache_size() - entries,
                staged=jax.device_get(ops[3:5]),
                fresh=(job_counts, feasible)))
            return out
        return call


def _assert_bit_identical(window) -> None:
    """Every real lane's real slots (the rounds kernel) or placements
    (the sequence kernel) pick and score exactly as on a fresh stack."""
    (chosen, scores), (want_c, want_s) = window.got, window.want
    staged_jc, staged_feas = window.staged
    fresh_jc, fresh_feas = window.fresh
    assert np.array_equal(staged_jc, fresh_jc)
    assert window.new_entries == 0
    for b, (_s, _p, a) in enumerate(window.lanes):
        assert np.array_equal(staged_feas[b, :a.g_pad],
                              fresh_feas[b, :a.g_pad])
        real = a.n_groups if window.kernel == "place_rounds_batch" \
            else a.n_place
        assert np.array_equal(chosen[b, :real], want_c[b, :real])
        assert np.array_equal(scores[b, :real].view(np.uint32),
                              want_s[b, :real].view(np.uint32))
        assert (np.asarray(chosen[b, :real]) >= 0).any()


def _fleet():
    h = Harness()
    for i in range(N_NODES):
        node = mock.node(i)
        node.meta = dict(node.meta, rack=f"r{i % 3}")
        h.state.upsert_node(h.next_index(), node)
    return h


def _eval(job) -> Evaluation:
    return Evaluation(id=generate_uuid(), priority=job.priority,
                      type=job.type, triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                      job_id=job.id)


def _half_placed(h, job):
    """Register ``job`` with four copies, two of them already placed on
    nodes 0 and 1: its plan places the other two, and its view counts
    the job on those two rows."""
    job.task_groups[0].count = 4
    h.state.upsert_job(h.next_index(), job)
    stored = h.state.job_by_id(job.id)
    tg = stored.task_groups[0]
    nodes = sorted(h.state.nodes(), key=lambda n: n.id)
    allocs = []
    for i in range(2):
        alloc = mock.alloc()
        alloc.job, alloc.job_id = stored, stored.id
        alloc.node_id = nodes[i].id
        alloc.task_group = tg.name
        alloc.name = f"{stored.name}.{tg.name}[{i}]"
        allocs.append(alloc)
    h.state.upsert_allocs(h.next_index(), allocs)
    return stored


def test_the_kept_stacks_place_exactly_as_a_fresh_stack(monkeypatch):
    """One runner, a window at a time on the snapshot of the moment:
    the same keys; keys changed at two positions; five lanes then seven
    inside one lane bucket (a real lane turns pad and back); a ``g_pad``
    16 window between two of 8; a lane that holds job counts, then none;
    the sequence kernel; a new fleet generation.  Each window places
    exactly as a fresh stack would, and its ``window.stack`` tags and
    the runner's counters say what was rewritten and reused."""
    windows = _Windows(monkeypatch)
    h = _fleet()
    runner = BatchEvalRunner(h.state.snapshot(), h)
    mixed = ["a", "a", "b", "a", "b", "a"]
    steps = [
        # (lanes, the window.stack tags it must read)
        (["a"] * 6, (6, 0, 0)),      # a new runner rewrites every lane
        (["a"] * 6, (0, 1, 1)),      # same keys: nothing to rewrite
        (mixed, (2, 0, 1)),          # b at positions 2 and 4
        (mixed[:5], (0, 1, 1)),      # position 5 turns pad, kept as is
        (mixed + ["a"], (1, 0, 1)),  # 5 real again; 6 never written
        (["wide"] + mixed[1:], (6, 0, 0)),   # g_pad 16: its own stacks
        (mixed, (0, 1, 1)),          # back to g_pad 8, kept
        (["held"] + mixed[1:], (0, 1, 0)),   # one lane counts its job
        (mixed, (0, 1, 1)),          # its rows cleared: nought again
        (["big"] + mixed[1:], (1, 0, 1)),    # the sequence kernel
    ]
    with trace.tracing(seed=42) as tracer, executor_override("device"), \
            mesh_override("off"):
        for kinds, _tags in steps:
            jobs = []
            for kind in kinds:
                if kind == "held":
                    jobs.append(_half_placed(h, _job("a")))
                    continue
                job = _job(kind)
                h.state.upsert_job(h.next_index(), job)
                jobs.append(job)
            runner.state = h.state.snapshot()
            runner.process([_eval(j) for j in jobs])
        # A node joins: a new fleet generation drops every kept stack.
        h.state.upsert_node(h.next_index(), mock.node(N_NODES))
        jobs = [_job(k) for k in mixed]
        for job in jobs:
            h.state.upsert_job(h.next_index(), job)
        runner.state = h.state.snapshot()
        runner.process([_eval(j) for j in jobs])
        steps.append((mixed, (6, 0, 0)))
        stacks = [s["tags"] for s in tracer.snapshot()
                  if s["name"] == "window.stack"]
    assert [e.status for e in h.evals] == ["complete"] * len(h.evals)
    assert len(h.evals) == sum(len(k) for k, _t in steps)
    assert len(windows.seen) == len(stacks) == len(steps)
    for window, tags, (kinds, want) in zip(windows.seen, stacks, steps):
        assert tags["lanes"] == len(kinds) == len(window.lanes)
        assert tuple(tags[k] for k in TAGS) == want, (kinds, tags)
        _assert_bit_identical(window)
    assert [w.kernel for w in windows.seen].count(
        "place_sequence_batch") == 1
    assert windows.seen[7].fresh[0].any()       # the held job's rows
    stats = runner.stats()
    assert (stats["window_lanes_restaged"], stats["window_feas_reuses"],
            stats["window_jc_reuses"]) == tuple(
        sum(t[i] for _k, t in steps) for i in range(3))
    assert stats["device_dispatches"] == len(steps)


def test_lanes_wider_than_the_bucket_take_a_kept_shape_of_their_own(
        monkeypatch):
    """Nine lanes pad to sixteen: another shape, stacked afresh; the
    eight-lane shape's stacks are still kept for the window after."""
    windows = _Windows(monkeypatch)
    h = _fleet()
    runner = BatchEvalRunner(h.state.snapshot(), h)
    with executor_override("device"), mesh_override("off"):
        for n in (6, 9, 6):
            jobs = [_job("a") for _ in range(n)]
            for job in jobs:
                h.state.upsert_job(h.next_index(), job)
            runner.state = h.state.snapshot()
            runner.process([_eval(j) for j in jobs])
    assert sorted(runner.staging) == [(8, 8, 64), (16, 8, 64)]
    assert (runner.window_lanes_restaged, runner.window_feas_reuses,
            runner.window_jc_reuses) == (6 + 9, 1, 1)
    for window in windows.seen:
        _assert_bit_identical(window)


def test_the_runner_keeps_a_few_shapes_and_drops_them_with_the_fleet():
    """``STAGED_SHAPES`` shapes at most, the least recently used dropped
    first; a new fleet generation drops them all; a shape checked out
    and not kept again is gone."""
    runner = BatchEvalRunner(None, None)
    shapes = [(8 << i, 8, 16) for i in range(STAGED_SHAPES + 1)]

    def window(shape, gen=1):
        staging = runner._staging_take(shape, gen)
        runner._staging_keep(shape, staging)
        return staging
    kept = [window(s) for s in shapes[:STAGED_SHAPES]]
    assert window(shapes[0]) is kept[0]   # now the newest
    window(shapes[-1])
    assert sorted(runner.staging) == sorted([shapes[0]] + shapes[2:])
    assert window(shapes[0]) is kept[0]
    assert runner._staging_take(shapes[0], gen=1) is kept[0]
    assert shapes[0] not in runner.staging
    assert window(shapes[0]) is not kept[0]
    assert window(shapes[0], gen=2) is not kept[0]
    assert list(runner.staging) == [shapes[0]]


def test_a_window_that_fails_leaves_no_stacks_behind(monkeypatch):
    """A window whose lanes changed keys and whose upload then raises
    leaves no kept stacks: the same lanes again are staged afresh and
    uploaded, and place exactly as a fresh stack would."""
    windows = _Windows(monkeypatch)
    h = _fleet()
    runner = BatchEvalRunner(h.state.snapshot(), h)
    put = devices.put_counted
    calls = {"failed": 0}

    def failing(x, *a, copy=False, **kw):
        if copy and x.dtype == bool and not calls["failed"]:
            calls["failed"] += 1
            raise RuntimeError("upload failed")
        return put(x, *a, copy=copy, **kw)
    mixed = ["a", "a", "b", "a", "b", "a"]
    with trace.tracing(seed=42) as tracer, executor_override("device"), \
            mesh_override("off"):
        for kinds, fails in ((["a"] * 6, False), (mixed, True),
                             (mixed, False), (mixed, False)):
            jobs = [_job(k) for k in kinds]
            for job in jobs:
                h.state.upsert_job(h.next_index(), job)
            runner.state = h.state.snapshot()
            if fails:
                monkeypatch.setattr(devices, "put_counted", failing)
                with pytest.raises(RuntimeError, match="upload failed"):
                    runner.process([_eval(j) for j in jobs])
                monkeypatch.setattr(devices, "put_counted", put)
                assert not runner.staging
                continue
            runner.process([_eval(j) for j in jobs])
        stacks = [s["tags"] for s in tracer.snapshot()
                  if s["name"] == "window.stack"]
    assert calls["failed"] == 1
    assert [tuple(t[k] for k in TAGS) for t in stacks] == [
        (6, 0, 0), (2, 0, 1), (6, 0, 0), (0, 1, 1)]
    assert len(windows.seen) == 3
    for window in windows.seen:
        _assert_bit_identical(window)


@pytest.mark.parametrize("b_pad", [4, 8])
def test_job_counts_clear_the_rows_written_last(b_pad):
    """A lane's job counts are its view's; the rows an earlier lane
    wrote at a position, a pad position's included, are cleared."""
    n_pad = 32
    staging = batch_mod._WindowStaging(b_pad, 8, n_pad)

    def lane(counts: dict, key="k"):
        jc = np.zeros(n_pad, dtype=np.int32)
        for row, c in counts.items():
            jc[row] = c
        args = SimpleNamespace(feas_key=key, g_pad=8,
                               view=SimpleNamespace(job_counts=jc),
                               feasible_h=np.ones((8, n_pad), dtype=bool))
        return (None, None, args)

    windows = [[lane({3: 1, 7: 2}), lane({}), lane({30: 1})],
               [lane({4: 1}), lane({})],
               [lane({}), lane({}), lane({})]]
    for pending, want in zip(windows, [(3, True), (0, True), (0, False)]):
        assert staging.stage(pending) == want
        fresh = np.zeros((b_pad, n_pad), dtype=np.int32)
        for b, (_s, _p, a) in enumerate(pending):
            fresh[b] = a.view.job_counts
        assert np.array_equal(staging.job_counts, fresh)


def test_a_rewritten_position_drops_the_device_stack():
    """Once a position's mask rows are rewritten the device copy no
    longer matches the host buffer, and is not passed again."""
    staging = batch_mod._WindowStaging(4, 8, 16)
    staging.feasible_d = object()
    lane = SimpleNamespace(feas_key="k", g_pad=8,
                           view=SimpleNamespace(
                               job_counts=np.zeros(16, dtype=np.int32)),
                           feasible_h=np.ones((8, 16), dtype=bool))
    assert staging.stage([(None, None, lane)]) == (1, False)
    assert staging.feasible_d is None
    assert staging.resident(1, False) == (False, False)


def test_a_copy_upload_shares_no_memory_with_its_host_array():
    """``put_counted(..., copy=True)``: writing the host array after the
    upload leaves the device array as it was uploaded."""
    host = np.zeros((8, 4096), dtype=np.int32)
    dev = put_counted(host, copy=True)
    host[:] = 7
    assert not jax.device_get(dev).any()
