"""Executor override + host/device parity smoke (tier-1, CPU backend).

The NOMAD_TPU_EXECUTOR override (scheduler/executor.py) only selects
WHICH engine runs the placement kernels — numpy twins or the jit
kernels — never what is planned.  This suite forces a micro eval
stream through PipelinedEvalRunner both ways on the CPU backend and
asserts identical placed counts and scores on every tier-1 run.
"""
from __future__ import annotations

import pytest

import nomad_tpu.mock as mock
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.executor import (
    EXECUTOR_AUTO,
    EXECUTOR_DEVICE,
    EXECUTOR_HOST,
    ExecutorPolicyError,
    executor_override,
    executor_policy,
    set_executor_policy,
)
from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner
from nomad_tpu.structs import (
    EVAL_TRIGGER_JOB_REGISTER,
    Evaluation,
    generate_uuid,
)


def make_eval(job):
    return Evaluation(
        id=generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
    )


def _cluster(n_nodes: int, n_jobs: int, count: int = 3):
    h = Harness()
    for i in range(n_nodes):
        h.state.upsert_node(h.next_index(), mock.node(i))
    jobs = []
    for _ in range(n_jobs):
        j = mock.job()
        j.task_groups[0].count = count
        h.state.upsert_job(h.next_index(), j)
        jobs.append(j)
    return h, jobs


def _run_stream(executor: str, depth: int = 3):
    h, jobs = _cluster(12, 5)
    runner = PipelinedEvalRunner(h.state.snapshot(), h, depth=depth)
    with executor_override(executor):
        runner.process([make_eval(j) for j in jobs])
    return h, runner


def _plan_shape(h):
    """Per-plan placement count + per-alloc binpack scores, rounded to
    float32-stable precision (host kernels run f32 like the device)."""
    shape = []
    for p in h.plans:
        allocs = [a for v in p.node_allocation.values() for a in v]
        scores = sorted(
            round(s, 3) for a in allocs
            for s in a.metrics.scores.values())
        shape.append((sum(len(v) for v in p.node_allocation.values()),
                      len(p.failed_allocs), scores))
    return sorted(shape, key=str)


class TestParitySmoke:
    def test_forced_host_vs_forced_device_identical(self):
        """The acceptance gate: same stream, executor forced both ways,
        identical placed counts AND scores."""
        h_host, r_host = _run_stream(EXECUTOR_HOST)
        h_dev, r_dev = _run_stream(EXECUTOR_DEVICE)

        assert r_host.host_dispatches == len(h_host.plans)
        assert r_host.device_dispatches == 0
        assert r_dev.device_dispatches == len(h_dev.plans)
        assert r_dev.host_dispatches == 0

        assert _plan_shape(h_host) == _plan_shape(h_dev)
        assert all(e.status == "complete" for e in h_host.evals)
        assert all(e.status == "complete" for e in h_dev.evals)

    def test_forced_device_matches_auto_plans(self):
        """auto on this micro shape picks host; forcing device must not
        change what is planned."""
        h_auto, _ = _run_stream(EXECUTOR_AUTO)
        h_dev, _ = _run_stream(EXECUTOR_DEVICE)
        assert _plan_shape(h_auto) == _plan_shape(h_dev)

    def test_stage_times_and_windows_recorded(self):
        _, runner = _run_stream(EXECUTOR_DEVICE)
        assert runner.latencies and all(v >= 0 for v in runner.latencies)
        assert runner.windows and sum(runner.windows) == len(
            runner.latencies)
        # Every stage ran: begin/dispatch on the front thread,
        # collect/finish/submit on the drain thread.
        assert all(v >= 0.0 for v in runner.stage_times.values())
        assert runner.stage_times["begin"] > 0.0
        assert runner.stage_times["submit"] > 0.0


class TestPolicyResolution:
    def test_env_wins_over_config(self, monkeypatch):
        set_executor_policy(EXECUTOR_HOST)
        try:
            monkeypatch.setenv("NOMAD_TPU_EXECUTOR", "device")
            assert executor_policy() == EXECUTOR_DEVICE
            monkeypatch.delenv("NOMAD_TPU_EXECUTOR")
            assert executor_policy() == EXECUTOR_HOST
        finally:
            set_executor_policy(EXECUTOR_AUTO)

    def test_invalid_values_fail_loudly(self, monkeypatch):
        with pytest.raises(ExecutorPolicyError):
            set_executor_policy("tpu")
        monkeypatch.setenv("NOMAD_TPU_EXECUTOR", "gpu")
        with pytest.raises(ExecutorPolicyError):
            executor_policy()

    def test_override_restores_prior_env(self, monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_EXECUTOR", "host")
        with executor_override(EXECUTOR_DEVICE):
            assert executor_policy() == EXECUTOR_DEVICE
        assert executor_policy() == EXECUTOR_HOST

    def test_server_boot_validates_env(self, monkeypatch):
        """A typo'd $NOMAD_TPU_EXECUTOR fails the server BOOT, not the
        first dispatch (README Executor policy guarantee)."""
        from nomad_tpu.server import Server, ServerConfig

        monkeypatch.setenv("NOMAD_TPU_EXECUTOR", "gpu")
        with pytest.raises(ExecutorPolicyError):
            Server(ServerConfig(num_schedulers=0))

    def test_second_server_installs_its_own_policy(self):
        """The policy is process-wide, so every Server installs its
        configured value — a default-config server booted after an
        executor="device" one must run ``auto``, not inherit."""
        from nomad_tpu.server import Server, ServerConfig

        try:
            Server(ServerConfig(num_schedulers=0,
                                executor="device")).shutdown()
            assert executor_policy() == EXECUTOR_DEVICE
            Server(ServerConfig(num_schedulers=0)).shutdown()
            assert executor_policy() == EXECUTOR_AUTO
        finally:
            set_executor_policy(EXECUTOR_AUTO)

    def test_batch_runner_honors_force(self):
        """The fused batch path (BatchEvalRunner) obeys the same
        override: forced device must produce the same committed allocs
        as forced host — and its dispatch mix says which engine ran."""
        from nomad_tpu.scheduler.batch import BatchEvalRunner

        placed = {}
        mix = {}
        for executor in (EXECUTOR_HOST, EXECUTOR_DEVICE):
            h, jobs = _cluster(10, 4)
            runner = BatchEvalRunner(h.state.snapshot(), h,
                                     state_refresh=h.snapshot)
            with executor_override(executor):
                runner.process([make_eval(j) for j in jobs])
            placed[executor] = _plan_shape(h)
            mix[executor] = runner.stats()
        assert placed[EXECUTOR_HOST] == placed[EXECUTOR_DEVICE]
        # Host: one twin call per lane, nothing on the device.  Device:
        # one fused call for all lanes, nothing on the twin; on the
        # suite's 8-device platform it rides the mesh.
        assert mix[EXECUTOR_HOST]["host_dispatches"] == len(jobs)
        assert mix[EXECUTOR_HOST]["device_dispatches"] == 0
        assert mix[EXECUTOR_DEVICE]["host_dispatches"] == 0
        assert mix[EXECUTOR_DEVICE]["device_dispatches"] == \
            mix[EXECUTOR_DEVICE]["fused_batches"] == 1
        assert mix[EXECUTOR_DEVICE]["sharded_dispatches"] == 1
