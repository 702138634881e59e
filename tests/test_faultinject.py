"""Fault-injection subsystem: spec grammar, registry lifecycle, and one
fast unit test per instrumented site (rpc.send, rpc.recv, raft.apply,
heartbeat.deliver, device.dispatch, device.collect, driver.start), plus
the device-executor circuit breaker's state machine and the client
retry regressions the subsystem was built to catch.
"""
from __future__ import annotations

import logging
import os
import threading
import time

import pytest

import nomad_tpu.mock as mock
from nomad_tpu import faultinject
from nomad_tpu.faultinject import (
    FaultDropped,
    FaultInjected,
    FaultPlan,
    FaultSpecError,
)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Every test here starts and ends with no active plan."""
    faultinject.clear_plan()
    yield
    faultinject.clear_plan()


# ---------------------------------------------------------------------------
# spec grammar + registry lifecycle
# ---------------------------------------------------------------------------

class TestSpecAndRegistry:
    def test_trivial_plan_injects_and_clears(self):
        """Tier-1 smoke: install -> fire -> clear is airtight."""
        assert not faultinject.ACTIVE
        faultinject.fire("raft.apply")  # no plan: no-op
        plan = FaultPlan().add("raft.apply", "error", count=1)
        faultinject.install_plan(plan)
        assert faultinject.ACTIVE
        with pytest.raises(FaultInjected):
            faultinject.fire("raft.apply")
        faultinject.fire("raft.apply")  # budget spent: no-op
        assert plan.exhausted()
        faultinject.clear_plan()
        assert not faultinject.ACTIVE
        assert faultinject.active_plan() is None
        faultinject.fire("raft.apply")  # cleared: no-op again
        assert plan.fire_count() == 1

    def test_injected_context_restores_previous(self):
        outer = FaultPlan()
        faultinject.install_plan(outer)
        with faultinject.injected(FaultPlan()) as inner:
            assert faultinject.active_plan() is inner
        assert faultinject.active_plan() is outer

    def test_injected_context_clears_on_exception(self):
        with pytest.raises(RuntimeError):
            with faultinject.injected(FaultPlan()):
                raise RuntimeError("test failure mid-soak")
        assert not faultinject.ACTIVE

    def test_parse_full_grammar(self):
        plan = FaultPlan.parse(
            "seed=7;"
            "rpc.send=drop(p=0.5,count=3,method=Node.*);"
            "heartbeat.deliver=drop(node=n-1);"
            "device.collect=hang(secs=0.01);"
            "raft.apply=delay(secs=0.02,after=2)")
        assert plan.seed == 7
        rules = {r.site: r for r in plan.rules()}
        assert rules["rpc.send"].action == "drop"
        assert rules["rpc.send"].p == 0.5
        assert rules["rpc.send"].count == 3
        assert rules["rpc.send"].method == "Node.*"
        assert rules["heartbeat.deliver"].node == "n-1"
        assert rules["device.collect"].secs == 0.01
        assert rules["raft.apply"].after == 2

    def test_serving_plane_sites_registered(self):
        """ISSUE 7 satellite: the edge chokepoints are first-class
        sites with the right predicate contexts."""
        from nomad_tpu.faultinject.plan import SITE_CONTEXT, SITES

        assert len(SITES) == 16
        for site in ("mux.accept", "conn.read", "watch.deliver"):
            assert site in SITES
        assert SITE_CONTEXT["mux.accept"] == ()
        assert SITE_CONTEXT["conn.read"] == ()
        assert SITE_CONTEXT["watch.deliver"] == ("method",)
        # The grammar accepts table-name predicates on watch.deliver.
        plan = FaultPlan.parse(
            "mux.accept=error(count=1);conn.read=drop(p=0.1);"
            "watch.deliver=drop(method=allocs)")
        rules = {r.site: r for r in plan.rules()}
        assert rules["watch.deliver"].method == "allocs"

    def test_storage_sites_registered(self):
        """ISSUE 8 satellite: the durable-storage chokepoints are
        first-class sites (16-site table) with path predicates, and
        the ``crash`` action is storage-only."""
        from nomad_tpu.faultinject.plan import (
            SITE_CONTEXT,
            SITES,
            STORAGE_SITES,
        )

        assert STORAGE_SITES == ("log.append", "log.fsync",
                                 "snapshot.persist", "meta.persist")
        for site in STORAGE_SITES:
            assert site in SITES
            # Stores pass their on-disk path as ``method`` so one
            # server's data_dir is targetable in a cluster soak.
            assert SITE_CONTEXT[site] == ("method",)
        plan = FaultPlan.parse(
            "seed=3;log.append=crash(count=1,after=2);"
            "snapshot.persist=crash(method=/tmp/cluster/s1*)")
        rules = {r.site: r for r in plan.rules()}
        assert rules["log.append"].action == "crash"
        assert rules["snapshot.persist"].method == "/tmp/cluster/s1*"
        # Non-crash actions remain legal at storage sites (a plain
        # slow disk is delay/error, not power loss).
        FaultPlan.parse("log.fsync=delay(secs=0.01);meta.persist=error")

    def test_crash_is_seeded_and_latches(self, tmp_path):
        """The crash action draws its torn-byte layout from the plan's
        seeded RNG (same seed = same bytes) and latches the plan so
        every storage site refuses writes until reset."""
        from nomad_tpu.faultinject import FaultCrash
        from nomad_tpu.server.raft import FileLogStore, StorageDead

        def torn_size(seed: int) -> int:
            path = str(tmp_path / f"log-{seed}.bin")
            store = FileLogStore(path)
            plan = FaultPlan(seed=seed).add("log.append", "crash",
                                            count=1)
            with faultinject.injected(plan):
                with pytest.raises(FaultCrash):
                    store.append(1, b"payload-payload-payload")
                assert plan.is_crashed()
                assert faultinject.crashed()
                with pytest.raises(StorageDead):
                    store.append(2, b"more")
            assert not faultinject.crashed()  # plan uninstalled
            store.close()
            return os.path.getsize(path)

        assert torn_size(42) == torn_size(42)  # deterministic replay
        sizes = {torn_size(s) for s in (1, 2, 3, 4, 5)}
        assert len(sizes) > 1  # the offset really is seed-drawn

    @pytest.mark.parametrize("bad", [
        "nope.site=error",               # unknown site
        "rpc.send=explode",              # unknown action
        "rpc.send=error(p=oops)",        # bad float
        "rpc.send=error(count=1.5)",     # bad int
        "rpc.send=error(zap=1)",         # unknown param
        "rpc.send",                      # missing '='
        "seed=abc",                      # bad seed
        "rpc.send=error(p=0.5",          # unterminated params
        "rpc.send=error(p=2)",           # probability out of range
        "raft.apply=error(method=X)",    # site supplies no method ctx
        "device.collect=error(node=n)",  # site supplies no node ctx
        "heartbeat.deliver=drop(method=Node.Heartbeat)",  # node-only site
        "mux.accept=error(method=X)",    # edge accept has no request ctx
        "conn.read=drop(node=n-1)",      # bytes have no node identity
        "watch.deliver=drop(node=n-1)",  # fan-out passes table as method
        "rpc.send=crash",                # crash only at storage sites
        "raft.apply=crash(count=1)",     # ditto: no bytes in flight
        "log.append=crash(node=n-1)",    # stores pass path as method
    ])
    def test_parse_rejects_malformed(self, bad):
        with pytest.raises(FaultSpecError):
            FaultPlan.parse(bad)

    def test_node_predicate_matches_alloc_update_payload(self):
        """fire_rpc digs the node id out of Node.UpdateAlloc's nested
        update dicts, so node-targeted rules cover that traffic too
        (a predicate that can never fire is rejected at parse; one
        that CAN fire must actually see the id)."""
        plan = FaultPlan().add("rpc.send", "error", node="n-7")
        with faultinject.injected(plan):
            faultinject.fire_rpc("rpc.send", "Node.UpdateAlloc",
                                 {"alloc": [{"id": "a", "node_id": "x"}]})
            with pytest.raises(FaultInjected):
                faultinject.fire_rpc(
                    "rpc.send", "Node.UpdateAlloc",
                    {"alloc": [{"id": "a", "node_id": "n-7"}]})

    def test_seeded_probability_is_deterministic(self):
        def run():
            out = []
            with faultinject.injected(
                    FaultPlan.parse("seed=11;rpc.send=drop(p=0.5)")):
                for _ in range(32):
                    try:
                        faultinject.fire("rpc.send")
                        out.append(0)
                    except FaultDropped:
                        out.append(1)
            return out

        first = run()
        assert first == run()
        assert 0 < sum(first) < 32  # actually probabilistic

    def test_match_predicates_and_after(self):
        plan = FaultPlan()
        plan.add("rpc.send", "error", method="Node.Register",
                 node="n-*", after=1)
        with faultinject.injected(plan):
            # Wrong method / wrong node / first match skipped.
            faultinject.fire("rpc.send", method="Job.Register", node="n-1")
            faultinject.fire("rpc.send", method="Node.Register", node="x")
            faultinject.fire("rpc.send", method="Node.Register", node="n-1")
            with pytest.raises(FaultInjected):
                faultinject.fire("rpc.send", method="Node.Register",
                                 node="n-2")


# ---------------------------------------------------------------------------
# per-site units
# ---------------------------------------------------------------------------

class TestSites:
    def test_rpc_send_site(self):
        """ConnPool.call consults rpc.send before anything touches the
        wire — no server needed to prove the drop."""
        from nomad_tpu.server.rpc import ConnPool

        pool = ConnPool()
        plan = FaultPlan().add("rpc.send", "drop", count=1,
                               method="Status.Ping")
        with faultinject.injected(plan):
            with pytest.raises(FaultDropped):
                pool.call(("127.0.0.1", 1), "Status.Ping", {})
        assert plan.fire_count("rpc.send") == 1
        pool.shutdown()

    def test_rpc_recv_drop_and_error(self):
        """Server-side receive faults: ``drop`` swallows the request
        (caller sees only its own timeout), ``error`` surfaces as an
        RPC error reply."""
        from nomad_tpu.server.rpc import ConnPool, RPCError, RPCServer

        srv = RPCServer()
        srv.register("Echo.Hello", lambda args: {"hi": 1})
        srv.start()
        pool = ConnPool()
        try:
            plan = FaultPlan()
            plan.add("rpc.recv", "drop", count=1)
            plan.add("rpc.recv", "error", count=1)
            with faultinject.injected(plan):
                with pytest.raises(TimeoutError):
                    pool.call(srv.address, "Echo.Hello", {}, timeout=0.4)
                with pytest.raises(RPCError, match="injected"):
                    pool.call(srv.address, "Echo.Hello", {})
                # Budget spent: the plane is healthy again.
                assert pool.call(srv.address, "Echo.Hello", {}) == \
                    {"hi": 1}
        finally:
            pool.shutdown()
            srv.shutdown()

    def test_rpc_recv_drop_on_plain_plane(self):
        """The non-mux (0x01) plane swallows dropped frames too."""
        from nomad_tpu.server.rpc import ConnPool, RPCServer

        srv = RPCServer()
        srv.register("Echo.Hello", lambda args: {"hi": 1})
        srv.start()
        pool = ConnPool(multiplex=False)
        try:
            with faultinject.injected(
                    FaultPlan().add("rpc.recv", "drop", count=1)):
                with pytest.raises((TimeoutError, OSError)):
                    pool.call(srv.address, "Echo.Hello", {}, timeout=0.4)
            assert pool.call(srv.address, "Echo.Hello", {}) == {"hi": 1}
        finally:
            pool.shutdown()
            srv.shutdown()

    def test_raft_apply_site(self):
        from nomad_tpu.server.raft import InmemRaft

        class _FSM:
            def apply(self, index, entry):
                return None

        raft = InmemRaft(_FSM())
        with faultinject.injected(
                FaultPlan().add("raft.apply", "error", count=1)):
            with pytest.raises(FaultInjected):
                raft.apply(b"entry")
            # Budget spent: the log moves again.
            raft.apply(b"entry").wait(1.0)
        assert raft.applied_index() == 1

    def test_heartbeat_deliver_site(self):
        """A dropped delivery leaves the TTL timer un-reset: the node
        is on the path to expiry while the client sees an error."""
        from nomad_tpu.server.heartbeat import HeartbeatManager

        hb = HeartbeatManager(server=None, timer_factory=_FakeTimer)
        try:
            plan = FaultPlan().add("heartbeat.deliver", "drop",
                                   node="n-victim")
            with faultinject.injected(plan):
                assert hb.reset_heartbeat_timer("n-ok") > 0
                with pytest.raises(FaultDropped):
                    hb.reset_heartbeat_timer("n-victim")
            with hb._lock:
                assert "n-ok" in hb._timers
                assert "n-victim" not in hb._timers
        finally:
            hb.clear()

    def test_driver_start_site(self, tmp_path):
        from nomad_tpu.client.allocdir import AllocDir
        from nomad_tpu.client.driver.base import ExecContext
        from nomad_tpu.client.task_runner import TaskRunner
        from nomad_tpu.structs import Resources, Task

        task = Task(name="echo", driver="raw_exec",
                    config={"command": "/bin/sh",
                            "args": "-c 'echo hi'"},
                    resources=Resources(cpu=100, memory_mb=64))
        ad = AllocDir(str(tmp_path / "alloc"))
        ad.build([task])
        states = []
        tr = TaskRunner(ExecContext(ad, "a"), task,
                        on_state=lambda n, s, d: states.append((s, d)))
        with faultinject.injected(
                FaultPlan().add("driver.start", "error",
                                method="raw_exec")):
            tr.run()  # inline: deterministic, no thread needed
        assert tr.failed
        assert tr.state == "dead"
        assert any("injected" in d for _s, d in states)


def _FakeTimer(ttl, fn, args):
    """Inert timer for fake-clock heartbeat tests."""
    class _T:
        def __init__(self):
            self.ttl = ttl
            self.fn = fn
            self.args = args
            self.cancelled = False

        def start(self):
            pass

        def cancel(self):
            self.cancelled = True

        def fire(self):
            self.fn(*self.args)
    return _T()


# ---------------------------------------------------------------------------
# device sites + circuit breaker through the pipeline
# ---------------------------------------------------------------------------

def _pipeline_cluster(n_nodes: int, n_jobs: int):
    from nomad_tpu.scheduler import Harness

    h = Harness()
    for i in range(n_nodes):
        h.state.upsert_node(h.next_index(), mock.node(i))
    jobs = []
    for _ in range(n_jobs):
        j = mock.job()
        h.state.upsert_job(h.next_index(), j)
        jobs.append(j)
    return h, jobs


def _make_eval(job):
    from nomad_tpu.structs import (EVAL_TRIGGER_JOB_REGISTER, Evaluation,
                                   generate_uuid)

    return Evaluation(id=generate_uuid(), priority=job.priority,
                      type=job.type,
                      triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                      job_id=job.id)


class TestDeviceBreaker:
    def test_dispatch_fault_trips_breaker_then_probe_closes(self):
        """device.dispatch fault: the eval re-runs on the host twin
        (still completes), the breaker opens, holds subsequent evals on
        host, then a half-open probe parity-checks and closes."""
        from nomad_tpu.scheduler.breaker import (CLOSED, OPEN,
                                                 DeviceCircuitBreaker)
        from nomad_tpu.scheduler.executor import executor_override
        from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner

        h, jobs = _pipeline_cluster(8, 3)
        breaker = DeviceCircuitBreaker(failure_threshold=1, cooldown=30.0)
        plan = FaultPlan().add("device.dispatch", "error", count=1)
        with faultinject.injected(plan), executor_override("device"):
            # Round 1: first dispatch faults -> open; the window's
            # remaining evals are held on host.
            r1 = PipelinedEvalRunner(h.state.snapshot(), h, depth=2,
                                     breaker=breaker)
            r1.process([_make_eval(j) for j in jobs[:2]])
            assert breaker.state == OPEN
            assert r1.breaker_reruns == 1
            assert breaker.stats()["opens"] == 1
            assert breaker.stats()["host_holds"] >= 1

            # Round 2: cooldown elapsed (fake it) -> probe -> parity
            # asserted -> closed.
            with breaker._lock:
                breaker._opened_at = -1e9
            r2 = PipelinedEvalRunner(h.state.snapshot(), h, depth=2,
                                     breaker=breaker,
                                     state_refresh=lambda:
                                     h.state.snapshot())
            r2.process([_make_eval(jobs[2])])
            assert breaker.state == CLOSED
            assert breaker.stats()["probes"] == 1
            assert breaker.stats()["closes"] == 1
            assert r2.parity_checks == 1
        assert all(e.status == "complete" for e in h.evals)
        assert len(h.plans) == 3

    def test_compile_refusal_propagates_past_the_breaker(self,
                                                         monkeypatch):
        """A dispatch the chip's compiler refuses is deterministic, not
        a transient fault: it must surface to the caller instead of
        re-running on the host twin and tripping the breaker."""
        import jax

        from nomad_tpu.scheduler.breaker import (CLOSED,
                                                 DeviceCircuitBreaker)
        from nomad_tpu.scheduler.executor import executor_override
        from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler
        from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner

        def refuse(self, args, pipelined=False, force=False):
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: Allocation (size=360038400000) "
                "would exceed memory (size=17179869184)")

        monkeypatch.setattr(JaxBinPackScheduler, "dispatch_device", refuse)
        h, jobs = _pipeline_cluster(8, 2)
        breaker = DeviceCircuitBreaker(failure_threshold=1, cooldown=30.0)
        runner = PipelinedEvalRunner(h.state.snapshot(), h, depth=2,
                                     breaker=breaker)
        with executor_override("device"), \
                pytest.raises(jax.errors.JaxRuntimeError,
                              match="would exceed memory"):
            runner.process([_make_eval(j) for j in jobs])
        assert breaker.state == CLOSED
        assert breaker.stats()["failures"] == 0
        assert runner.breaker_reruns == 0 and not h.plans

    def test_collect_fault_reruns_on_host(self):
        """device.collect fault mid-window: drain re-runs that eval on
        the host twin; plans still land, breaker records the failure."""
        import time as _time

        from nomad_tpu.scheduler.breaker import DeviceCircuitBreaker
        from nomad_tpu.scheduler.executor import executor_override
        from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner, _Item

        h, jobs = _pipeline_cluster(8, 3)
        breaker = DeviceCircuitBreaker(failure_threshold=2, cooldown=30.0)
        runner = PipelinedEvalRunner(h.state.snapshot(), h, depth=8,
                                     breaker=breaker)
        plan = FaultPlan().add("device.collect", "error", count=1)
        with faultinject.injected(plan), executor_override("device"):
            window = []
            for j in jobs:
                start = _time.perf_counter()
                sched = runner._begin_eval(_make_eval(j),
                                           finish_noop=False)
                place, args = sched.deferred
                handles, probe = runner._dispatch(sched, args)
                window.append(_Item(sched, place, args, handles, start,
                                    probe=probe))
            runner._drain_window(window)
        assert runner.breaker_reruns == 1
        assert breaker.stats()["failures"] == 1
        assert breaker.state == "closed"  # threshold=2, one failure
        assert all(e.status == "complete" for e in h.evals)
        assert len(h.plans) == 3

    def test_collect_deadline_breaks_hang(self):
        """A hung device collect (injected hang) is cut off by the
        watchdog deadline and re-run on host."""
        import time as _time

        from nomad_tpu.scheduler.breaker import OPEN, DeviceCircuitBreaker
        from nomad_tpu.scheduler.executor import executor_override
        from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner, _Item

        h, jobs = _pipeline_cluster(8, 1)
        breaker = DeviceCircuitBreaker(failure_threshold=1, cooldown=30.0)
        runner = PipelinedEvalRunner(h.state.snapshot(), h, depth=2,
                                     breaker=breaker,
                                     device_deadline=0.2)
        plan = FaultPlan().add("device.collect", "hang", secs=1.5,
                               count=1)
        t0 = _time.monotonic()
        with faultinject.injected(plan), executor_override("device"):
            sched = runner._begin_eval(_make_eval(jobs[0]),
                                       finish_noop=False)
            place, args = sched.deferred
            handles, probe = runner._dispatch(sched, args)
            runner._drain_window([_Item(sched, place, args, handles,
                                        _time.perf_counter(),
                                        probe=probe)])
        # The watchdog cut the hang off well before its 1.5s.
        assert _time.monotonic() - t0 < 1.2
        assert runner.breaker_reruns == 1
        assert breaker.state == OPEN
        assert all(e.status == "complete" for e in h.evals)

    def test_breaker_state_machine_with_fake_clock(self):
        from nomad_tpu.scheduler.breaker import (ADMIT_DEVICE, ADMIT_HOST,
                                                 ADMIT_PROBE, CLOSED,
                                                 HALF_OPEN, OPEN,
                                                 DeviceCircuitBreaker)

        now = [0.0]
        b = DeviceCircuitBreaker(failure_threshold=2, cooldown=10.0,
                                 clock=lambda: now[0])
        assert b.admit() == ADMIT_DEVICE
        b.record_failure()
        assert b.state == CLOSED          # below threshold
        b.record_success()                # success resets the streak
        b.record_failure()
        b.record_failure()
        assert b.state == OPEN            # threshold consecutive
        assert b.admit() == ADMIT_HOST    # held during cooldown
        now[0] += 10.0
        assert b.admit() == ADMIT_PROBE   # cooldown elapsed
        assert b.state == HALF_OPEN
        assert b.admit() == ADMIT_HOST    # one probe in flight at a time
        b.record_failure(probe=True)      # probe failed: re-open
        assert b.state == OPEN
        now[0] += 10.0
        assert b.admit() == ADMIT_PROBE
        b.record_success(probe=True)
        assert b.state == CLOSED
        stats = b.stats()
        assert stats["opens"] == 2 and stats["closes"] == 1
        assert stats["probes"] == 2 and stats["host_holds"] == 2

    def test_lost_probe_outcome_reprobes_after_timeout(self):
        """Review regression: a probe whose outcome is never recorded
        (its window was discarded by an unrelated drain error) must not
        pin the breaker half-open-on-host forever — past probe_timeout
        a fresh probe is issued."""
        from nomad_tpu.scheduler.breaker import (ADMIT_HOST, ADMIT_PROBE,
                                                 CLOSED,
                                                 DeviceCircuitBreaker)

        now = [0.0]
        b = DeviceCircuitBreaker(failure_threshold=1, cooldown=1.0,
                                 probe_timeout=5.0,
                                 clock=lambda: now[0])
        b.record_failure()           # open
        now[0] += 1.0
        assert b.admit() == ADMIT_PROBE
        # ... the probe item is lost: no outcome ever recorded ...
        now[0] += 4.0
        assert b.admit() == ADMIT_HOST    # not yet presumed lost
        now[0] += 1.5
        assert b.admit() == ADMIT_PROBE   # presumed lost: re-probe
        b.record_success(probe=True)
        assert b.state == CLOSED

    def test_probe_parity_mismatch_fails_loudly_and_reopens(self):
        """Review regression: a probe whose device result the host
        scorer rejects must raise (not silently close the breaker) and
        re-open it."""
        from nomad_tpu.scheduler.breaker import OPEN, DeviceCircuitBreaker
        from nomad_tpu.scheduler.executor import executor_override
        from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner

        h, jobs = _pipeline_cluster(8, 1)
        breaker = DeviceCircuitBreaker(failure_threshold=1, cooldown=0.0)
        breaker.record_failure()  # open; next admission is a probe

        class _CorruptDevice(PipelinedEvalRunner):
            def _collect_device_bounded(self, it):
                chosen, scores = super()._collect_device_bounded(it)
                chosen = chosen.copy()
                chosen[1] = chosen[0]  # two copies stacked on one node
                return chosen, scores

        runner = _CorruptDevice(h.state.snapshot(), h, depth=2,
                                breaker=breaker)
        with executor_override("device"):
            with pytest.raises(RuntimeError, match="parity violation"):
                runner.process([_make_eval(jobs[0])])
        assert breaker.state == OPEN  # probe failure re-opened it
        assert runner.parity_checks == 0

    def test_probe_contract_follows_the_device_trajectory(self):
        """On a TPU 10^x rounds differently from numpy, so near-tied
        nodes may swap between the engines and their usage trajectories
        part ways (measured on a v5e: PERF.md, bring-up).  The probe
        therefore asks the host scorer to rank each device pick at the
        step the device made it: a tied node in place of the twin's is
        fine; a clearly worse node, a node outside the fleet, or a copy
        left unplaced is not — in both kernel modes."""
        import numpy as np

        from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler
        from nomad_tpu.scheduler.pipeline import probe_agrees

        h, jobs = _pipeline_cluster(8, 1)
        sched = JaxBinPackScheduler(h.state.snapshot(), h, batch=False)
        sched.eval = _make_eval(jobs[0])
        sched.defer_device = True
        sched._begin()
        _place, args = sched.deferred
        assert args.rounds_eligible
        twin, _scores = sched.collect_device(args, sched.dispatch_host(args))
        n_place = args.n_place
        assert (twin[:n_place] >= 0).all()
        for rounds_mode in (True, False):
            args.rounds_eligible = rounds_mode
            assert probe_agrees(args, twin)
            # The empty homogeneous fleet ties exactly: the two copies
            # trading nodes is the swap a TPU's rounding produces.
            swapped = twin.copy()
            swapped[[0, 1]] = twin[[1, 0]]
            assert probe_agrees(args, swapped)
            # Stacking two copies on one node forfeits the 10-point
            # anti-affinity term: far outside any rounding.
            stacked = twin.copy()
            stacked[1] = twin[0]
            assert not probe_agrees(args, stacked)
            outside = twin.copy()
            outside[0] = args.statics.n_real  # a padding row
            assert not probe_agrees(args, outside)
            dropped = twin.copy()
            dropped[n_place - 1] = -1
            assert not probe_agrees(args, dropped)

    def test_pipeline_unaffected_without_faults(self):
        """No plan, forced device: the breaker stays closed and counts
        stay clean (the parity suite guards semantics; this guards the
        new plumbing's no-fault path)."""
        from nomad_tpu.scheduler.breaker import DeviceCircuitBreaker
        from nomad_tpu.scheduler.executor import executor_override
        from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner

        h, jobs = _pipeline_cluster(8, 3)
        breaker = DeviceCircuitBreaker()
        runner = PipelinedEvalRunner(h.state.snapshot(), h, depth=2,
                                     breaker=breaker)
        with executor_override("device"):
            runner.process([_make_eval(j) for j in jobs])
        assert breaker.state == "closed"
        assert breaker.stats() == {"opens": 0, "closes": 0, "probes": 0,
                                   "host_holds": 0, "failures": 0,
                                   "state": "closed"}
        assert runner.breaker_reruns == 0
        assert runner.device_dispatches == len(jobs)
        assert runner.host_dispatches == 0
        assert all(e.status == "complete" for e in h.evals)


# ---------------------------------------------------------------------------
# client retry regressions (the satellites)
# ---------------------------------------------------------------------------

class _ScriptedRPC:
    """In-proc rpc_handler whose UpdateAlloc failures are scripted."""

    def __init__(self, fail_updates: int = 0) -> None:
        self.fail_updates = fail_updates
        self.update_payloads: list = []
        self.lock = threading.Lock()

    def call(self, method: str, args: dict, timeout=None):
        if method == "Node.UpdateAlloc":
            with self.lock:
                if self.fail_updates > 0:
                    self.fail_updates -= 1
                    raise ConnectionError("scripted outage")
                self.update_payloads.append(args["alloc"])
            return {}
        return {"heartbeat_ttl": 10.0}


def _make_client(rpc_handler):
    from nomad_tpu.client import Client, ClientConfig

    return Client(ClientConfig(
        rpc_handler=rpc_handler,
        options={"fingerprint.skip_accel": "1"}))


def _alloc_update(alloc_id: str, status: str):
    from nomad_tpu.structs import Allocation

    return Allocation(id=alloc_id, client_status=status,
                      node_id="n-1", task_states={})


class TestClientRetries:
    def test_update_alloc_failure_queues_for_heartbeat(self, monkeypatch):
        """Satellite: a Node.UpdateAlloc that exhausts its retry burst
        is queued, not dropped, and the next heartbeat delivers it."""
        import nomad_tpu.client.client as client_mod
        from nomad_tpu.utils.retry import RetryPolicy

        monkeypatch.setattr(
            client_mod, "UPDATE_ALLOC_POLICY",
            RetryPolicy(base=0.01, max_delay=0.02, max_attempts=2,
                        retryable=lambda e: isinstance(e, Exception),
                        name="test.update_alloc"))
        rpc = _ScriptedRPC(fail_updates=5)  # outlasts one burst
        client = _make_client(rpc)
        try:
            client._sync_alloc_status(_alloc_update("a-1", "failed"))
            with client._update_lock:
                assert "a-1" in client._pending_updates  # queued, not lost
            # Newer status for the same alloc supersedes the queued one.
            client._sync_alloc_status(_alloc_update("a-1", "complete"))

            rpc.fail_updates = 0  # server back: heartbeat flushes
            client._flush_alloc_updates()
            with client._update_lock:
                assert not client._pending_updates
            assert len(rpc.update_payloads) == 1
            (delivered,) = rpc.update_payloads[0]
            assert delivered["id"] == "a-1"
            assert delivered["client_status"] == "complete"
        finally:
            client.shutdown()

    def test_flush_retry_resnapshots_queue(self, monkeypatch):
        """Review regression: a retry attempt must re-snapshot the
        queue, never re-send a payload a newer update superseded
        mid-burst (the stale re-send would regress a terminal status
        on the server)."""
        import nomad_tpu.client.client as client_mod
        from nomad_tpu.utils.retry import RetryPolicy

        monkeypatch.setattr(
            client_mod, "UPDATE_ALLOC_POLICY",
            RetryPolicy(base=0.01, max_delay=0.02, max_attempts=3,
                        retryable=lambda e: isinstance(e, Exception),
                        name="test.update_alloc"))

        client = _make_client(None)  # handler installed below

        class _FailOnceThenRecord:
            def __init__(self):
                self.payloads = []
                self.failed = False

            def call(self, method, args, timeout=None):
                if method != "Node.UpdateAlloc":
                    return {"heartbeat_ttl": 10.0}
                if not self.failed:
                    self.failed = True
                    # Simulate a runner queueing a NEWER status while
                    # this attempt is failing.
                    with client._update_lock:
                        client._pending_updates["a-1"] = {
                            "id": "a-1", "client_status": "complete",
                            "client_description": "",
                            "task_states": {}, "node_id": "n-1"}
                    raise ConnectionError("first attempt lost")
                self.payloads.append(args["alloc"])
                return {}

        rpc = _FailOnceThenRecord()
        client.rpc = rpc
        try:
            client._sync_alloc_status(_alloc_update("a-1", "running"))
            assert len(rpc.payloads) == 1
            (delivered,) = rpc.payloads[0]
            assert delivered["client_status"] == "complete"  # not stale
            with client._update_lock:
                assert not client._pending_updates
        finally:
            client.shutdown()

    def test_update_alloc_success_path_unqueued(self):
        rpc = _ScriptedRPC()
        client = _make_client(rpc)
        try:
            client._sync_alloc_status(_alloc_update("a-2", "running"))
            with client._update_lock:
                assert not client._pending_updates
            assert len(rpc.update_payloads) == 1
        finally:
            client.shutdown()

    def test_register_backoff_with_injected_fault(self, monkeypatch,
                                                  caplog):
        """Satellite: registration under an injected rpc.send fault
        retries with capped backoff and logs one traceback then
        one-line WARNs — and eventually registers."""
        import nomad_tpu.client.client as client_mod
        from nomad_tpu.server import Server, ServerConfig

        monkeypatch.setattr(client_mod, "REGISTER_RETRY_INTERVAL", 0.02)
        monkeypatch.setattr(client_mod, "REGISTER_RETRY_MAX", 0.05)
        srv = Server(ServerConfig(enable_rpc=True, num_schedulers=0))
        srv.establish_leadership()
        client = None
        try:
            from nomad_tpu.client import Client, ClientConfig

            client = Client(ClientConfig(
                servers=[srv.rpc_address()],
                options={"fingerprint.skip_accel": "1"}))
            plan = FaultPlan().add("rpc.send", "error", count=3,
                                   method="Node.Register")
            with caplog.at_level(logging.WARNING, logger="nomad_tpu"):
                with faultinject.injected(plan):
                    client._register()
            assert srv.fsm.state.node_by_id(client.node.id) is not None
            assert plan.fire_count("rpc.send") == 3
            warns = [r for r in caplog.records
                     if "registration" in r.getMessage()]
            assert len(warns) == 3
            assert all(r.levelno == logging.WARNING for r in warns)
            # Traceback on the first only; the rest are one-liners.
            assert warns[0].exc_info
            assert not any(r.exc_info for r in warns[1:])
        finally:
            if client is not None:
                client.shutdown()
            srv.shutdown()

    def test_register_gives_up_on_shutdown(self, monkeypatch):
        """The capped backoff honors shutdown: _register returns when
        the client stops, instead of spinning forever."""
        import nomad_tpu.client.client as client_mod

        monkeypatch.setattr(client_mod, "REGISTER_RETRY_INTERVAL", 0.02)
        monkeypatch.setattr(client_mod, "REGISTER_RETRY_MAX", 0.05)

        class _DeadRPC:
            def call(self, method, args, timeout=None):
                raise ConnectionError("nobody home")

        client = _make_client(_DeadRPC())
        t = threading.Thread(target=client._register, daemon=True)
        t.start()
        time.sleep(0.1)  # sleep-ok: park _register inside its backoff sleep
        client._shutdown.set()
        t.join(2.0)
        assert not t.is_alive()
        client.shutdown()


# ---------------------------------------------------------------------------
# site liveness: every registered site fires under one seeded plan
# ---------------------------------------------------------------------------

class TestSiteLiveness:
    """One seeded plan with a benign delay rule per registered site,
    driven through a live server (plus the device pipeline, a raw_exec
    driver, and the durable meta store — the planes a single server
    process does not own).  Every site must fire at least once, and
    placement must still converge exactly once: a site that never
    fires is registered-but-dead instrumentation the static pass's
    ``dead-site`` rule cannot see from the callgraph alone."""

    TERMINAL = ("complete", "failed", "canceled")

    def test_every_registered_site_fires(self, tmp_path):
        from nomad_tpu.faultinject.plan import SITES

        plan = FaultPlan(seed=19)
        for site in SITES:
            # delay(1ms): proves the chokepoint is consulted without
            # perturbing any outcome the convergence bar asserts.
            plan.add(site, "delay", secs=0.001)

        with faultinject.injected(plan):
            self._server_phase(plan, tmp_path)
            self._device_phase()
            self._driver_phase(tmp_path)
            self._meta_phase(tmp_path)

        silent = [s for s in SITES if plan.fire_count(s) == 0]
        assert not silent, f"registered-but-dead fault sites: {silent}"

    def _server_phase(self, plan, tmp_path):
        """Real RPC server with a durable raft plane: covers the rpc,
        mux, raft-storage, broker, heartbeat, and watch sites."""
        from nomad_tpu.server import Server, ServerConfig
        from nomad_tpu.server.rpc import ConnPool
        from nomad_tpu.structs import Resources, Task, TaskGroup

        srv = Server(ServerConfig(
            num_schedulers=2, enable_rpc=True,
            data_dir=str(tmp_path / "data"),
            raft_snapshot_threshold=4))  # trip snapshot.persist early
        srv.establish_leadership()
        pool = ConnPool()
        try:
            addr = srv.rpc_address()

            nodes = [mock.node(i) for i in range(4)]
            for node in nodes:
                out = pool.call(addr, "Node.Register",
                                {"node": node.to_dict()}, timeout=5.0)
                assert out["heartbeat_ttl"] > 0
            for node in nodes:
                pool.call(addr, "Node.Heartbeat",
                          {"node_id": node.id}, timeout=5.0)

            # Park a blocking query at the current index, then advance
            # it: the matured waiter rides the watch.deliver site.
            cur = srv.fsm.state.get_index("nodes")
            blocked: list = []
            waiter = threading.Thread(
                target=lambda: blocked.append(
                    pool.call(addr, "Node.List",
                              {"min_query_index": cur,
                               "max_query_time": 5.0}, timeout=10.0)),
                daemon=True)
            waiter.start()
            time.sleep(0.2)  # sleep-ok: let the query park on the watch
            late = mock.node(99)
            pool.call(addr, "Node.Register",
                      {"node": late.to_dict()}, timeout=5.0)
            waiter.join(10.0)
            assert not waiter.is_alive(), "blocking query never woke"
            assert blocked and blocked[0]["index"] > cur

            jobs = []
            for _ in range(2):
                job = mock.job()
                job.task_groups = [
                    TaskGroup(name=f"tg-{g}", count=1,
                              tasks=[Task(name="web", driver="exec",
                                          resources=Resources(
                                              cpu=200, memory_mb=64))])
                    for g in range(2)]
                pool.call(addr, "Job.Register",
                          {"job": job.to_dict()}, timeout=5.0)
                jobs.append(job)

            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                state = srv.fsm.state
                evals = state.evals()
                if evals and len(evals) >= len(jobs) and \
                        all(e.status in self.TERMINAL for e in evals):
                    break
                time.sleep(0.05)  # sleep-ok: poll cadence for convergence

            state = srv.fsm.state
            stuck = [(e.id, e.status) for e in state.evals()
                     if e.status not in self.TERMINAL]
            assert not stuck, f"non-terminal evals: {stuck}"
            # Exactly-once placement: per job AND per group.
            for job in jobs:
                live = [a for a in state.allocs_by_job(job.id)
                        if not a.terminal_status()]
                want = sum(tg.count for tg in job.task_groups)
                assert len(live) == want, \
                    f"job {job.id}: {len(live)} live allocs, want {want}"
                by_group: dict = {}
                for a in live:
                    by_group[a.task_group] = \
                        by_group.get(a.task_group, 0) + 1
                assert all(by_group.get(tg.name) == tg.count
                           for tg in job.task_groups), "duplicate placement"
        finally:
            pool.shutdown()
            srv.shutdown()

    def _device_phase(self):
        """Pipelined runner on the device executor: covers the
        device.dispatch / device.collect sites."""
        from nomad_tpu.scheduler.executor import executor_override
        from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner

        h, jobs = _pipeline_cluster(4, 2)
        with executor_override("device"):
            runner = PipelinedEvalRunner(h.state.snapshot(), h, depth=2)
            runner.process([_make_eval(j) for j in jobs])
        assert all(e.status == "complete" for e in h.evals)

    def _driver_phase(self, tmp_path):
        """raw_exec task through the real TaskRunner: covers the
        driver.start site; the delay must not fail the task."""
        from nomad_tpu.client.allocdir import AllocDir
        from nomad_tpu.client.driver.base import ExecContext
        from nomad_tpu.client.task_runner import TaskRunner
        from nomad_tpu.structs import Resources, Task

        task = Task(name="echo", driver="raw_exec",
                    config={"command": "/bin/sh",
                            "args": "-c 'echo site-liveness'"},
                    resources=Resources(cpu=100, memory_mb=64))
        ad = AllocDir(str(tmp_path / "alloc"))
        ad.build([task])
        tr = TaskRunner(ExecContext(ad, "alloc-live"), task)
        tr.run()  # inline: deterministic, no thread needed
        assert tr.state == "dead"
        assert not tr.failed

    def _meta_phase(self, tmp_path):
        """The raft term/vote MetaStore is NetRaft's plane (a single
        inmem server never persists meta); its site liveness is proved
        against the real store directly."""
        from nomad_tpu.server.raft import MetaStore

        meta = MetaStore(str(tmp_path / "meta" / "meta.json"))
        meta.save({"term": 1, "voted_for": "s1"})
        assert meta.load() == {"term": 1, "voted_for": "s1"}
