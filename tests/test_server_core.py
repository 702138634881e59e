"""Server-core tests: broker, plan queue, timetable, FSM, full pipeline."""
from __future__ import annotations

import threading
import time

import pytest

import nomad_tpu.mock as mock
from tests.conftest import wait_until
from nomad_tpu.server import (
    EvalBroker,
    NomadFSM,
    InmemRaft,
    PlanQueue,
    Server,
    ServerConfig,
    TimeTable,
)
from nomad_tpu.structs import Evaluation, Plan, codec, generate_uuid


def make_eval(priority=50, type_="service", job_id=None) -> Evaluation:
    return Evaluation(
        id=generate_uuid(), priority=priority, type=type_,
        job_id=job_id or generate_uuid(), status="pending",
        triggered_by="job-register",
    )


# ---------------------------------------------------------------------------
# EvalBroker
# ---------------------------------------------------------------------------

class TestEvalBroker:
    def test_enqueue_dequeue_priority(self):
        b = EvalBroker(nack_timeout=5, delivery_limit=3)
        b.set_enabled(True)
        low = make_eval(priority=20)
        high = make_eval(priority=90)
        b.enqueue(low)
        b.enqueue(high)
        ev, token = b.dequeue(["service"], timeout=1)
        assert ev.id == high.id
        assert token
        ev2, _ = b.dequeue(["service"], timeout=1)
        assert ev2.id == low.id

    def test_disabled_raises(self):
        b = EvalBroker(5, 3)
        with pytest.raises(RuntimeError):
            b.dequeue(["service"], timeout=0.05)

    def test_per_job_serialization(self):
        b = EvalBroker(5, 3)
        b.set_enabled(True)
        e1 = make_eval(job_id="job-1")
        e2 = make_eval(job_id="job-1")
        b.enqueue(e1)
        b.enqueue(e2)
        ev, token = b.dequeue(["service"], timeout=1)
        assert ev.id == e1.id
        # Second eval for the job is blocked.
        none, _ = b.dequeue(["service"], timeout=0.05)
        assert none is None
        assert b.stats()["total_blocked"] == 1
        # Ack unblocks it.
        b.ack(e1.id, token)
        ev2, _ = b.dequeue(["service"], timeout=1)
        assert ev2.id == e2.id

    def test_nack_requeues_then_fails(self):
        b = EvalBroker(5, delivery_limit=2)
        b.set_enabled(True)
        ev = make_eval()
        b.enqueue(ev)
        for _ in range(2):
            got, token = b.dequeue(["service"], timeout=1)
            assert got.id == ev.id
            b.nack(ev.id, token)
        # Past the delivery limit: routed to _failed.
        got, token = b.dequeue(["_failed"], timeout=1)
        assert got.id == ev.id

    def test_nack_timer_fires(self):
        b = EvalBroker(nack_timeout=0.05, delivery_limit=3)
        b.set_enabled(True)
        ev = make_eval()
        b.enqueue(ev)
        got, token = b.dequeue(["service"], timeout=1)
        # Event-driven: the timer's auto-nack shows up as a ready eval.
        wait_until(lambda: b.stats()["total_ready"] == 1,
                   msg="nack timer requeue")
        got2, _ = b.dequeue(["service"], timeout=1)
        assert got2.id == ev.id

    def test_wait_delay(self):
        b = EvalBroker(5, 3)
        b.set_enabled(True)
        ev = make_eval()
        ev.wait = 0.08
        b.enqueue(ev)
        none, _ = b.dequeue(["service"], timeout=0.02)
        assert none is None
        got, _ = b.dequeue(["service"], timeout=1)
        assert got.id == ev.id

    def test_dequeue_batch(self):
        b = EvalBroker(5, 3)
        b.set_enabled(True)
        evs = [make_eval() for _ in range(5)]
        for e in evs:
            b.enqueue(e)
        batch = b.dequeue_batch(["service"], max_batch=3, timeout=1)
        assert len(batch) == 3
        assert len({e.id for e, _ in batch}) == 3

    def test_dedup_enqueue(self):
        b = EvalBroker(5, 3)
        b.set_enabled(True)
        ev = make_eval()
        b.enqueue(ev)
        b.enqueue(ev)
        b.dequeue(["service"], timeout=1)
        none, _ = b.dequeue(["service"], timeout=0.05)
        assert none is None

    def test_token_mismatch(self):
        b = EvalBroker(5, 3)
        b.set_enabled(True)
        ev = make_eval()
        b.enqueue(ev)
        got, token = b.dequeue(["service"], timeout=1)
        with pytest.raises(ValueError):
            b.ack(ev.id, "wrong-token")
        b.ack(ev.id, token)


class TestEvalBrokerEdgeTable:
    """The reference's eval_broker_test.go scenario table
    (/root/reference/nomad/eval_broker_test.go): nack-timer redelivery
    accounting, delivery-limit -> `_failed` lifecycle, token rotation,
    and ordering guarantees."""

    def test_nack_timer_redeliveries_count_toward_limit(self):
        """TestEvalBroker_Nack_Timeout + delivery limit: redeliveries
        caused by the nack TIMER (a worker died silently) are deliveries
        too — enough of them routes the eval to `_failed`, it does not
        ping-pong forever."""
        b = EvalBroker(nack_timeout=0.05, delivery_limit=2)
        b.set_enabled(True)
        ev = make_eval()
        b.enqueue(ev)
        for _ in range(2):  # two deliveries, neither acked
            got, _token = b.dequeue(["service"], timeout=1)
            assert got.id == ev.id
            wait_until(lambda: b.stats()["total_ready"] == 1,
                       msg="nack timer requeue")
        # Past the limit: the timer's own nack routed it to _failed.
        got, token = b.dequeue(["_failed"], timeout=1)
        assert got.id == ev.id
        b.ack(ev.id, token)

    def test_token_rotates_on_timer_redelivery(self):
        """After a nack-timer redelivery the OLD delivery token is dead:
        a zombie worker acking with it must be rejected, and
        `outstanding` reports the new token."""
        b = EvalBroker(nack_timeout=0.05, delivery_limit=5)
        b.set_enabled(True)
        ev = make_eval()
        b.enqueue(ev)
        _got, token1 = b.dequeue(["service"], timeout=1)
        wait_until(lambda: b.stats()["total_ready"] == 1,
                   msg="nack timer requeue")
        _got2, token2 = b.dequeue(["service"], timeout=1)
        assert token1 != token2
        out_token, ok = b.outstanding(ev.id)
        assert ok and out_token == token2
        with pytest.raises(ValueError):
            b.ack(ev.id, token1)
        b.ack(ev.id, token2)

    def test_failed_queue_ack_releases_job_serialization(self):
        """TestEvalBroker_DeliveryLimit: an eval nacked past the limit is
        dequeued from `_failed` like any queue; acking it releases the
        per-job serialization so the job's NEXT eval flows."""
        b = EvalBroker(nack_timeout=5, delivery_limit=1)
        b.set_enabled(True)
        ev = make_eval()
        b.enqueue(ev)
        got, token = b.dequeue(["service"], timeout=1)
        b.nack(ev.id, token)
        # Delivery limit 1: straight to the failed queue.
        stats = b.stats()
        assert stats["by_scheduler"].get("_failed") == 1
        got, token = b.dequeue(["_failed"], timeout=1)
        assert got.id == ev.id
        # While outstanding from _failed, a sibling eval stays blocked.
        ev2 = make_eval(job_id=ev.job_id)
        b.enqueue(ev2)
        assert b.stats()["total_blocked"] == 1
        b.ack(ev.id, token)
        got2, token2 = b.dequeue(["service"], timeout=1)
        assert got2.id == ev2.id
        b.ack(ev2.id, token2)
        assert b.stats()["total_ready"] == 0

    def test_fifo_within_priority(self):
        """TestEvalBroker_Dequeue_FIFO: same priority drains in create
        order (create_index ascending)."""
        b = EvalBroker(5, 3)
        b.set_enabled(True)
        evs = []
        for i in range(5):
            ev = make_eval(priority=50)
            ev.create_index = 100 + i
            evs.append(ev)
        for ev in reversed(evs):  # enqueue newest first on purpose
            b.enqueue(ev)
        got = [b.dequeue(["service"], timeout=1)[0].id for _ in evs]
        assert got == [ev.id for ev in evs]

    def test_blocked_promotion_is_priority_ordered(self):
        """Blocked same-job evals promote highest-priority first when the
        in-flight eval acks (PendingEvaluations heap ordering)."""
        b = EvalBroker(5, 3)
        b.set_enabled(True)
        first = make_eval(priority=50)
        b.enqueue(first)
        low = make_eval(priority=10, job_id=first.job_id)
        high = make_eval(priority=90, job_id=first.job_id)
        b.enqueue(low)
        b.enqueue(high)
        got, token = b.dequeue(["service"], timeout=1)
        assert got.id == first.id
        assert b.stats()["total_blocked"] == 2
        b.ack(first.id, token)
        got2, token2 = b.dequeue(["service"], timeout=1)
        assert got2.id == high.id
        b.ack(high.id, token2)
        got3, token3 = b.dequeue(["service"], timeout=1)
        assert got3.id == low.id
        b.ack(low.id, token3)

    def test_nack_resets_delivery_token_immediately(self):
        """An explicit Nack invalidates the old token synchronously (no
        timer involved) — the redelivered eval carries a fresh one."""
        b = EvalBroker(nack_timeout=5, delivery_limit=3)
        b.set_enabled(True)
        ev = make_eval()
        b.enqueue(ev)
        _got, token1 = b.dequeue(["service"], timeout=1)
        b.nack(ev.id, token1)
        _token, ok = b.outstanding(ev.id)
        assert not ok  # nothing outstanding until redelivered
        _got2, token2 = b.dequeue(["service"], timeout=1)
        assert token2 != token1
        with pytest.raises(ValueError):
            b.ack(ev.id, token1)
        b.ack(ev.id, token2)


# ---------------------------------------------------------------------------
# PlanQueue
# ---------------------------------------------------------------------------

def test_worker_unblocks_when_plan_queue_dies(monkeypatch):
    """A worker awaiting a plan future whose applier died (leadership
    loss mid-pop) must error out once the queue is closed, not block
    forever — a parked worker pins its dispatch's gc_pause for the
    process lifetime (runtime-sanitizer regression)."""
    from nomad_tpu.server import worker as worker_mod

    monkeypatch.setattr(worker_mod, "PLAN_WAIT_POLL", 0.05)
    pq = PlanQueue()
    pq.set_enabled(True)

    class FakeServer:
        plan_queue = pq

    w = worker_mod.Worker(FakeServer())
    future = pq.enqueue(Plan())
    pending = pq.dequeue(timeout=1)   # the applier popped it...
    assert pending is not None
    pq.set_enabled(False)             # ...then leadership died: no respond
    with pytest.raises(RuntimeError, match="plan queue closed"):
        w._wait_plan(future)


class TestPlanQueue:
    def test_priority_order_and_future(self):
        q = PlanQueue()
        q.set_enabled(True)
        f1 = q.enqueue(Plan(priority=10))
        f2 = q.enqueue(Plan(priority=90))
        first = q.dequeue(timeout=1)
        assert first.plan.priority == 90
        second = q.dequeue(timeout=1)
        assert second.plan.priority == 10
        # future round trip
        from nomad_tpu.structs import PlanResult
        result = PlanResult(alloc_index=7)
        first.respond(result)
        assert f2.wait(1).alloc_index == 7

    def test_flush_fails_waiters(self):
        q = PlanQueue()
        q.set_enabled(True)
        f = q.enqueue(Plan())
        q.set_enabled(False)
        with pytest.raises(RuntimeError):
            f.wait(1)


# ---------------------------------------------------------------------------
# TimeTable
# ---------------------------------------------------------------------------

def test_timetable_witness_and_lookup():
    tt = TimeTable(granularity=10, limit=3)
    tt.witness(10, 100.0)
    tt.witness(20, 200.0)
    tt.witness(30, 300.0)
    tt.witness(25, 305.0)  # lower index ignored
    assert tt.nearest_index(250.0) == 20
    assert tt.nearest_index(50.0) == 0
    assert tt.nearest_index(1000.0) == 30
    rows = tt.serialize()
    tt2 = TimeTable()
    tt2.deserialize(rows)
    assert tt2.nearest_index(250.0) == 20


# ---------------------------------------------------------------------------
# FSM
# ---------------------------------------------------------------------------

class TestFSM:
    def test_apply_and_snapshot_roundtrip(self):
        fsm = NomadFSM()
        node = mock.node()
        job = mock.job()
        fsm.apply(1, codec.encode(codec.NODE_REGISTER_REQUEST,
                                  {"node": node.to_dict()}))
        fsm.apply(2, codec.encode(codec.JOB_REGISTER_REQUEST,
                                  {"job": job.to_dict()}))
        ev = make_eval(job_id=job.id)
        fsm.apply(3, codec.encode(codec.EVAL_UPDATE_REQUEST,
                                  {"evals": [ev.to_dict()]}))
        alloc = mock.alloc()
        alloc.node_id = node.id
        fsm.apply(4, codec.encode(codec.ALLOC_UPDATE_REQUEST,
                                  {"alloc": [alloc.to_dict()]}))

        blob = fsm.snapshot()
        fsm2 = NomadFSM()
        fsm2.restore(blob)
        assert fsm2.state.node_by_id(node.id).name == node.name
        assert fsm2.state.job_by_id(job.id).name == job.name
        assert fsm2.state.eval_by_id(ev.id) is not None
        restored = fsm2.state.alloc_by_id(alloc.id)
        assert restored.resources.cpu == alloc.resources.cpu
        assert restored.job.task_groups[0].tasks[0].name == "web"

    def test_eval_apply_enqueues_into_broker(self):
        broker = EvalBroker(5, 3)
        broker.set_enabled(True)
        fsm = NomadFSM(eval_broker=broker)
        ev = make_eval()
        fsm.apply(1, codec.encode(codec.EVAL_UPDATE_REQUEST,
                                  {"evals": [ev.to_dict()]}))
        got, _ = broker.dequeue(["service"], timeout=1)
        assert got.id == ev.id

    def test_unknown_type(self):
        fsm = NomadFSM()
        with pytest.raises(ValueError):
            fsm.apply(1, codec.encode(99, {}))
        # ignorable flag: no error
        fsm.apply(2, codec.encode(99 | codec.IGNORE_UNKNOWN_TYPE_FLAG, {}))

    def test_apply_every_remaining_type(self):
        """The apply table rows not covered above: node deregister /
        status / drain, job deregister, eval delete (reference
        fsm_test.go:100-366)."""
        fsm = NomadFSM()
        node = mock.node()
        fsm.apply(1, codec.encode(codec.NODE_REGISTER_REQUEST,
                                  {"node": node.to_dict()}))
        fsm.apply(2, codec.encode(codec.NODE_UPDATE_STATUS_REQUEST,
                                  {"node_id": node.id, "status": "down"}))
        assert fsm.state.node_by_id(node.id).status == "down"
        fsm.apply(3, codec.encode(codec.NODE_UPDATE_DRAIN_REQUEST,
                                  {"node_id": node.id, "drain": True}))
        assert fsm.state.node_by_id(node.id).drain is True
        fsm.apply(4, codec.encode(codec.NODE_DEREGISTER_REQUEST,
                                  {"node_id": node.id}))
        assert fsm.state.node_by_id(node.id) is None

        job = mock.job()
        fsm.apply(5, codec.encode(codec.JOB_REGISTER_REQUEST,
                                  {"job": job.to_dict()}))
        fsm.apply(6, codec.encode(codec.JOB_DEREGISTER_REQUEST,
                                  {"job_id": job.id}))
        assert fsm.state.job_by_id(job.id) is None

        ev = make_eval()
        alloc = mock.alloc()
        alloc.eval_id = ev.id
        fsm.apply(7, codec.encode(codec.EVAL_UPDATE_REQUEST,
                                  {"evals": [ev.to_dict()]}))
        fsm.apply(8, codec.encode(codec.ALLOC_UPDATE_REQUEST,
                                  {"alloc": [alloc.to_dict()]}))
        fsm.apply(9, codec.encode(codec.EVAL_DELETE_REQUEST,
                                  {"evals": [ev.id],
                                   "allocs": [alloc.id]}))
        assert fsm.state.eval_by_id(ev.id) is None
        assert fsm.state.alloc_by_id(alloc.id) is None
        assert fsm.state.get_index("evals") == 9

    def test_snapshot_restores_timetable(self):
        """TimeTable witnesses ride the snapshot so GC cutoffs survive a
        restore (reference fsm_test.go:590-626)."""
        fsm = NomadFSM()
        fsm.timetable.granularity = 0.0
        fsm.timetable.witness(1000, 12345.0)
        fsm.timetable.witness(2000, 23456.0)
        blob = fsm.snapshot()
        fsm2 = NomadFSM()
        fsm2.restore(blob)
        assert fsm2.timetable.nearest_index(20000.0) == 1000
        assert fsm2.timetable.nearest_index(30000.0) == 2000

    def test_client_update_merges_status_only(self):
        fsm = NomadFSM()
        alloc = mock.alloc()
        fsm.apply(1, codec.encode(codec.ALLOC_UPDATE_REQUEST,
                                  {"alloc": [alloc.to_dict()]}))
        update = alloc.copy()
        update.client_status = "running"
        update.desired_status = "SHOULD-NOT-MOVE"
        fsm.apply(2, codec.encode(codec.ALLOC_CLIENT_UPDATE_REQUEST,
                                  {"alloc": [update.to_dict()]}))
        stored = fsm.state.alloc_by_id(alloc.id)
        assert stored.client_status == "running"
        assert stored.desired_status == alloc.desired_status


# ---------------------------------------------------------------------------
# Durable raft backend
# ---------------------------------------------------------------------------

def test_raft_log_replay_and_snapshot(tmp_path):
    from nomad_tpu.server.raft import FileLogStore, SnapshotStore

    log = FileLogStore(str(tmp_path / "log.bin"))
    fsm = NomadFSM()
    raft = InmemRaft(fsm, log)
    node = mock.node()
    raft.apply(codec.encode(codec.NODE_REGISTER_REQUEST,
                            {"node": node.to_dict()})).wait(1)
    log.close()

    # Reboot: replay from disk.
    fsm2 = NomadFSM()
    raft2 = InmemRaft(fsm2, FileLogStore(str(tmp_path / "log.bin")))
    assert raft2.applied_index() == 1
    assert fsm2.state.node_by_id(node.id) is not None


# ---------------------------------------------------------------------------
# Full pipeline: Server end-to-end
# ---------------------------------------------------------------------------

def make_server(**kw) -> Server:
    kw.setdefault("num_schedulers", 2)
    cfg = ServerConfig(**kw)
    srv = Server(cfg)
    srv.establish_leadership()
    return srv


class TestWorker:
    def test_pause_holds_work_until_resume(self):
        """A paused worker leaves ready evals on the broker; resuming
        drains them (reference worker.go:77-93 — the leader pauses one
        worker to reserve CPU for its own duties)."""
        srv = Server(ServerConfig(num_schedulers=1))
        srv.establish_leadership()
        try:
            srv.node_register(mock.node())
            worker = srv.workers[0]
            worker.set_pause(True)
            # Outwait an in-flight dequeue (0.25s timeout) started
            # before the pause flag was set: the loop only re-checks
            # the gate between iterations.
            time.sleep(0.4)  # sleep-ok: outwait the in-flight dequeue
            job = mock.job()
            _, eval_id = srv.job_register(job)
            time.sleep(0.4)  # sleep-ok: prove the ABSENCE of processing
            ev = srv.fsm.state.eval_by_id(eval_id)
            assert ev.status == "pending", "paused worker processed eval"
            worker.set_pause(False)
            srv.wait_for_evals([eval_id], timeout=10)
            assert srv.fsm.state.eval_by_id(eval_id).status == "complete"
        finally:
            srv.shutdown()

    def test_wait_for_index_times_out_on_lagging_fsm(self):
        """An eval whose modify_index outruns the local FSM must not be
        scheduled from a stale snapshot; past the sync limit the worker
        gives up (reference worker.go:209-230)."""
        from nomad_tpu.server.worker import Worker

        srv = Server(ServerConfig(num_schedulers=0))
        srv.establish_leadership()
        try:
            w = Worker(srv)
            far_future = srv.raft.applied_index() + 1000
            with pytest.raises(TimeoutError):
                w._wait_for_index(far_future, timeout=0.2)
            # An already-applied index returns immediately.
            w._wait_for_index(srv.raft.applied_index(), timeout=0.2)
        finally:
            srv.shutdown()


class TestPlanTokenFencing:
    def test_stale_or_wrong_token_plans_rejected(self):
        """The plan applier is the split-brain fence: a plan whose eval
        token doesn't match the outstanding delivery — or whose eval is
        no longer outstanding at all — must be refused before touching
        state (reference plan_apply.go:53-65)."""
        srv = Server(ServerConfig(num_schedulers=0))
        srv.establish_leadership()
        try:
            srv.node_register(mock.node())
            ev = make_eval()
            srv.apply_eval_update([ev])
            got, token = srv.eval_broker.dequeue(["service"], timeout=2)
            assert got.id == ev.id

            # Wrong token (another scheduler's claim): rejected.
            plan = got.make_plan(None)
            plan.eval_token = "not-the-token"
            future = srv.plan_queue.enqueue(plan)
            with pytest.raises(RuntimeError, match="token does not"):
                future.wait(5.0)

            # Right token while outstanding: accepted (empty plan).
            plan2 = got.make_plan(None)
            plan2.eval_token = token
            result = srv.plan_queue.enqueue(plan2).wait(5.0)
            assert result is not None

            # After ack the eval is no longer outstanding: even the
            # once-valid token is fenced out.
            srv.eval_broker.ack(got.id, token)
            plan3 = got.make_plan(None)
            plan3.eval_token = token
            with pytest.raises(RuntimeError, match="not outstanding"):
                srv.plan_queue.enqueue(plan3).wait(5.0)
        finally:
            srv.shutdown()


class TestServerEndToEnd:
    def test_job_register_schedules_allocs(self):
        srv = make_server()
        try:
            for i in range(5):
                srv.node_register(mock.node(i))
            job = mock.job()
            job.task_groups[0].count = 5
            _, eval_id = srv.job_register(job)
            statuses = srv.wait_for_evals([eval_id], timeout=15)
            assert statuses[eval_id] == "complete"
            allocs = srv.fsm.state.allocs_by_job(job.id)
            placed = [a for a in allocs if a.node_id]
            assert len(placed) == 5
            # Spread across nodes by anti-affinity.
            assert len({a.node_id for a in placed}) == 5
        finally:
            srv.shutdown()

    def test_job_register_device_scheduler_off(self):
        srv = make_server(use_device_scheduler=False)
        try:
            for i in range(4):
                srv.node_register(mock.node(i))
            job = mock.job()
            job.task_groups[0].count = 4
            _, eval_id = srv.job_register(job)
            statuses = srv.wait_for_evals([eval_id], timeout=15)
            assert statuses[eval_id] == "complete"
            assert len(srv.fsm.state.allocs_by_job(job.id)) == 4
        finally:
            srv.shutdown()

    def test_device_unavailable_fails_the_boot(self, monkeypatch):
        """A server asked for the device scheduler on a host whose JAX
        backend cannot hand out devices does not boot — degrading to
        the sequential schedulers would hide the missing chip behind a
        working, slow cluster.  use_device_scheduler=False is how an
        operator asks for them."""
        from nomad_tpu.parallel import devices
        from nomad_tpu.server.worker import BatchWorker

        def no_backend():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(devices, "default_platform_devices",
                            no_backend)
        before = threading.active_count()
        with pytest.raises(RuntimeError, match="initialize backend"):
            Server(ServerConfig(num_schedulers=2))
        # Refused before any thread or socket of the server existed.
        assert threading.active_count() == before
        srv = make_server(use_device_scheduler=False)
        try:
            assert not any(isinstance(w, BatchWorker)
                           for w in srv.workers)
        finally:
            srv.shutdown()

    def test_concurrent_jobs_no_oversubscription(self):
        from nomad_tpu.structs import allocs_fit

        srv = make_server()
        try:
            nodes = [mock.node(i) for i in range(4)]
            for n in nodes:
                srv.node_register(n)
            eval_ids, jobs = [], []
            for _ in range(6):
                job = mock.job()
                job.task_groups[0].count = 2
                job.task_groups[0].tasks[0].resources.cpu = 800
                _, eid = srv.job_register(job)
                jobs.append(job)
                eval_ids.append(eid)
            srv.wait_for_evals(eval_ids, timeout=20)
            # The plan applier must never commit an oversubscribed node.
            state = srv.fsm.state
            for node in nodes:
                allocs = [a for a in state.allocs_by_node(node.id)
                          if not a.terminal_status() and a.node_id]
                fit, dim, _ = allocs_fit(node, allocs)
                assert fit, f"node oversubscribed: {dim}"
        finally:
            srv.shutdown()

    def test_job_deregister_stops_allocs(self):
        srv = make_server()
        try:
            for i in range(3):
                srv.node_register(mock.node(i))
            job = mock.job()
            job.task_groups[0].count = 3
            _, e1 = srv.job_register(job)
            srv.wait_for_evals([e1], timeout=15)
            _, e2 = srv.job_deregister(job.id)
            srv.wait_for_evals([e2], timeout=15)
            allocs = srv.fsm.state.allocs_by_job(job.id)
            stopped = [a for a in allocs if a.desired_status == "stop"]
            assert len(stopped) == 3
        finally:
            srv.shutdown()


class TestNodeLifecycle:
    def test_node_down_triggers_migration(self):
        srv = make_server()
        try:
            nodes = [mock.node(i) for i in range(4)]
            for n in nodes:
                srv.node_register(n)
            job = mock.job()
            job.task_groups[0].count = 2
            _, e1 = srv.job_register(job)
            srv.wait_for_evals([e1], timeout=15)
            placed = {a.node_id for a in srv.fsm.state.allocs_by_job(job.id)}

            victim = next(iter(placed))
            srv.node_update_status(victim, "down")
            # A node-update eval per affected job reschedules the allocs.
            def migrated():
                allocs = srv.fsm.state.allocs_by_job(job.id)
                live = [a for a in allocs if not a.terminal_status()]
                return len(live) == 2 and all(
                    a.node_id != victim for a in live)

            wait_until(migrated, msg="allocs migrated off the down node")
        finally:
            srv.shutdown()

    def test_drain_migrates_allocs(self):
        srv = make_server()
        try:
            for i in range(3):
                srv.node_register(mock.node(i))
            job = mock.job()
            job.task_groups[0].count = 1
            _, e1 = srv.job_register(job)
            srv.wait_for_evals([e1], timeout=15)
            alloc = srv.fsm.state.allocs_by_job(job.id)[0]

            srv.node_update_drain(alloc.node_id, True)
            def migrated():
                live = [a for a in srv.fsm.state.allocs_by_job(job.id)
                        if not a.terminal_status()]
                return bool(live) and all(
                    a.node_id != alloc.node_id for a in live)

            wait_until(migrated, msg="alloc migrated off drained node")
        finally:
            srv.shutdown()

    def test_heartbeat_ttl_and_expiry(self):
        srv = make_server()
        srv.heartbeats.min_ttl = 0.1
        srv.heartbeats.grace = 0.05
        try:
            node = mock.node()
            srv.node_register(node)
            ttl = srv.node_heartbeat(node.id)
            assert ttl >= 0.1
            # Stop heartbeating: the node must be marked down.
            wait_until(
                lambda: srv.fsm.state.node_by_id(node.id).status == "down",
                timeout=5, msg="node marked down after TTL")
        finally:
            srv.shutdown()

    def test_heartbeat_ttl_rate_scaled(self):
        """TTL stretches with fleet size so aggregate heartbeat rate
        stays under max_rate (reference heartbeat.go:37-72,
        MaxHeartbeatsPerSecond=50)."""
        from nomad_tpu.server.heartbeat import HeartbeatManager

        hb = HeartbeatManager(server=None)
        try:
            # Small fleet: the 10s floor dominates (jitter adds <= 1/16).
            ttl = hb.reset_heartbeat_timer("n-small")
            assert 10.0 <= ttl <= 10.0 * (1 + 1 / 16)
            # ~1000-node fleet: ttl >= n/50 (~20s), so at most 50
            # heartbeats/s arrive in aggregate.  Seed the timer table
            # with inert entries — the math only reads len().
            class _Inert:
                def cancel(self):
                    pass
            for i in range(1000):
                hb._timers[f"n-{i}"] = _Inert()
            base = hb.active() / hb.max_rate
            ttl = hb.reset_heartbeat_timer("n-0")
            assert base <= ttl <= base * (1 + 1 / 16)
        finally:
            hb.clear()

    def test_failover_rearms_all_nodes_at_long_ttl(self):
        """A new leader can't know when the last heartbeats happened, so
        initialize() re-arms every live node at the failover TTL
        (heartbeat.go:21-35)."""
        srv = make_server()
        try:
            for i in range(3):
                srv.node_register(mock.node(i))
            down = mock.node(9)
            srv.node_register(down)
            srv.node_update_status(down.id, "down")
            srv.heartbeats.clear()
            assert srv.heartbeats.active() == 0
            srv.heartbeats.initialize()
            # Live nodes re-armed; the down node is not.
            assert srv.heartbeats.active() == 3
        finally:
            srv.shutdown()

    def test_system_job_runs_everywhere(self):
        srv = make_server()
        try:
            for i in range(3):
                srv.node_register(mock.node(i))
            job = mock.system_job()
            _, e1 = srv.job_register(job)
            srv.wait_for_evals([e1], timeout=15)
            allocs = srv.fsm.state.allocs_by_job(job.id)
            assert len({a.node_id for a in allocs}) == 3
            # A new node joining gets the system job via node evals.
            late = mock.node(99)
            srv.node_register(late)
            eval_ids = srv.node_evaluate(late.id)
            srv.wait_for_evals(eval_ids, timeout=15)
            allocs = [a for a in srv.fsm.state.allocs_by_job(job.id)
                      if not a.terminal_status()]
            assert len({a.node_id for a in allocs}) == 4
        finally:
            srv.shutdown()


class TestLeaderLifecycle:
    def test_reap_failed_eval(self):
        """An eval nacked past the delivery limit lands in the failed
        queue and the leader's reaper marks it failed in replicated
        state (reference leader_test.go:309-360)."""
        srv = make_server(num_schedulers=0, eval_delivery_limit=1)
        try:
            ev = mock.eval()
            srv.eval_broker.enqueue(ev)
            out, token = srv.eval_broker.dequeue(["service"], timeout=2)
            assert out.id == ev.id
            srv.eval_broker.nack(out.id, token)

            srv.wait_for_evals([ev.id], timeout=10)
            got = srv.fsm.state.eval_by_id(ev.id)
            assert got.status == "failed"
            assert "delivery limit" in got.status_description
        finally:
            srv.shutdown()

    def test_periodic_dispatch_enqueues_core_evals(self):
        """Tiny GC intervals: the leader's periodic loop mints _core
        evals for eval-gc and node-gc (reference
        leader_test.go:289-307 + leader.go:171-199)."""
        from nomad_tpu.structs import CORE_JOB_EVAL_GC, CORE_JOB_NODE_GC

        srv = make_server(num_schedulers=0, eval_gc_interval=0.05,
                          node_gc_interval=0.05)
        try:
            seen = set()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and len(seen) < 2:
                ev, token = srv.eval_broker.dequeue(["_core"],
                                                    timeout=0.5)
                if ev is not None:
                    seen.add(ev.job_id)
                    srv.eval_broker.ack(ev.id, token)
            assert seen == {CORE_JOB_EVAL_GC, CORE_JOB_NODE_GC}
        finally:
            srv.shutdown()


class TestCoreGC:
    def test_eval_gc_reaps_old_terminal_evals(self):
        from nomad_tpu.server.core_sched import CoreScheduler
        from nomad_tpu.structs import CORE_JOB_EVAL_GC

        srv = make_server()
        srv.config.eval_gc_threshold = 0.0  # everything is old
        try:
            srv.node_register(mock.node())
            job = mock.job()
            job.task_groups[0].count = 1
            _, e1 = srv.job_register(job)
            srv.wait_for_evals([e1], timeout=15)
            _, e2 = srv.job_deregister(job.id)
            srv.wait_for_evals([e2], timeout=15)
            # Mark allocs terminal via client update so GC can take them.
            for a in srv.fsm.state.allocs_by_job(job.id):
                up = a.copy()
                up.client_status = "dead"
                srv.raft_apply(codec.ALLOC_CLIENT_UPDATE_REQUEST,
                               {"alloc": [up.to_dict()]})
            # Force the timetable to see current indexes as old (bypass the
            # 5-minute witness granularity).
            srv.fsm.timetable.granularity = 0.0
            srv.fsm.timetable.witness(srv.raft.applied_index() + 1,
                                      time.time())

            gc_eval = Evaluation(id=generate_uuid(), type="_core",
                                 job_id=CORE_JOB_EVAL_GC)
            CoreScheduler(srv, srv.fsm.state.snapshot()).process(gc_eval)
            assert srv.fsm.state.eval_by_id(e1) is None
            assert srv.fsm.state.eval_by_id(e2) is None
        finally:
            srv.shutdown()

    def test_node_gc_deregisters_down_empty_nodes(self):
        """Down nodes with no remaining allocs are deregistered; down
        nodes still carrying allocs, and ready nodes, survive
        (reference nomad/core_sched_test.go:72-130)."""
        from nomad_tpu.server.core_sched import CoreScheduler
        from nomad_tpu.structs import CORE_JOB_NODE_GC, NODE_STATUS_DOWN

        srv = make_server()
        srv.config.node_gc_threshold = 0.0
        try:
            empty_down = mock.node(1)
            busy_down = mock.node(2)
            alive = mock.node(3)
            for n in (empty_down, busy_down, alive):
                srv.node_register(n)
            # An alloc pins busy_down.
            a = mock.alloc()
            a.node_id = busy_down.id
            srv.raft_apply(codec.ALLOC_UPDATE_REQUEST,
                           {"alloc": [a.to_dict()]})
            for nid in (empty_down.id, busy_down.id):
                srv.raft_apply(codec.NODE_UPDATE_STATUS_REQUEST,
                               {"node_id": nid,
                                "status": NODE_STATUS_DOWN})
            srv.fsm.timetable.granularity = 0.0
            srv.fsm.timetable.witness(srv.raft.applied_index() + 1,
                                      time.time())
            gc_eval = Evaluation(id=generate_uuid(), type="_core",
                                 job_id=CORE_JOB_NODE_GC)
            CoreScheduler(srv, srv.fsm.state.snapshot()).process(gc_eval)
            state = srv.fsm.state
            assert state.node_by_id(empty_down.id) is None
            assert state.node_by_id(busy_down.id) is not None
            assert state.node_by_id(alive.id) is not None
        finally:
            srv.shutdown()
