"""Trace & telemetry plane (nomad_tpu/obs/): ISSUE 10.

Four layers:

1. **Tracer units** — seedable ids, per-thread buffers, ring
   bound/overflow accounting, ambient nesting, Chrome-trace export
   shape, and the disabled-path contract (one module bool).
2. **Registry units** — the flatten grammar, provider replace/
   deregister, erroring-provider isolation, publish-to-metrics.
3. **Flight recorder** — incident file shape and bounds, rate limit,
   on-disk pruning, the stall watchdog, and the real triggers
   (breaker-open, overload entry).
4. **Span trees on a live server** — every terminal eval has a closed,
   single-rooted span tree even under seeded rpc drops and raft-apply
   faults with plan retries; exactly-once upsert spans for exactly-once
   placements; and one seeded chaos eval exports a Chrome trace
   spanning agent edge -> broker -> scheduler stages -> window verify
   -> raft apply -> store upsert (the ISSUE acceptance bar).

Plus the tier-1 tracing-overhead assertion: a generous structural
bound on a small stream, so a hot-path instrumentation regression
fails tier-1.
"""
from __future__ import annotations

import json
import os
import threading
import time

import pytest

import nomad_tpu.mock as mock
from nomad_tpu import faultinject
from nomad_tpu.faultinject import FaultPlan
from nomad_tpu.obs import flight, registry, trace
from nomad_tpu.obs.registry import MetricsRegistry, flatten
from nomad_tpu.obs.trace import Tracer
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.rpc import ConnPool
from nomad_tpu.structs import Resources, Task, TaskGroup
from nomad_tpu.utils.retry import RetryPolicy

from tests.conftest import wait_until

TERMINAL = ("complete", "failed", "canceled")


def _job(n_groups: int = 2, count: int = 1):
    job = mock.job()
    job.task_groups = [
        TaskGroup(name=f"tg-{g}", count=count,
                  tasks=[Task(name="web", driver="exec",
                              resources=Resources(cpu=100,
                                                  memory_mb=32))])
        for g in range(n_groups)]
    return job


# ---------------------------------------------------------------------------
# 1. tracer units
# ---------------------------------------------------------------------------

class TestTracerUnits:
    def test_seeded_ids_are_deterministic(self):
        a, b = Tracer(seed=7), Tracer(seed=7)
        assert [a.new_id() for _ in range(5)] == \
            [b.new_id() for _ in range(5)]
        assert Tracer(seed=8).new_id() != Tracer(seed=7).new_id()

    def test_span_timestamps_are_monotonic_deltas(self):
        t = Tracer(seed=1)
        with t.span("a"):
            pass
        span = t.snapshot()[0]
        # Tracer-epoch relative, not wall: a fresh tracer's first span
        # starts near zero regardless of the wall clock.
        assert 0.0 <= span["t0"] < 60.0
        assert span["dur"] >= 0.0

    def test_ambient_nesting_links_parents(self):
        t = Tracer(seed=1)
        with t.span("outer") as outer:
            with t.span("inner") as inner:
                assert t.ctx() == inner
            assert t.ctx() == outer
        assert t.ctx() is None
        by_name = {s["name"]: s for s in t.snapshot()}
        assert by_name["inner"]["parent_id"] == \
            by_name["outer"]["span_id"]
        assert by_name["inner"]["trace_id"] == \
            by_name["outer"]["trace_id"]
        assert by_name["outer"]["parent_id"] is None

    def test_attach_adopts_cross_thread_context(self):
        t = Tracer(seed=1)
        ctx = t.anchor("eval.created", eval_id="e1")
        done = threading.Event()

        def worker():
            with t.attach(ctx):
                with t.span("work"):
                    pass
            done.set()

        th = threading.Thread(target=worker)
        th.start()
        th.join(5.0)
        assert done.is_set()
        by_name = {s["name"]: s for s in t.snapshot()}
        assert by_name["work"]["parent_id"] == ctx["span_id"]
        assert by_name["work"]["trace_id"] == ctx["trace_id"]

    def test_ring_bound_and_overflow_accounting(self):
        t = Tracer(seed=1, ring=8)
        for i in range(200):
            t.record("s", 0.0, 0.0)
        st = t.stats()
        # 3 full thread-buffer flushes (64 spans each) hit the ring;
        # the ring keeps the newest 8 and counts every drop.
        assert st["ring"] == 8
        assert st["dropped"] == 192 - 8
        assert st["buffered"] == 200 - 192
        assert st["recorded"] == 200
        assert len(t.snapshot()) == 16  # ring + still-buffered

    def test_dead_thread_buffers_fold_into_ring(self):
        t = Tracer(seed=1)

        def worker():
            t.record("from-thread", 0.0, 0.0)

        th = threading.Thread(target=worker)
        th.start()
        th.join(5.0)
        names = [s["name"] for s in t.snapshot()]
        assert "from-thread" in names
        # The dead thread's buffer was folded; a second snapshot must
        # not double-report it.
        assert [s["name"] for s in t.snapshot()].count("from-thread") == 1

    def test_dead_thread_buffers_pruned_without_snapshot(self):
        """Short-lived recording threads (the applier's per-window
        respond thread) must not grow the buffer registry on an
        always-on tracer nobody snapshots: each NEW thread's
        registration sweeps the dead ones into the ring."""
        t = Tracer(seed=1)
        for _ in range(20):
            th = threading.Thread(
                target=lambda: t.record("s", 0.0, 0.0))
            th.start()
            th.join(5.0)
        with t._lock:
            live_bufs = len(t._bufs)
        assert live_bufs <= 2, live_bufs  # newest dead + this thread
        assert t.stats()["recorded"] == 20

    def test_chrome_trace_export_shape(self, tmp_path):
        t = Tracer(seed=1)
        with t.span("rpc.serve.Job.Register", method="Job.Register"):
            t.anchor("eval.created", eval_id="e1")
        path = str(tmp_path / "trace.json")
        n = t.export_chrome(path)
        with open(path) as fh:
            doc = json.load(fh)
        assert n == 2 and len(doc["traceEvents"]) == 2
        for ev in doc["traceEvents"]:
            assert ev["ph"] == "X"
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            assert "span_id" in ev["args"]
        cats = {ev["cat"] for ev in doc["traceEvents"]}
        assert cats == {"rpc", "eval"}

    def test_disabled_is_one_module_bool(self):
        assert trace.ENABLED is False and trace.tracer() is None
        # The no-op module API stays no-op with tracing off.
        args = {"a": 1}
        assert trace.inject(args) is args
        assert trace.ctx() is None
        with trace.client_call("Job.Register", args) as out:
            assert out is args

    def test_envelope_inject_extract_roundtrip(self):
        with trace.tracing(seed=3) as t:
            with t.span("outer"):
                args = trace.inject({"x": 1})
                assert trace.TRACE_KEY in args
                got = trace.extract(args)
                assert got == t.ctx()
            # inject copies: the caller's dict is never mutated.
            original = {"x": 1}
            with t.span("outer2"):
                stamped = trace.inject(original)
                assert stamped is not original
                assert trace.TRACE_KEY not in original


# ---------------------------------------------------------------------------
# 2. registry units
# ---------------------------------------------------------------------------

class TestRegistryUnits:
    def test_flatten_key_grammar(self):
        flat = flatten({"a": 1, "b": {"c": 2.5, "d": {"e": 3}},
                        "on": True, "name": "x", "ws": [1, 2, 3]},
                       "nomad.p")
        assert flat == {"nomad.p.a": 1, "nomad.p.b.c": 2.5,
                        "nomad.p.b.d.e": 3, "nomad.p.on": 1,
                        "nomad.p.name": "x", "nomad.p.ws.len": 3}

    def test_register_snapshot_deregister(self):
        reg = MetricsRegistry()
        tok = reg.register("broker", lambda: {"ready": 4})
        assert reg.snapshot() == {"nomad.broker.ready": 4}
        assert reg.providers() == ["broker"]
        assert reg.deregister(tok)
        assert reg.snapshot() == {} and not reg.deregister(tok)

    def test_same_name_replaces(self):
        reg = MetricsRegistry()
        reg.register("x", lambda: {"v": 1})
        reg.register("x", lambda: {"v": 2})
        assert reg.snapshot() == {"nomad.x.v": 2}
        assert reg.providers() == ["x"]

    def test_erroring_provider_is_isolated(self):
        reg = MetricsRegistry()
        reg.register("bad", lambda: 1 / 0)
        reg.register("good", lambda: {"v": 1})
        snap = reg.snapshot()
        assert snap["nomad.good.v"] == 1
        assert "ZeroDivisionError" in snap["nomad.bad.error"]

    def test_publish_sets_gauges_numeric_only(self):
        from nomad_tpu.utils.metrics import Metrics

        reg = MetricsRegistry()
        reg.register("p", lambda: {"depth": 3, "state": "normal"})
        m = Metrics()
        assert reg.publish(m) == 1
        assert m.inmem.snapshot()["gauges"] == {"nomad.p.depth": 3.0}

    def test_extra_registries_merge(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.register("one", lambda: {"v": 1})
        b.register("two", lambda: {"v": 2})
        assert a.snapshot(extra=[b]) == {"nomad.one.v": 1,
                                         "nomad.two.v": 2}


# ---------------------------------------------------------------------------
# 3. flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_incident_file_shape_and_sections(self, tmp_path):
        reg = MetricsRegistry()
        reg.register("broker", lambda: {"ready": 2})
        with trace.tracing(seed=5) as t:
            t.anchor("eval.created", eval_id="e1")
            with flight.installed(str(tmp_path), registries=[reg]):
                path = flight.trip("breaker.open", {"opens": 1})
        assert path is not None
        with open(path) as fh:
            doc = json.load(fh)
        assert doc["reason"] == "breaker.open"
        assert doc["extra"] == {"opens": 1}
        assert any(s["name"] == "eval.created" for s in doc["spans"])
        # The pprof-goroutine analogue: this very thread's stack shows.
        assert any("test" in k.lower() or "main" in k.lower()
                   for k in doc["thread_stacks"])
        assert doc["metrics"]["providers"]["nomad.broker.ready"] == 2
        assert "counters" in doc["metrics"]["inmem"]

    def test_rate_limit_and_stats(self, tmp_path):
        with flight.installed(str(tmp_path), min_interval=60.0) as rec:
            assert flight.trip("overload.enter") is not None
            assert flight.trip("overload.enter") is None  # suppressed
            assert flight.trip("breaker.open") is not None  # other reason
            st = rec.stats()
            assert st["trips"] == 2 and st["suppressed"] == 1
            assert st["on_disk"] == 2

    def test_on_disk_bound_prunes_oldest(self, tmp_path):
        with flight.installed(str(tmp_path), max_files=3,
                              min_interval=0.0) as rec:
            for i in range(6):
                assert flight.trip(f"r{i}") is not None
            names = rec.incidents()
            assert len(names) == 3
            assert names[-1].startswith("incident-0006")

    def test_span_section_is_bounded(self, tmp_path):
        with trace.tracing(seed=5) as t:
            for _ in range(300):
                t.record("s", 0.0, 0.0)
            with flight.installed(str(tmp_path), max_spans=16):
                path = flight.trip("stall.test")
        with open(path) as fh:
            assert len(json.load(fh)["spans"]) == 16

    def test_stall_watchdog_trips_and_disarm_does_not(self, tmp_path):
        with flight.installed(str(tmp_path)) as rec:
            with flight.guard("fast.section", timeout=5.0):
                pass  # disarmed in time: no incident
            with flight.guard("slow.section", timeout=0.05):
                wait_until(lambda: rec.incidents(), timeout=5.0)
            names = rec.incidents()
            assert len(names) == 1 and "stall.slow.section" in names[0]
        # uninstall joined the watchdog thread.
        assert not any(th.name == "flight-stall-watchdog"
                       for th in threading.enumerate())

    def test_wedged_applier_window_incident_names_its_evals(
            self, tmp_path, monkeypatch):
        """The applier.window stall guard's incident dump says WHAT the
        applier was verifying when the window wedged — the window's
        eval ids — not just that it wedged."""
        import nomad_tpu.mock as mock
        import nomad_tpu.ops.plan_conflict as plan_conflict
        from nomad_tpu.server.eval_broker import EvalBroker
        from nomad_tpu.server.fsm import NomadFSM
        from nomad_tpu.server.plan_apply import PlanApplier
        from nomad_tpu.server.plan_queue import PlanQueue
        from nomad_tpu.server.raft import InmemRaft
        from nomad_tpu.structs import (Allocation, Evaluation, Plan,
                                       Resources, codec, generate_uuid)

        broker = EvalBroker()
        broker.set_enabled(True)
        fsm = NomadFSM(eval_broker=broker)
        raft = InmemRaft(fsm)
        queue = PlanQueue()
        queue.set_enabled(True)
        applier = PlanApplier(queue, broker, raft, lambda: fsm.state)
        applier.WINDOW_STALL_S = 0.05
        node = mock.node()
        raft.apply(codec.encode(codec.NODE_REGISTER_REQUEST,
                                {"node": node.to_dict()})).wait(5.0)
        ev = Evaluation(id=generate_uuid(), priority=50, type="service",
                        job_id=generate_uuid(), status="pending",
                        triggered_by="job-register")
        raft.apply(codec.encode(codec.EVAL_UPDATE_REQUEST,
                                {"evals": [ev.to_dict()]})).wait(5.0)
        _got, token = broker.dequeue(["service"], timeout=2.0)
        plan = Plan(eval_id=ev.id, eval_token=token, priority=50)
        plan.append_alloc(Allocation(
            id=generate_uuid(), node_id=node.id, job_id=ev.job_id,
            task_group="web", resources=Resources(cpu=100, memory_mb=64),
            desired_status="run", client_status="pending"))

        # Wedge the verify itself: the window stays open until released.
        started = threading.Event()
        release = threading.Event()
        verify = plan_conflict.evaluate_window

        def wedged(snap, plans):
            started.set()
            release.wait(10.0)
            return verify(snap, plans)

        monkeypatch.setattr(plan_conflict, "evaluate_window", wedged)
        with flight.installed(str(tmp_path)) as rec:
            future = queue.enqueue(plan)
            applier.start()
            try:
                assert started.wait(5.0)
                wait_until(lambda: rec.incidents(), timeout=5.0)
            finally:
                release.set()
                assert future.wait(5.0).alloc_index > 0
                queue.set_enabled(False)
                applier.shutdown(5.0)
                broker.shutdown()
            names = rec.incidents()
            assert len(names) == 1 and "applier.window" in names[0]
            with open(os.path.join(str(tmp_path), names[0])) as fh:
                doc = json.load(fh)
            assert doc["extra"]["verifying"] == {
                "plans": 1, "eval_ids": [ev.id]}, \
                "the incident must name the wedged window's evals"
            assert "stalled_for_s" in doc["extra"]

    def test_breaker_open_trips(self, tmp_path):
        from nomad_tpu.scheduler.breaker import DeviceCircuitBreaker

        breaker = DeviceCircuitBreaker(failure_threshold=2)
        with flight.installed(str(tmp_path)) as rec:
            breaker.record_failure()
            assert rec.incidents() == []  # below the threshold
            breaker.record_failure()      # CLOSED -> OPEN
            names = rec.incidents()
            assert len(names) == 1 and "breaker.open" in names[0]

    def test_overload_entry_trips(self, tmp_path):
        from nomad_tpu.server.overload import OverloadController

        depth = [0]
        ctrl = OverloadController(brownout_ratio=0.5, overload_ratio=0.9)
        ctrl.add_source("q", lambda: (depth[0], 10))
        with flight.installed(str(tmp_path)) as rec:
            assert ctrl.state() == "normal" and rec.incidents() == []
            depth[0] = 10
            assert ctrl.state() == "overload"
            names = rec.incidents()
            assert len(names) == 1 and "overload.enter" in names[0]
            # Staying in overload is not a new entry edge.
            assert ctrl.state() == "overload"
            assert len(rec.incidents()) == 1

    def test_uninstalled_trip_is_noop(self):
        assert flight.INSTALLED is False
        assert flight.trip("breaker.open") is None


class TestFlightRecorderEdges:
    """ISSUE 14 satellite: the rate-limit window and max_files pruning
    get direct edge-case coverage, and incident JSON carries the
    controller's per-knob positions via the recorder-level extra_fn
    hook."""

    def test_same_reason_burst_rate_limits_per_reason(self, tmp_path):
        clock = [100.0]
        rec = flight.FlightRecorder(str(tmp_path), min_interval=5.0,
                                    clock=lambda: clock[0])
        # A burst of the SAME reason inside the window: one file.
        assert rec.record("control.reversal") is not None
        for _ in range(10):
            assert rec.record("control.reversal") is None
        # A different reason is a different window.
        assert rec.record("control.rail") is not None
        st = rec.stats()
        assert st["trips"] == 2 and st["suppressed"] == 10
        # The window is per-reason AND sliding: advancing past it
        # re-arms exactly that reason.
        clock[0] += 5.1
        assert rec.record("control.reversal") is not None
        assert rec.record("control.reversal") is None

    def test_prune_order_under_mixed_reasons(self, tmp_path):
        """max_files keeps the NEWEST incidents by sequence regardless
        of reason interleaving (the zero-padded seq prefix IS the sort
        key; a burst of reason-B files must evict old reason-A ones)."""
        rec = flight.FlightRecorder(str(tmp_path), max_files=3,
                                    min_interval=0.0)
        reasons = ["overload.enter", "control.rail", "breaker.open",
                   "control.reversal", "stall.applier.window"]
        for reason in reasons:
            assert rec.record(reason) is not None
        names = rec.incidents()
        assert len(names) == 3
        assert [n.split("-")[1] for n in names] == \
            ["0003", "0004", "0005"]
        assert "breaker.open" in names[0]
        assert "stall.applier.window" in names[-1]

    def test_extra_fn_carries_controller_positions(self, tmp_path):
        """Every incident — whatever tripped it — names where every
        control knob sat, via the recorder's extra_fn hook (the
        controller's positions() is the intended payload)."""
        from nomad_tpu.control import AIMD, Actuator, Controller

        ctl = Controller(lambda: {}, interval=0.05)
        state = {"v": 6}
        ctl.add_knob(
            Actuator("pipeline.depth", get=lambda: state["v"],
                     set=lambda v: state.__setitem__("v", v),
                     lo=1, hi=16, integer=True),
            law=AIMD(), driver=lambda view: 0)
        rec = flight.install(str(tmp_path), extra_fn=ctl.positions)
        try:
            path = flight.trip("breaker.open", {"opens": 1})
        finally:
            flight.uninstall()
        with open(path) as fh:
            doc = json.load(fh)
        assert doc["extra"]["opens"] == 1  # the trigger's extra kept
        assert doc["extra"]["context"] == {"pipeline.depth": 6}

    def test_broken_extra_fn_does_not_eat_the_incident(self, tmp_path):
        def boom():
            raise RuntimeError("context bug")
        rec = flight.FlightRecorder(str(tmp_path), extra_fn=boom)
        path = rec.record("breaker.open")
        assert path is not None
        with open(path) as fh:
            assert "context" not in json.load(fh)["extra"]


class TestRegistryCollect:
    """ISSUE 14 satellite: collect() = snapshot() hardened for the
    serving surface — per-provider age_s staleness stamps and a sample
    deadline that isolates a hung provider instead of blocking the
    whole collection."""

    def test_age_stamps_track_value_changes(self):
        clock = [50.0]
        reg = MetricsRegistry(clock=lambda: clock[0])
        live = [0]
        reg.register("live", lambda: {"n": live[0]})
        reg.register("frozen", lambda: {"n": 1})
        reg.collect()
        clock[0] += 10.0
        live[0] += 1
        out = reg.collect()
        assert out["nomad.live.age_s"] == 0.0     # changed this sample
        assert out["nomad.frozen.age_s"] == 10.0  # frozen for 10s
        clock[0] += 5.0
        out = reg.collect()
        assert out["nomad.live.age_s"] == 5.0
        assert out["nomad.frozen.age_s"] == 15.0

    def test_hung_provider_isolated_by_sample_timeout(self):
        reg = MetricsRegistry()
        release = threading.Event()

        def hung():
            release.wait(30.0)
            return {"late": 1}
        reg.register("hung", hung)
        reg.register("fine", lambda: {"ok": 1})
        t0 = time.monotonic()
        out = reg.collect(timeout=0.2)
        try:
            wall = time.monotonic() - t0
            assert wall < 2.0  # the hang never blocks the collection
            assert "timeout" in out["nomad.hung.error"]
            assert out["nomad.fine.ok"] == 1
            # The abandoned sampler's late result can never pollute a
            # LATER collect (its queues died with it).
            release.set()
            out2 = reg.collect(timeout=1.0)
            assert out2.get("nomad.hung.late") == 1
            assert "nomad.hung.error" not in out2
        finally:
            release.set()
            reg.clear()  # reaps the parked sampler thread

    def test_erroring_provider_keeps_its_age_baseline(self):
        clock = [10.0]
        reg = MetricsRegistry(clock=lambda: clock[0])
        fail = [False]

        def flappy():
            if fail[0]:
                raise RuntimeError("torn down")
            return {"n": 1}
        reg.register("flappy", flappy)
        reg.collect()
        clock[0] += 3.0
        fail[0] = True
        out = reg.collect()
        # The .error path still stamps how long the last good value
        # has been standing.
        assert "torn down" in out["nomad.flappy.error"]
        assert out["nomad.flappy.age_s"] == 3.0

    def test_error_path_races_replace_on_name(self):
        """The erroring-provider path racing register() replacing the
        same name: collection never raises, and once the replacement
        lands its staleness clock starts fresh (the successor is not
        blamed for the predecessor's errors)."""
        clock = [0.0]
        reg = MetricsRegistry(clock=lambda: clock[0])

        def broken():
            raise RuntimeError("always failing")
        reg.register("racy", broken)
        stop = threading.Event()
        errors: list = []

        def collector():
            while not stop.is_set():
                try:
                    reg.collect()
                except Exception as e:  # pragma: no cover
                    errors.append(repr(e))

        t = threading.Thread(target=collector, daemon=True)
        t.start()
        try:
            for _ in range(50):
                reg.register("racy", broken)
                reg.register("racy", lambda: {"ok": 1})
        finally:
            stop.set()
            t.join(5.0)
        assert errors == []
        # Replace-on-name resets the age baseline: a provider
        # registered AFTER the collector stopped (so nothing sampled
        # it yet) starts its staleness clock at its own first sample.
        clock[0] = 7.0
        reg.register("racy", lambda: {"ok": 1})
        out = reg.collect()
        assert out["nomad.racy.ok"] == 1
        assert out["nomad.racy.age_s"] == 0.0

    def test_collect_snapshot_parity_and_extra(self):
        reg = MetricsRegistry()
        reg.register("a", lambda: {"x": 1, "flag": True})
        other = MetricsRegistry()
        other.register("b", lambda: {"y": 2})
        snap = reg.snapshot(extra=[other])
        out = reg.collect(extra=[other])
        for key, val in snap.items():
            assert out[key] == val  # same grammar, plus age stamps
        assert "nomad.a.age_s" in out and "nomad.b.age_s" in out


# ---------------------------------------------------------------------------
# 4. span trees on a live server
# ---------------------------------------------------------------------------

def _eval_spans(tracer, eval_id: str) -> list:
    return [s for s in tracer.snapshot()
            if (s.get("tags") or {}).get("eval_id") == eval_id]


def _assert_single_rooted_closed(spans: list, eval_id: str) -> dict:
    """The tree bar: every span closed (a duration, a trace id), ONE
    span whose parent lies outside the eval's set (the anchor hanging
    off the serving RPC), everything else parented within."""
    assert spans, f"eval {eval_id} recorded no spans"
    ids = {s["span_id"] for s in spans}
    assert len(ids) == len(spans), "duplicate span ids"
    roots = [s for s in spans if s["parent_id"] not in ids]
    assert len(roots) == 1, (
        f"eval {eval_id}: want exactly one root, got "
        f"{[(s['name'], s['parent_id']) for s in roots]}")
    assert roots[0]["name"] == "eval.created"
    assert len({s["trace_id"] for s in spans}) == 1
    for s in spans:
        assert s["dur"] >= 0.0
    return roots[0]


class TestSpanTreesLiveServer:
    SUBMIT = RetryPolicy(base=0.1, max_delay=0.5, max_attempts=10,
                         retryable=lambda e: isinstance(e, Exception),
                         name="obs.submit")

    def test_span_trees_complete_under_seeded_faults(self):
        """Seeded rpc.send/rpc.recv drops on submission plus a
        raft.apply error (the plan batch fails once, the broker
        redelivers, the retry commits): every terminal eval still has a
        closed single-rooted tree, and exactly-once placements carry
        exactly-once upsert accounting."""
        plan = FaultPlan.parse(
            "seed=10;"
            "rpc.send=drop(p=0.5,count=2,method=Job.Register);"
            "rpc.recv=drop(p=0.5,count=2,method=Job.Register);"
            "raft.apply=error(after=8,count=1)")
        with trace.tracing(seed=10) as tracer:
            with faultinject.injected(plan):
                srv = Server(ServerConfig(num_schedulers=2,
                                          enable_rpc=True,
                                          eval_nack_timeout=5.0))
                srv.establish_leadership()
                pool = ConnPool()
                try:
                    addr = srv.rpc_address()
                    for i in range(8):
                        self.SUBMIT.call(
                            lambda n=mock.node(i): pool.call(
                                addr, "Node.Register",
                                {"node": n.to_dict()}, timeout=2.0))
                    jobs = [_job(2) for _ in range(6)]
                    eval_ids = []
                    for job in jobs:
                        # timeout=2.0: a recv-dropped frame gets no
                        # reply at all — the retry policy must see a
                        # bounded timeout, not the 330s default.
                        out = self.SUBMIT.call(
                            lambda j=job: pool.call(
                                addr, "Job.Register",
                                {"job": j.to_dict()}, timeout=2.0))
                        eval_ids.append(out["eval_id"])

                    def terminal():
                        return all(
                            (srv.fsm.state.eval_by_id(eid) or
                             mock.job()).status in TERMINAL
                            if srv.fsm.state.eval_by_id(eid) else False
                            for eid in eval_ids)
                    wait_until(terminal, timeout=30.0)

                    state = srv.fsm.state
                    for eid in eval_ids:
                        ev = state.eval_by_id(eid)
                        assert ev.status == "complete", (eid, ev.status)
                        spans = _eval_spans(tracer, eid)
                        _assert_single_rooted_closed(spans, eid)
                        # Exactly-once: each placed alloc id appears
                        # once in state, and the upsert spans account
                        # for every placement exactly once.
                        allocs = [a for a in state.allocs_by_eval(eid)
                                  if a.node_id]
                        assert len({a.id for a in allocs}) == len(allocs)
                        upserts = [s for s in spans
                                   if s["name"] == "store.upsert"]
                        assert upserts, f"eval {eid}: no upsert span"
                        assert sum((s.get("tags") or {})["n_allocs"]
                                   for s in upserts) == len(allocs)
                    # The seeded fault really fired (else this proves
                    # nothing about plan retries).
                    assert plan.fire_count("raft.apply") == 1
                finally:
                    pool.shutdown()
                    srv.shutdown()

    def test_chaos_eval_exports_chrome_trace_across_planes(self,
                                                          tmp_path):
        """ISSUE acceptance: one seeded chaos eval's exported
        Chrome-trace tree spans agent edge -> broker -> scheduler
        stages -> window verify -> raft apply -> store upsert."""
        from nomad_tpu.agent import Agent, AgentConfig

        plan = FaultPlan.parse("seed=11;raft.apply=delay(secs=0.002,p=0.5)")
        with trace.tracing(seed=11) as tracer:
            with faultinject.injected(plan):
                agent = Agent(AgentConfig(server_enabled=True,
                                          http_port=0, rpc_port=0))
                try:
                    srv = agent.server
                    for i in range(8):
                        srv.node_register(mock.node(i))
                    out = agent.rpc("Job.Register",
                                    {"job": _job(3).to_dict()})
                    eval_id = out["eval_id"]
                    wait_until(
                        lambda: (srv.fsm.state.eval_by_id(eval_id)
                                 is not None and
                                 srv.fsm.state.eval_by_id(eval_id)
                                 .status in TERMINAL),
                        timeout=20.0)
                    assert srv.fsm.state.eval_by_id(eval_id).status == \
                        "complete"

                    spans = _eval_spans(tracer, eval_id)
                    root = _assert_single_rooted_closed(spans, eval_id)
                    names = {s["name"] for s in spans}
                    # The full plane walk.  Scheduler stages come from
                    # the fused batch worker (sched.*) or the plain
                    # worker (worker.invoke) depending on the backend.
                    assert "broker.wait" in names
                    assert {"sched.begin", "sched.submit"} <= names or \
                        "worker.invoke" in names
                    assert "applier.verify" in names   # window verify
                    assert "raft.apply" in names
                    assert "fsm.decode" in names
                    assert "store.upsert" in names
                    # Agent edge: the anchor's parent chain reaches the
                    # serving RPC span, whose parent is the in-proc
                    # client span — the trace's root.
                    all_spans = {s["span_id"]: s
                                 for s in tracer.snapshot()}
                    serve = all_spans[root["parent_id"]]
                    assert serve["name"] == "rpc.serve.Job.Register"
                    client = all_spans[serve["parent_id"]]
                    assert client["name"] == "rpc.client.Job.Register"
                    assert client["parent_id"] is None

                    # Export and re-read: the file is Chrome-trace
                    # loadable JSON with the whole walk inside.
                    path = str(tmp_path / "chaos-eval.json")
                    n = tracer.export_chrome(path)
                    with open(path) as fh:
                        doc = json.load(fh)
                    assert len(doc["traceEvents"]) == n >= len(spans)
                    exported = {e["name"] for e in doc["traceEvents"]
                                if e["args"].get("eval_id") == eval_id}
                    assert {"applier.verify", "raft.apply",
                            "store.upsert"} <= exported
                finally:
                    agent.shutdown()

    def test_metrics_endpoint_table(self):
        """/v1/agent/metrics beside the reference agent endpoint table
        (command/agent/http.go route registrations): the unified
        registry document over live HTTP, with every expected provider
        present and the in-mem sink riding along."""
        from nomad_tpu.agent import Agent, AgentConfig
        from nomad_tpu.api import APIClient

        agent = Agent(AgentConfig(server_enabled=True, http_port=0,
                                  rpc_port=0))
        try:
            client = APIClient(
                f"http://{agent.http.address[0]}:"
                f"{agent.http.address[1]}")
            doc = client.agent_metrics()
            providers = {k.split(".")[1] for k in doc["providers"]}
            assert {"broker", "plan_queue", "applier", "overload",
                    "heartbeat", "store", "workers", "rpc", "http",
                    "breaker"} <= providers
            # Key grammar: nomad.<provider>.<path...>, numeric gauges.
            assert doc["providers"]["nomad.plan_queue.depth"] == 0
            assert doc["providers"]["nomad.overload.state"] == "normal"
            assert isinstance(
                doc["providers"]["nomad.store.tables.nodes"], int)
            assert "counters" in doc["inmem"]

            # The CLI dump rides the same endpoint.
            from nomad_tpu.cli.main import main as cli_main
            rc = cli_main(
                ["-address", client.address, "metrics", "-filter",
                 "plan_queue"])
            assert rc == 0
        finally:
            agent.shutdown()

    def test_metrics_watch_mode(self, capsys):
        """ISSUE 14 satellite: `nomad-tpu metrics -watch N` re-samples
        every N seconds and renders deltas (rates for counters) —
        bounded here by -rounds; the substring filter rides to the
        server as ?filter= so the polled payload stays small."""
        from nomad_tpu.agent import Agent, AgentConfig
        from nomad_tpu.api import APIClient
        from nomad_tpu.cli.main import main as cli_main

        agent = Agent(AgentConfig(server_enabled=True, http_port=0,
                                  rpc_port=0))
        try:
            client = APIClient(
                f"http://{agent.http.address[0]}:"
                f"{agent.http.address[1]}")
            # Server-side filter: only matching provider keys return.
            doc = client.agent_metrics(filter="plan_queue")
            assert doc["providers"]
            assert all("plan_queue" in k for k in doc["providers"])

            rc = cli_main(
                ["-address", client.address, "metrics",
                 "-watch", "0.05", "-rounds", "2",
                 "-filter", "plan_queue"])
            assert rc == 0
            out = capsys.readouterr().out
            # Round 1 prints the listing; later rounds print the delta
            # header and per-key rates.
            assert "nomad.plan_queue.depth = 0" in out
            assert out.count("keys changed") == 2
            assert "/s)" in out
        finally:
            agent.shutdown()

    def test_registry_clears_on_server_shutdown(self):
        srv = Server(ServerConfig(num_schedulers=0))
        assert "broker" in srv.obs_registry.providers()
        srv.shutdown()
        assert srv.obs_registry.providers() == []


# ---------------------------------------------------------------------------
# 5. the tier-1 overhead assertion
# ---------------------------------------------------------------------------

class TestTracingOverhead:
    def _stream(self, h, jobs) -> float:
        from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner

        class _Rec:
            def __init__(self):
                self.plans = []

            def submit_plan(self, plan):
                from nomad_tpu.structs import PlanResult
                self.plans.append(plan)
                result = PlanResult(
                    node_update=dict(plan.node_update),
                    node_allocation=dict(plan.node_allocation))
                return result, None

            def update_eval(self, ev):
                pass

            def create_eval(self, ev):
                pass

        best = float("inf")
        for _ in range(5):
            rec = _Rec()
            runner = PipelinedEvalRunner(h.state.snapshot(), rec,
                                         depth=4)
            evals = []
            for j in jobs:
                from nomad_tpu.structs import Evaluation, generate_uuid
                evals.append(Evaluation(
                    id=generate_uuid(), priority=j.priority,
                    type="service", triggered_by="job-register",
                    job_id=j.id, status="pending"))
            t0 = time.perf_counter()
            runner.process(evals)
            best = min(best, time.perf_counter() - t0)
            assert len(rec.plans) == len(jobs)
        return best

    def test_tracing_on_overhead_bounded(self):
        """The tier-1 tracing-overhead tripwire: on a
        small stream the tracing-ON best-of-5 must stay within 50% of
        OFF (generous — CI noise — but a hot path that started
        allocating per-span dicts with tracing OFF, or an O(n) tracer
        regression, blows way past it)."""
        from nomad_tpu.scheduler.harness import Harness

        h = Harness()
        for i in range(64):
            h.state.upsert_node(h.next_index(), mock.node(i))
        jobs = [_job(4) for _ in range(12)]
        for j in jobs:
            h.state.upsert_job(h.next_index(), j)
        self._stream(h, jobs)  # warm compile/prep caches
        off = self._stream(h, jobs)
        with trace.tracing(seed=2):
            on = self._stream(h, jobs)
        off2 = self._stream(h, jobs)
        baseline = min(off, off2)
        assert on <= baseline * 1.5 + 0.005, (
            f"tracing-on stream {on * 1000:.1f}ms vs off "
            f"{baseline * 1000:.1f}ms (> 1.5x + 5ms)")

    def test_disabled_sites_skip_the_tracer_entirely(self):
        """With tracing off the instrumentation is one module-bool
        read: no tracer exists to record into, and a stream leaves no
        spans behind when tracing is enabled AFTERWARDS."""
        assert trace.ENABLED is False
        from nomad_tpu.scheduler.harness import Harness

        h = Harness()
        for i in range(8):
            h.state.upsert_node(h.next_index(), mock.node(i))
        job = _job(2)
        h.state.upsert_job(h.next_index(), job)
        self._stream(h, [job])
        with trace.tracing(seed=4) as t:
            assert t.snapshot() == []


# ---------------------------------------------------------------------------
# 6. the whole path (ISSUE 26): HTTP socket -> blocking-query answer, the
#    fused runner's cycle, device dispatches
# ---------------------------------------------------------------------------

def _reducer(name: str):
    """A reader of the benchmark (benchmarks/reducers/<name>.py): the
    coverage it reports on the chip is the coverage asserted here."""
    import importlib.util
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:    # the readers import their xplane.py
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        f"obs_reducer_{name}", os.path.join(bench, "reducers",
                                            f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _tags(span: dict) -> dict:
    return span.get("tags") or {}


def _pending_eval(job_id: str):
    from nomad_tpu.structs import Evaluation, generate_uuid

    return Evaluation(id=generate_uuid(), priority=50, type="service",
                      triggered_by="job-register", job_id=job_id,
                      status="pending")


def _http_agent():
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api import APIClient

    agent = Agent(AgentConfig(server_enabled=True, http_port=0,
                              rpc_port=0, enable_debug=True))
    for i in range(8):
        agent.server.node_register(mock.node(i))
    host, port = agent.http.address
    return agent, APIClient(f"http://{host}:{port}")


def _http_get(api, path: str) -> tuple:
    """(status, body) of a GET that may be refused."""
    from nomad_tpu.api.client import APIError

    try:
        return 200, api.raw("GET", path)[0]
    except APIError as e:
        return e.status, None


def _await_eval(api, eval_id: str, timeout: float = 20.0):
    """The benchmark client's wait: a blocking query, never a poll."""
    from nomad_tpu.api.client import QueryOptions

    opts, deadline = None, time.monotonic() + timeout
    while True:
        ev, meta = api.eval_info(eval_id, opts)
        if ev.terminal_status() or time.monotonic() > deadline:
            return ev
        opts = QueryOptions(wait_index=meta.last_index, wait_time=5.0)


class TestWholePathSpans:
    def _one_job(self):
        """One job registered over HTTP and awaited by a blocking
        query, every raft apply slowed so the path's steps (not the
        HTTP codec) are what the interval is made of."""
        plan = FaultPlan.parse("seed=26;raft.apply=delay(secs=0.02)")
        agent, api = _http_agent()
        try:
            api.job_register(_job(1))      # warm: compile, caches
            time.sleep(0.5)  # sleep-ok: let the warm job's batch finish
            with trace.tracing(seed=26) as tracer:
                with faultinject.injected(plan):
                    eval_id = api.job_register(_job(2))["eval_id"]
                    ev = _await_eval(api, eval_id)
                assert ev.status == "complete"
                # The runner records worker.batch after its last ack,
                # the HTTP worker its span after the answer is written.
                wait_until(lambda: {"worker.batch", "complete"} <= {
                    _tags(s).get("eval_status", s["name"])
                    for s in tracer.snapshot()}, timeout=5.0)
                return eval_id, tracer.snapshot()
        finally:
            agent.shutdown()

    def test_one_job_over_http_is_one_closed_chain(self):
        eval_id, spans = self._one_job()
        by_id = {s["span_id"]: s for s in spans}
        anchor = next(s for s in spans if s["name"] == "eval.created"
                      and _tags(s).get("eval_id") == eval_id)
        tree = [s for s in spans if s["trace_id"] == anchor["trace_id"]]
        roots = [s for s in tree if s["parent_id"] not in by_id]
        assert [s["name"] for s in roots] == ["http.serve.job_register"]
        assert roots[0]["parent_id"] is None
        assert all(s["dur"] >= 0.0 for s in tree)
        assert _tags(roots[0])["code"] == 200
        assert _tags(roots[0])["blocking"] == 0
        names = {s["name"] for s in tree}
        assert {"rpc.client.Job.Register", "rpc.serve.Job.Register",
                "server.apply.job_register", "server.apply.eval_update",
                "broker.wait", "sched.begin", "sched.dispatch",
                "sched.finish", "sched.submit", "sched.status",
                "plan.queued", "applier.window", "raft.apply",
                "store.upsert"} <= names
        # The status write hangs under the lane's sched.submit, and its
        # raft apply under it.
        status = [s for s in tree if s["name"] == "sched.status"]
        assert [_tags(s)["status"] for s in status] == ["complete"]
        assert by_id[status[0]["parent_id"]]["name"] == "sched.submit"
        applies = [s for s in tree
                   if s["name"] == "server.apply.eval_update"]
        assert {by_id[s["parent_id"]]["name"] for s in applies} == \
            {"rpc.serve.Job.Register", "sched.status"}
        # The old invariant, with the reads of the eval set aside (each
        # request roots a trace of its own, tied to the eval by tag).
        _assert_single_rooted_closed(
            [s for s in spans if _tags(s).get("eval_id") == eval_id
             and not s["name"].startswith("http.serve.")], eval_id)

        # The runner's cycle: one batch of one lane around the stages.
        begin = next(s for s in tree if s["name"] == "sched.begin")
        batch = next(s for s in spans if s["name"] == "worker.batch"
                     and s["t0"] <= begin["t0"] <= s["t0"] + s["dur"])
        assert _tags(batch)["lanes"] == 1
        assert 0.0 <= _tags(batch)["cpu_s"] <= batch["dur"]
        assert {s["name"] for s in spans
                if s["parent_id"] == batch["span_id"]} == {
            "worker.dequeue", "worker.sync", "worker.snapshot",
            "worker.ack"}
        submit = next(s for s in tree if s["name"] == "sched.submit")
        assert submit["t0"] + submit["dur"] <= \
            batch["t0"] + batch["dur"] + 1e-6

        # The answer: a read of this eval that found it changed.
        reads = [s for s in spans if s["name"] == "http.serve.eval_get"
                 and _tags(s).get("eval_id") == eval_id]
        answer = [s for s in reads
                  if _tags(s)["eval_status"] == "complete"]
        assert answer and _tags(answer[0])["changed"] == 1
        assert _tags(answer[0])["code"] == 200
        blocked = [s for s in spans if s["name"] == "query.blocked"]
        assert blocked and all(
            _tags(s)["table"] == "evals" and
            _tags(s)["fired"] in ("index", "timeout") for s in blocked)
        assert {by_id[s["parent_id"]]["name"] for s in blocked} == \
            {"rpc.serve.Eval.GetEval"}

    def test_window_span_counts_its_claims(self):
        """``applier.window`` says how many claims its plan made and how
        many of them the per-claim walk decided; ``nomad.plan.claims``
        and ``.claims_walked`` under /v1/agent/metrics add up to the
        spans' tags over a traced window."""
        agent, api = _http_agent()

        def counters() -> tuple:
            code, body = _http_get(api, "/v1/agent/metrics?filter=plan")
            assert code == 200
            got = body["inmem"]["counters"]
            return (got["nomad.plan.claims"],
                    got["nomad.plan.claims_walked"])

        try:
            warm = api.job_register(_job(1))["eval_id"]
            assert _await_eval(api, warm).status == "complete"
            with trace.tracing(seed=29) as tracer:
                before = counters()
                eval_id = api.job_register(_job(4, count=3))["eval_id"]
                assert _await_eval(api, eval_id).status == "complete"
                after = counters()
                spans = tracer.snapshot()
        finally:
            agent.shutdown()
        windows = [_tags(s) for s in spans
                   if s["name"] == "applier.window"]
        assert windows and all(
            t["eval_id"] == eval_id and t["claims"] >= 1
            and 0 <= t["walked"] <= t["claims"] for t in windows)
        assert after[0] - before[0] == sum(t["claims"] for t in windows)
        assert after[1] - before[1] == sum(t["walked"] for t in windows)

    def _replan_storm(self, names: tuple) -> tuple:
        """Six jobs gathered into ONE batch whose lanes bin-pack the
        same nodes (two copies a job, two such copies a node: two plans
        a fused round commit whole), so a second fused round and
        one-by-one re-plans follow.  Returns the ``names`` of
        ``nomad.batch_runner.*`` under /v1/agent/metrics before and
        after, the spans, and the allocations the store ends with."""
        agent, api = _http_agent()
        srv = agent.server

        def counters() -> dict:
            code, body = _http_get(
                api, "/v1/agent/metrics?filter=batch_runner")
            assert code == 200
            return {k: body["providers"][f"nomad.batch_runner.{k}"]
                    for k in names}

        try:
            warm = api.job_register(_job(1))["eval_id"]
            assert _await_eval(api, warm).status == "complete"
            before = counters()
            for w in srv.workers:
                w.set_pause(True)   # the evals gather into ONE batch
            time.sleep(0.6)  # sleep-ok: workers leave their dequeue
            with trace.tracing(seed=34) as tracer:
                eval_ids = []
                for _ in range(6):
                    job = _job(1, count=2)
                    job.task_groups[0].tasks[0].resources.cpu = 1500
                    eval_ids.append(api.job_register(job)["eval_id"])
                for w in srv.workers:
                    w.set_pause(False)
                assert {_await_eval(api, e).status
                        for e in eval_ids} == {"complete"}
                after = counters()
                spans = tracer.snapshot()
            n_allocs = len(srv.fsm.state.allocs())
        finally:
            for w in srv.workers:
                w.set_pause(False)
            agent.shutdown()
        return before, after, spans, n_allocs

    def test_replan_counters_add_up_to_the_retry_spans(self):
        """A batch whose lanes bin-pack the same nodes leaves
        stragglers: ``nomad.batch_runner.replans``, ``.replan_attempts``
        and ``.usage_walks`` under /v1/agent/metrics move by what the
        ``sched.retry`` spans' ``attempts`` / ``usage_walks`` tags say,
        and with one runner every re-plan takes one attempt."""
        before, after, spans, n_allocs = self._replan_storm(
            ("replans", "replan_attempts", "usage_walks",
             "host_dispatches"))
        assert before["replans"] == before["replan_attempts"] == 0
        retries = [_tags(s) for s in spans if s["name"] == "sched.retry"]
        assert retries, "the storm left no straggler"
        assert after["replans"] - before["replans"] == len(retries)
        assert after["replan_attempts"] - before["replan_attempts"] == \
            sum(t["attempts"] for t in retries) == len(retries)
        assert after["usage_walks"] == before["usage_walks"] == \
            sum(t["usage_walks"] for t in retries) == 0
        # The twin's seconds and real slots of each re-plan ride on its
        # span: it has no ``sched.dispatch`` of its own.
        assert all(t["twin_slots"] == t["host_calls"] == 1
                   and 0.0 < t["twin_s"] for t in retries)
        assert after["host_dispatches"] - before["host_dispatches"] >= \
            6 + len(retries)
        assert n_allocs == 1 + 6 * 2

    def test_plan_encode_once_a_commit_window_on_both_wire_formats(self):
        """``plan.encode`` (accepted portions -> log entry) is recorded
        once a commit window, one span per member plan over the
        window's one interval as ``raft.apply`` is, on the committer's
        thread, whichever wire format the window took: a lone plan's
        ``ALLOC_UPDATE_REQUEST`` (``plans`` 1: every one-by-one
        re-plan) and a window's ``PLAN_BATCH_APPLY_REQUEST``."""
        _before, _after, spans, _n = self._replan_storm(("replans",))

        def windows(name: str) -> dict:
            out = {}
            for s in spans:
                if s["name"] == name:
                    out.setdefault((s["t0"], s["dur"]), []).append(s)
            return dict(sorted(out.items()))

        encodes, applies = windows("plan.encode"), windows("raft.apply")
        assert len(encodes) == len(applies) >= 3
        for (enc, members), (app, committed) in zip(encodes.items(),
                                                    applies.items()):
            # Encoded before it was dispatched, one span a member.
            assert enc[0] <= app[0] and enc[0] + enc[1] <= app[0] + app[1]
            assert {_tags(s)["eval_id"] for s in members} == \
                {_tags(s)["eval_id"] for s in committed}
            assert {_tags(s)["plans"] for s in members} == \
                {_tags(s)["window"] for s in committed} == {len(members)}
            assert all(_tags(s)["bytes"] > 0 for s in members)
            assert {s["thread"] for s in members} == \
                {s["thread"] for s in committed}
            parents = {s["span_id"]: s for s in spans}
            assert all(parents[s["parent_id"]]["name"] == "eval.created"
                       for s in members)
        sizes = {len(members) for members in encodes.values()}
        assert 1 in sizes and max(sizes) > 1, sizes

    def test_the_runner_says_what_it_computed_chose_to_wait_and_stood(self):
        """On a served batch every stage span of the runner's thread
        carries ``cpu_s`` and ``blocked_s``; the status write stays the
        parent of its raft apply; and the benchmark's own readers make
        100 of the batch: on a CPU, in waits it chose, runnable and not
        running (``runner_on_cpu_share`` + ``runner_blocked_share`` +
        ``runner_stalled_share``), with the re-plans' share of the
        cycle and the stage table beside them."""
        _before, _after, spans, _n = self._replan_storm(("replans",))
        staged = ("worker.batch", "worker.sync", "worker.snapshot",
                  "worker.ack", "sched.begin", "sched.dispatch",
                  "sched.finish", "sched.submit", "sched.status",
                  "sched.retry", "retry.refresh", "retry.begin",
                  "retry.dispatch", "retry.finish", "retry.submit")
        seen = set()
        for s in spans:
            if s["name"] in staged:
                seen.add(s["name"])
                assert _tags(s)["cpu_s"] >= 0.0, s
                # Wall less CPU inside the waits, from two clocks read
                # one after the other: a few microseconds either way.
                assert _tags(s)["blocked_s"] >= -1e-4, s
                assert s["thread"] == "scheduler-worker"
        assert seen == set(staged)
        by_id = {s["span_id"]: s for s in spans}
        applies = [s for s in spans
                   if s["name"] == "server.apply.eval_update"
                   and by_id.get(s["parent_id"], {}).get("name")
                   == "sched.status"]
        assert len(applies) == sum(s["name"] == "sched.status"
                                   for s in spans) >= 6
        # A re-plan waits for its plan: most of ``retry.submit`` is a
        # wait the runner chose.
        submits = [s for s in spans if s["name"] == "retry.submit"]
        assert sum(_tags(s)["blocked_s"] for s in submits) > \
            0.5 * sum(s["dur"] for s in submits)
        ctx = {"spans": spans, "notes": []}
        stages, cycle = _reducer("runner_stages"), _reducer("runner_cycle")
        on_cpu = cycle.reduce({"what": "on_cpu_share"}, ctx)
        blocked = stages.reduce({"what": "blocked_share"}, ctx)
        stalled = stages.reduce({"what": "stalled_share"}, ctx)
        assert on_cpu + blocked + stalled == pytest.approx(100.0)
        assert blocked > 0.0
        assert 0.0 < stages.reduce({"what": "retry_cycle_share"}, ctx) \
            < 100.0
        assert stages.reduce({"what": "retry_stalled_share"}, ctx) \
            is not None
        table = [n for n in ctx["notes"] if n.startswith("  ")]
        assert {n.split(":")[0].split(" [")[0].strip()
                for n in table} >= set(staged)

    def test_fit_walk_rows_ride_the_prep_spans_and_two_counters(self):
        """Every prep says how many rows its fit walk examined
        (``fit_rows``) of those a whole walk examines (``fit_rows_full``
        = the fleet's real rows a slot): on ``sched.begin`` for a fused
        round's lane, on ``sched.retry`` for a one-by-one re-plan, and
        ``nomad.batch_runner.fit_rows`` / ``.fit_rows_full`` under
        /v1/agent/metrics move by their sum.  Eight nodes are under one
        block of the walk: it examines them all."""
        before, after, spans, _n = self._replan_storm(
            ("fit_rows", "fit_rows_full", "replans"))
        begins = [_tags(s) for s in spans if s["name"] == "sched.begin"]
        retries = [_tags(s) for s in spans if s["name"] == "sched.retry"]
        # A first round of six lanes, a second of those left partial.
        assert len(begins) > 6 and retries
        assert after["replans"] - before["replans"] == len(retries)
        for t in begins:
            assert t["fit_rows"] == t["fit_rows_full"] == 8 * t["slots"]
        for t in retries:
            assert t["fit_rows"] == t["fit_rows_full"] == 8 * t["attempts"]
        for name in ("fit_rows", "fit_rows_full"):
            assert after[name] - before[name] == \
                sum(t[name] for t in begins + retries)

    def test_twin_rows_ride_the_host_dispatches_and_two_counters(self):
        """Every rounds pass of the numpy twin says how many rows it
        scored (``twin_rows``) of those whole passes score
        (``twin_rows_full`` = the fleet's real rows a slot and round):
        on a host-engine ``sched.dispatch`` for a fused round's lane,
        on ``sched.retry`` and its ``retry.dispatch`` for a one-by-one
        re-plan, and ``nomad.batch_runner.twin_rows`` /
        ``.twin_rows_full`` under /v1/agent/metrics move by their sum.
        On eight nodes sixteen empties a shape are over the fall-back
        line: every pass scores them all."""
        before, after, spans, _n = self._replan_storm(
            ("twin_rows", "twin_rows_full", "replans"))
        lanes = [_tags(s) for s in spans if s["name"] == "sched.dispatch"]
        retries = [_tags(s) for s in spans if s["name"] == "sched.retry"]
        attempts = [_tags(s) for s in spans
                    if s["name"] == "retry.dispatch"]
        assert len(lanes) > 6 and retries
        assert after["replans"] - before["replans"] == len(retries)
        for t in lanes + attempts:
            assert t["engine"] == "host"
            assert 0 < t["twin_rows"] <= t["twin_rows_full"] \
                == 8 * t["slots"]
        for t in retries:
            assert 0 < t["twin_rows"] <= t["twin_rows_full"] \
                == 8 * t["attempts"]
        for name in ("twin_rows", "twin_rows_full"):
            assert sum(t[name] for t in attempts) == \
                sum(t[name] for t in retries)
            assert after[name] - before[name] == \
                sum(t[name] for t in lanes + retries)

    def test_slot_tags_and_counters_say_how_many_slots_a_lane_carried(self):
        """A job of three groups whose asks differ is a lane of three
        REAL kernel slots; one whose two groups share an ask dedupes to
        one.  ``sched.begin`` and ``sched.dispatch`` say so per lane
        (``slots``), and ``nomad.batch_runner.slots`` / ``.padded_slots``
        under /v1/agent/metrics move by the real slots of every kernel
        call and by the padded axis (8) each was shaped to."""
        agent, api = _http_agent()

        def counters() -> dict:
            code, body = _http_get(
                api, "/v1/agent/metrics?filter=batch_runner")
            assert code == 200
            return {k: body["providers"][f"nomad.batch_runner.{k}"]
                    for k in ("slots", "padded_slots", "host_dispatches",
                              "device_dispatches")}

        try:
            warm = api.job_register(_job(1))["eval_id"]
            assert _await_eval(api, warm).status == "complete"
            with trace.tracing(seed=35) as tracer:
                before = counters()
                stack = _job(3, count=2)
                for g, tg in enumerate(stack.task_groups):
                    tg.tasks[0].resources.memory_mb = 32 * (g + 1)
                wanted = {}
                for job, slots in ((stack, 3), (_job(2, count=2), 1)):
                    eval_id = api.job_register(job)["eval_id"]
                    assert _await_eval(api, eval_id).status == "complete"
                    wanted[eval_id] = slots
                after = counters()
                spans = tracer.snapshot()
        finally:
            agent.shutdown()
        for name in ("sched.begin", "sched.dispatch"):
            got = {_tags(s)["eval_id"]: _tags(s)["slots"] for s in spans
                   if s["name"] == name}
            assert got == wanted, name
        calls = sum(after[k] - before[k]
                    for k in ("host_dispatches", "device_dispatches"))
        assert calls == 2
        assert after["slots"] - before["slots"] == 3 + 1
        assert after["padded_slots"] - before["padded_slots"] == 2 * 8

    def test_leaf_spans_cover_the_interval(self):
        """The chain is contiguous: at most a tenth of socket-readable
        -> answer-written lies under no leaf span (the benchmark's
        commit_unattributed_share, by the benchmark's own reader)."""
        _eval_id, spans = self._one_job()
        ctx = {"spans": spans, "notes": []}
        chain = _reducer("job_chain")
        share = chain.reduce({"what": "unattributed_share"}, ctx)
        assert share is not None and share <= 10.0, (share, ctx["notes"])
        lag = chain.reduce({"what": "wake_lag_ms"}, ctx)
        assert lag is not None and lag < 1000.0

    def test_unrelated_eval_write_leaves_the_query_parked(self):
        """The per-evaluation watch: a write to ANOTHER eval leaves a
        client blocked on its own eval parked; its own eval's write
        wakes it, the span says fired=index, changed=1, and the
        registry counts the wake-up as a useful one."""
        from nomad_tpu.api.client import QueryOptions

        agent, api = _http_agent()
        srv = agent.server
        try:
            for w in srv.workers:
                w.set_pause(True)   # evals stay pending
            time.sleep(0.6)  # sleep-ok: workers leave their dequeue
            mine, other = _pending_eval("job-a"), _pending_eval("job-b")
            srv.apply_eval_update([mine])
            index = srv.fsm.state.get_index("evals")
            got = {}
            with trace.tracing(seed=27) as tracer:
                reader = threading.Thread(target=lambda: got.update(
                    zip(("ev", "meta"), api.eval_info(
                        mine.id, QueryOptions(wait_index=index,
                                              wait_time=10.0)))))
                reader.start()
                wait_until(lambda: len(srv.fsm.state.watch._waiters)
                           >= 1, timeout=5.0)
                srv.apply_eval_update([other])
                reader.join(0.3)
                assert reader.is_alive(), \
                    "another eval's write woke the read of this one"
                assert srv.fsm.state.watch.live_waiters() == 1
                done = mine.copy()
                done.status = "complete"
                srv.apply_eval_update([done])
                reader.join(10.0)
                assert not reader.is_alive()
                wait_until(lambda: any(
                    s["name"] == "http.serve.eval_get"
                    for s in tracer.snapshot()), timeout=5.0)
                spans = tracer.snapshot()
            assert got["ev"].status == "complete"
            assert got["meta"].last_index == got["ev"].modify_index \
                == srv.fsm.state.get_index("evals")
            read = next(s for s in spans
                        if s["name"] == "http.serve.eval_get")
            assert _tags(read)["fired"] == "index"
            assert _tags(read)["changed"] == 1
            assert _tags(read)["blocking"] == 1
            assert _tags(read)["eval_id"] == mine.id
            assert _tags(read)["eval_status"] == "complete"
            blocked = next(s for s in spans
                           if s["name"] == "query.blocked")
            assert _tags(blocked)["fired"] == "index"
            assert read["t0"] <= blocked["t0"] and \
                blocked["t0"] + blocked["dur"] <= read["t0"] + read["dur"]
            stats = agent.http.stats()
            assert stats["blocking_wakes"] == 1
            assert stats["blocking_wakes_changed"] == 1
            metrics = agent.metrics_payload()["providers"]
            assert metrics["nomad.http.blocking_wakes"] == 1
            assert metrics["nomad.http.blocking_wakes_changed"] == 1
            assert metrics["nomad.workers.batches"] >= 0
            assert metrics["nomad.workers.batch_busy_s"] >= 0.0
            assert metrics["nomad.finish.node_inits"] >= \
                metrics["nomad.finish.node_walks"] >= 0
            assert metrics["nomad.batch_runner.host_lanes"] == \
                metrics["nomad.batch_runner.host_dispatches"] >= 0
            assert metrics["nomad.batch_runner.device_lanes"] >= \
                metrics["nomad.batch_runner.device_dispatches"] >= 0
        finally:
            for w in srv.workers:
                w.set_pause(False)
            agent.shutdown()

    def test_every_wake_of_eight_waiters_is_its_own_write(self):
        """8 clients blocked on 8 evals over HTTP, 50 writes to other
        evals: nobody wakes; then each eval's own write wakes its one
        reader, so every counted wake is a useful one, and the fan-out
        registry is empty again (no entry leaked under the per-eval
        key)."""
        from nomad_tpu.api.client import QueryOptions

        agent, api = _http_agent()
        srv = agent.server
        watch = srv.fsm.state.watch
        try:
            for w in srv.workers:
                w.set_pause(True)   # evals stay pending
            time.sleep(0.6)  # sleep-ok: workers leave their dequeue
            mine = [_pending_eval(f"job-{i}") for i in range(8)]
            srv.apply_eval_update(mine)
            index = srv.fsm.state.get_index("evals")
            got = {}

            def read(ev):
                got[ev.id] = api.eval_info(ev.id, QueryOptions(
                    wait_index=index, wait_time=30.0))
            readers = [threading.Thread(target=read, args=(ev,))
                       for ev in mine]
            for t in readers:
                t.start()
            wait_until(lambda: watch.live_waiters() == 8, timeout=10.0)
            for i in range(50):
                srv.apply_eval_update([_pending_eval(f"other-{i}")])
            assert watch.stats()["live_waiters"] == 8
            assert agent.http.stats()["blocking_wakes"] == 0
            for n, ev in enumerate(mine, 1):
                done = ev.copy()
                done.status = "complete"
                srv.apply_eval_update([done])
                wait_until(lambda: agent.http.stats()["blocking_wakes"]
                           == n, timeout=10.0)
                stats = agent.http.stats()
                assert stats["blocking_wakes_changed"] == \
                    stats["blocking_wakes"] == n
                assert watch.stats()["live_waiters"] == 8 - n
            for t in readers:
                t.join(10.0)
                assert not t.is_alive()
            for ev in mine:
                answered, meta = got[ev.id]
                assert answered.status == "complete"
                assert meta.last_index == answered.modify_index > index
            metrics = agent.metrics_payload()["providers"]
            assert metrics["nomad.http.blocking_wakes_changed"] == \
                metrics["nomad.http.blocking_wakes"] == 8
            assert watch.stats()["live_waiters"] == 0
            assert watch.stats()["timeouts"] == 0
        finally:
            for w in srv.workers:
                w.set_pause(False)
            agent.shutdown()

    @pytest.mark.parametrize("wait, fired", [(10.0, "index"),
                                             (0.2, "timeout")])
    def test_parked_query_records_its_wait(self, wait, fired):
        """The RPC plane parks a blocking query instead of holding a
        thread: the span still runs subscribe -> wake."""
        srv = Server(ServerConfig(num_schedulers=0, enable_rpc=True))
        srv.establish_leadership()
        pool = ConnPool()
        try:
            mine = _pending_eval("job-a")
            srv.apply_eval_update([mine])
            index = srv.fsm.state.get_index("evals")
            with trace.tracing(seed=28) as tracer:
                reader = threading.Thread(target=lambda: pool.call(
                    srv.rpc_address(), "Eval.GetEval",
                    {"eval_id": mine.id, "min_query_index": index,
                     "max_query_time": wait}, timeout=15.0))
                reader.start()
                if fired == "index":
                    wait_until(lambda: len(
                        srv.fsm.state.watch._waiters) >= 1, timeout=5.0)
                    # Another eval's write leaves it parked; its own
                    # wakes it.
                    srv.apply_eval_update([_pending_eval("job-b")])
                    assert srv.fsm.state.watch.live_waiters() == 1
                    srv.apply_eval_update([mine])
                reader.join(15.0)
                assert not reader.is_alive()
                blocked = [s for s in tracer.snapshot()
                           if s["name"] == "query.blocked"]
            assert [_tags(s)["fired"] for s in blocked] == [fired]
            assert _tags(blocked[0])["table"] == "evals"
            if fired == "timeout":
                assert blocked[0]["dur"] >= 0.15
        finally:
            pool.shutdown()
            srv.shutdown()

    def test_device_dispatch_names_program_and_shapes(self):
        """device.dispatch carries the jitted function's name as the
        device plane shows it (``jit_<name>`` minus ``jit_``) and the
        shapes of the call; bytes come from the counted seams."""
        import numpy as np

        from nomad_tpu.models import fleet
        from nomad_tpu.ops import binpack
        from nomad_tpu.parallel.devices import put_counted
        from nomad_tpu.parallel.mesh import mesh_override
        from nomad_tpu.scheduler.batch import BatchEvalRunner
        from nomad_tpu.scheduler.executor import executor_override
        from nomad_tpu.scheduler.harness import Harness
        from nomad_tpu.structs import Evaluation, generate_uuid

        h = Harness()
        for i in range(16):
            h.state.upsert_node(h.next_index(), mock.node(i))
        jobs = [_job(2, count=2) for _ in range(3)]
        for j in jobs:
            h.state.upsert_job(h.next_index(), j)
        evals = [Evaluation(id=generate_uuid(), priority=j.priority,
                            type="service", triggered_by="job-register",
                            job_id=j.id, status="pending") for j in jobs]
        with trace.tracing(seed=29) as tracer:
            usage = put_counted(np.zeros((16, 6), np.float32))
            tracer.snapshot()
            fleet._scatter_rows(usage, np.array([1, 2, 3], np.int32),
                                np.ones((3, 6), np.float32))
            # The single-device twin: the suite's 8 virtual devices
            # would otherwise shard the lanes (another program's name).
            runner = BatchEvalRunner(h.state.snapshot(), h)
            with executor_override("device"), mesh_override("off"):
                runner.process(evals)
            lanes = [_tags(s) for s in tracer.snapshot()
                     if s["name"] == "sched.dispatch"]
            spans = [s for s in tracer.snapshot()
                     if s["name"] == "device.dispatch"]
        # The lane spans say what the choice was made on, and the
        # always-on pair counts the lanes each engine placed.
        assert {(t["engine"], t["lanes"], t["cost"]) for t in lanes} == \
            {("device", 3, 3 * 8 * 16)}
        assert runner.stats()["device_lanes"] == 3
        assert runner.stats()["host_lanes"] == 0
        scatter = next(s for s in spans if _tags(s)["program"] ==
                       fleet._scatter_jit_impl.__name__)
        assert _tags(scatter)["async"] == 1
        assert _tags(scatter)["rows"] == 4 and _tags(scatter)["n_pad"] == 16
        assert _tags(scatter)["h2d_bytes"] >= 4 * 4 + 4 * 6 * 4
        fused = next(s for s in spans if _tags(s)["program"] ==
                     binpack.place_rounds_batch.__name__)
        assert _tags(fused)["program"] == "_place_rounds_batched"
        assert "async" not in _tags(fused)
        assert _tags(fused)["lanes"] == 3 and _tags(fused)["b_pad"] == 4
        assert _tags(fused)["slots"] == 3     # one real slot a lane
        for key in ("g_pad", "k_cap", "rounds", "n_pad"):
            assert _tags(fused)[key] >= 1, key
        assert _tags(fused)["h2d_bytes"] > 0
        assert _tags(fused)["d2h_bytes"] > 0
        assert {e.status for e in h.evals} == {"complete"}

    def test_disabled_sites_read_one_bool(self, monkeypatch):
        """Tracing off: no new site takes the thread's CPU clock, opens
        a dispatch bracket or a profiler annotation, or touches a
        tracer — a job over HTTP and a device dispatch run with all of
        them booby-trapped."""
        import jax
        import numpy as np

        from nomad_tpu.models import fleet
        from nomad_tpu.parallel import devices
        from nomad_tpu.parallel.devices import put_counted

        def boom(*_a, **_kw):
            raise AssertionError("a tracing-only call ran with "
                                 "tracing off")
        assert trace.ENABLED is False
        monkeypatch.setattr(time, "thread_time", boom)
        monkeypatch.setattr(devices, "device_dispatch", boom)
        monkeypatch.setattr(fleet, "device_dispatch", boom, raising=False)
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
        monkeypatch.setattr(Tracer, "record", boom)
        monkeypatch.setattr(Tracer, "span", boom)
        monkeypatch.setattr(Tracer, "new_id", boom)
        agent, api = _http_agent()
        try:
            eval_id = api.job_register(_job(2))["eval_id"]
            assert _await_eval(api, eval_id).status == "complete"
            assert agent.server.workers[0].dispatch_failures == 0
            assert agent.http.stats()["blocking_wakes"] >= 0
        finally:
            agent.shutdown()
        usage = put_counted(np.zeros((16, 6), np.float32))
        out = fleet._scatter_rows(usage, np.array([1], np.int32),
                                  np.ones((1, 6), np.float32))
        assert float(np.asarray(out)[1, 0]) == 1.0
        assert devices._moved.__dict__ == {}

    @pytest.mark.parametrize("method, path, key", [
        ("PUT", "/v1/jobs", "job_register"),
        ("GET", "/v1/jobs", "job_list"),
        ("GET", "/v1/job/abc", "job_get"),
        ("DELETE", "/v1/job/abc", "job_deregister"),
        ("GET", "/v1/job/abc/allocations", "job_allocations"),
        ("GET", "/v1/evaluation/9f3c?index=7&wait=5s", "eval_get"),
        ("GET", "/v1/evaluation/9f3c/allocations", "eval_allocations"),
        ("GET", "/v1/allocation/9f3c", "alloc_get"),
        ("GET", "/v1/nodes", "node_list"),
        ("PUT", "/v1/node/n1/drain", "node_drain"),
        ("GET", "/v1/agent/metrics", "agent_metrics"),
        ("GET", "/v1/agent/force-leave", "agent_force_leave"),
        ("GET", "/v1/status/leader", "status_leader"),
        ("GET", "/v1/job/abc/9f3c-looks-like-an-id", "other"),
        ("GET", "/v1/agent/9f3c", "other"),
        ("GET", "/nothing", "other"),
    ])
    def test_route_key_is_low_cardinality(self, method, path, key):
        from nomad_tpu.agent.http_server import route_key

        assert route_key(method, path) == key

    def test_operator_starts_dumps_and_stops_the_recorder(self, tmp_path):
        """/v1/agent/trace beside /v1/agent/profile: start with a ring,
        dump a Chrome-trace document that holds the request chain, stop;
        a profile start brings the span recorder up with it."""
        agent, api = _http_agent()
        try:
            assert _http_get(api, "/v1/agent/trace?action=dump")[0] == 400
            code, out = _http_get(
                api, "/v1/agent/trace?action=start&ring=4096&seed=7")
            assert code == 200 and out == {"tracing": True, "ring": 4096}
            assert trace.ENABLED and trace.tracer().stats()[
                "ring_max"] == 4096
            assert _http_get(api, "/v1/agent/trace?action=start")[0] == 400
            eval_id = api.job_register(_job(1))["eval_id"]
            assert _await_eval(api, eval_id).status == "complete"
            code, doc = _http_get(api, "/v1/agent/trace?action=dump")
            assert code == 200
            events = doc["traceEvents"]
            assert all(e["ph"] == "X" and e["dur"] >= 0 for e in events)
            assert {"http.serve.job_register", "broker.wait",
                    "sched.submit"} <= {e["name"] for e in events}
            assert any(e["args"].get("eval_id") == eval_id
                       for e in events)
            code, out = _http_get(api, "/v1/agent/trace?action=stop")
            assert code == 200 and out["tracing"] is False
            assert out["spans"]["dropped"] == 0
            assert trace.ENABLED is False and trace.tracer() is None
            assert _http_get(api, "/v1/agent/trace?action=stop")[0] == 400

            log_dir = str(tmp_path / "profile")
            code, out = _http_get(
                api, f"/v1/agent/profile?action=start&dir={log_dir}")
            assert code == 200 and out["spans"] == "started"
            assert trace.ENABLED
            assert _http_get(api, "/v1/agent/profile?action=stop")[0] == 200
            assert trace.ENABLED   # the recorder is dumped on its own
            assert _http_get(api, "/v1/agent/trace?action=stop")[0] == 200
        finally:
            trace.disable()
            agent.shutdown()
