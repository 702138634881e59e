"""Feedback control plane (ISSUE 14): actuators, laws, controller,
wiring, and the seeded chaos rig.

The contract under test: a deterministic, seeded tick loop reads the
gauges the metrics registry already publishes and adjusts the live
knobs through railed actuators — with every decision observable
(control.tick/control.adjust spans, the ``controller`` registry
provider) and every misbehavior self-indicting (flight dumps on
reversal and rail saturation).  These tests pin the mechanisms.
"""
from __future__ import annotations

import threading
import time

import pytest

from nomad_tpu import faultinject, mock
from nomad_tpu.control import (
    AIMD,
    Actuator,
    Controller,
    GradientStep,
    applier_controller,
    runner_controller,
)
from nomad_tpu.control.controller import TickView
from nomad_tpu.faultinject import FaultPlan
from nomad_tpu.obs import flight, trace

from tests.conftest import wait_until


def _box(value):
    state = {"v": value}
    return state, (lambda: state["v"]), \
        (lambda v: state.__setitem__("v", v))


def _actuator(value=8, lo=1, hi=16, integer=True, name="k"):
    state, get, set_ = _box(value)
    return state, Actuator(name, get=get, set=set_, lo=lo, hi=hi,
                           integer=integer, gauge="g")


# ---------------------------------------------------------------------------
# 1. actuators: rails, reversals, pin
# ---------------------------------------------------------------------------

class TestActuator:
    def test_clamps_into_rails_and_counts_saturation_once(self):
        state, act = _actuator(8, lo=1, hi=10)
        old, new, ev = act.apply(50)
        assert (old, new, state["v"]) == (8, 10, 10)
        assert ev["rail"] is True and act.rail_hits == 1
        # Parked at the rail: further saturated decisions book NO new
        # rail hit (transition-counted, not per-tick).
        _old, _new, ev2 = act.apply(50)
        assert ev2["rail"] is False and act.rail_hits == 1
        # Moving back inside re-arms the transition.
        act.apply(5)
        act.apply(50)
        assert act.rail_hits == 2

    def test_reversals_count_direction_flips(self):
        _state, act = _actuator(8)
        act.apply(9)    # up
        act.apply(10)   # up: no reversal
        assert act.reversals == 0
        act.apply(5)    # down: reversal
        act.apply(7)    # up again: reversal
        assert act.reversals == 2
        assert act.stats()["trajectory"] == [8, 9, 10, 5, 7]

    def test_integer_knob_rounds(self):
        state, act = _actuator(3, integer=True)
        act.apply(4.6)
        assert state["v"] == 5

    def test_pin_takes_knob_out_of_the_loop(self):
        state, act = _actuator(8)
        ctl = Controller(lambda: {"g": 1.0}, interval=0.05)
        ctl.add_knob(act, law=AIMD(), driver=lambda v: +1)
        ctl.tick()                      # baseline
        assert ctl.tick()               # adjusts
        act.pin(4)
        assert state["v"] == 4
        assert ctl.tick() == []         # pinned: untouched
        assert state["v"] == 4
        act.pin(None)
        assert ctl.tick()               # back in the loop
        assert act.stats()["pinned"] is False

    def test_pin_clamps_to_rails(self):
        state, act = _actuator(8, lo=2, hi=10)
        act.pin(100)
        assert state["v"] == 10

    def test_rejects_inverted_rails(self):
        with pytest.raises(ValueError):
            Actuator("bad", get=lambda: 1, set=lambda v: None,
                     lo=5, hi=5)


class TestLaws:
    def test_aimd_shape(self):
        law = AIMD(add=2.0, mult=0.5)
        assert law.step(8, +1) == 10
        assert law.step(8, -1) == 4
        assert law.step(8, 0) == 8
        with pytest.raises(ValueError):
            AIMD(add=0)
        with pytest.raises(ValueError):
            AIMD(mult=1.5)

    def test_gradient_shape(self):
        law = GradientStep(up=1.5, down=0.5)
        assert law.step(8, +1) == 12
        assert law.step(8, -1) == 4
        assert law.step(8, 0) == 8
        assert law.step(0.0, +1) > 0  # never wedges at zero
        with pytest.raises(ValueError):
            GradientStep(up=0.9)


# ---------------------------------------------------------------------------
# 2. the controller: determinism, isolation, spans, flight, lifecycle
# ---------------------------------------------------------------------------

def _scripted_controller(script, seed=7):
    """A controller over a scripted gauge stream (one dict per tick)."""
    feed = {"i": -1}

    def gauges():
        feed["i"] = min(feed["i"] + 1, len(script) - 1)
        return dict(script[feed["i"]])

    ctl = Controller(gauges, interval=0.05, seed=seed)
    _state, act = _actuator(8, lo=1, hi=64)
    ctl.add_knob(act, law=AIMD(add=1, mult=0.5),
                 driver=lambda v: +1 if v.get("g") > 0
                 else (-1 if v.get("g") < 0 else 0))
    return ctl


class TestController:
    SCRIPT = [{"g": 0}, {"g": 1}, {"g": 1}, {"g": -1}, {"g": 0},
              {"g": 1}]

    def test_deterministic_over_a_gauge_stream(self):
        runs = []
        for _ in range(2):
            ctl = _scripted_controller(self.SCRIPT)
            decisions = [ctl.tick() for _ in self.SCRIPT]
            stats = ctl.stats()
            stats.pop("interval_s")
            runs.append((decisions, stats))
        assert runs[0] == runs[1]
        # And the decisions are what the script dictates: two grows, a
        # halving (reversal), a hold, a grow (reversal).
        flat = [d for tick in runs[0][0] for d in tick]
        assert [d["new"] for d in flat] == [9, 10, 5, 6]
        assert [d["reversal"] for d in flat] == [False, False, True,
                                                 True]

    def test_first_tick_only_seeds_the_baseline(self):
        ctl = _scripted_controller([{"g": 1}, {"g": 1}])
        assert ctl.tick() == []
        assert ctl.tick() != []

    def test_every_n_slow_lane(self):
        gauges = {"g": 1.0}
        ctl = Controller(lambda: dict(gauges), interval=0.05)
        _state, act = _actuator(8, name="slow")
        ctl.add_knob(act, law=AIMD(), driver=lambda v: +1, every=3)
        moved = [bool(ctl.tick()) for _ in range(10)]
        # Evaluated on ticks 3/6/9; tick 3 seeds the knob's own delta
        # baseline (slow-lane deltas span the knob's whole cadence).
        assert moved == [False, False, False, False, False, True,
                         False, False, True, False]

    def test_broken_driver_is_isolated(self):
        gauges = {"g": 1.0}
        ctl = Controller(lambda: dict(gauges), interval=0.05)
        _s1, bad = _actuator(8, name="bad")

        def boom(view):
            raise RuntimeError("driver bug")
        ctl.add_knob(bad, law=AIMD(), driver=boom)
        s2, good = _actuator(8, name="good")
        ctl.add_knob(good, law=AIMD(), driver=lambda v: +1)
        ctl.tick()
        ctl.tick()
        assert s2["v"] == 9              # the healthy knob still moved
        assert ctl.stats()["driver_errors"] == 1

    def test_broken_gauges_fn_is_isolated(self):
        def boom():
            raise RuntimeError("gauge bug")
        ctl = Controller(boom, interval=0.05)
        assert ctl.tick() == []
        assert ctl.stats()["tick_errors"] == 1

    def test_decision_spans(self):
        with trace.tracing(seed=3) as tracer:
            ctl = _scripted_controller(self.SCRIPT)
            for _ in range(3):
                ctl.tick()
            spans = tracer.snapshot()
        ticks = [s for s in spans if s["name"] == "control.tick"]
        adjusts = [s for s in spans if s["name"] == "control.adjust"]
        assert len(ticks) == 3 and len(adjusts) == 2
        by_id = {s["span_id"]: s for s in spans}
        for adj in adjusts:
            parent = by_id[adj["parent_id"]]
            assert parent["name"] == "control.tick"
            tags = adj["tags"]
            assert tags["knob"] == "k" and tags["gauge"] == "g"
            assert tags["new"] == tags["old"] + 1
            assert tags["direction"] == 1

    def test_reversal_and_rail_trip_the_flight_recorder(self, tmp_path):
        with flight.installed(str(tmp_path), min_interval=0.0) as rec:
            gauges = {"g": 1.0}
            ctl = Controller(lambda: dict(gauges), interval=0.05,
                             name="ctl-test")
            _state, act = _actuator(8, lo=1, hi=9)
            ctl.add_knob(act, law=AIMD(), driver=lambda v: +1
                         if v.get("g") > 0 else -1)
            ctl.tick()          # baseline
            ctl.tick()          # 8 -> 9 (at rail, desired 9 in-range)
            ctl.tick()          # desired 10: rail saturation
            gauges["g"] = -1.0
            ctl.tick()          # halve: reversal
            names = [n.split("-", 2)[2] for n in rec.incidents()]
            assert any("control.rail" in n for n in names)
            assert any("control.reversal" in n for n in names)

    def test_tick_thread_starts_and_joins(self):
        gauges = {"g": 0.0}
        ctl = Controller(lambda: dict(gauges), interval=0.01,
                         seed=5, name="control-tick-t")
        ctl.start()
        wait_until(lambda: ctl.stats()["ticks"] >= 2,
                   msg="controller ticking")
        ctl.stop()
        assert not ctl.running()
        assert not any(t.name == "control-tick-t"
                       for t in threading.enumerate())

    def test_duplicate_knob_rejected(self):
        ctl = Controller(lambda: {}, interval=0.05)
        _s, act = _actuator(8)
        ctl.add_knob(act, law=AIMD(), driver=lambda v: 0)
        _s2, act2 = _actuator(9)
        with pytest.raises(ValueError):
            ctl.add_knob(act2, law=AIMD(), driver=lambda v: 0)


# ---------------------------------------------------------------------------
# 3. wiring: drivers, server assembly, invariants out of reach
# ---------------------------------------------------------------------------

def _view(cur, prev=None, dt=1.0):
    return TickView(cur, prev if prev is not None else
                    {k: 0 for k in cur}, dt, None)


class TestDrivers:
    def test_max_window_driver(self):
        from nomad_tpu.control.wiring import _max_window_driver as drv

        base = {"nomad.applier.commits": 0,
                "nomad.applier.plans_committed": 0}
        # Occupancy tracking the cap -> the cap binds -> grow.
        assert drv(_view({"nomad.applier.commits": 10,
                          "nomad.applier.plans_committed": 150,
                          "nomad.applier.max_window": 16}, base)) == 1
        # Thin windows far under a fat cap -> drift back.
        assert drv(_view({"nomad.applier.commits": 10,
                          "nomad.applier.plans_committed": 100,
                          "nomad.applier.max_window": 256}, base)) == -1
        # Verify latency blowing up -> shrink regardless.
        assert drv(_view({"nomad.applier.commits": 10,
                          "nomad.applier.plans_committed": 150,
                          "nomad.applier.max_window": 16,
                          "nomad.plan.evaluate_window.p99": 0.5},
                         base)) == -1
        # No commits this tick -> no signal.
        assert drv(_view({"nomad.applier.commits": 0,
                          "nomad.applier.plans_committed": 0,
                          "nomad.applier.max_window": 16}, base)) == 0

    def test_gather_driver_cost_vs_benefit(self):
        from nomad_tpu.control.wiring import _gather_driver as drv

        base = {"nomad.applier.commits": 0,
                "nomad.applier.plans_committed": 0,
                "nomad.applier.gather_wall_s": 0.0}
        # Burning gather wall while windows stay thin -> shrink.
        assert drv(_view({"nomad.applier.commits": 2,
                          "nomad.applier.plans_committed": 40,
                          "nomad.applier.max_window": 256,
                          "nomad.applier.gather_wall_s": 0.8},
                         base)) == -1
        # Many small commits per second -> amortize: grow.
        assert drv(_view({"nomad.applier.commits": 40,
                          "nomad.applier.plans_committed": 120,
                          "nomad.applier.max_window": 64,
                          "nomad.applier.gather_wall_s": 0.01},
                         base)) == 1
        # Full windows: hold (max_window's business, not gather's).
        assert drv(_view({"nomad.applier.commits": 40,
                          "nomad.applier.plans_committed": 2500,
                          "nomad.applier.max_window": 64,
                          "nomad.applier.gather_wall_s": 0.8},
                         base)) == 0

    def test_inflight_driver(self):
        from nomad_tpu.control.wiring import _inflight_driver as drv

        base = {"nomad.applier.commit_backpressure_s": 0,
                "nomad.applier.dispatch_failures": 0}
        assert drv(_view({"nomad.applier.commit_backpressure_s": 0.5,
                          "nomad.applier.dispatch_failures": 0},
                         base)) == 1
        assert drv(_view({"nomad.applier.commit_backpressure_s": 0.5,
                          "nomad.applier.dispatch_failures": 1},
                         base)) == -1
        assert drv(_view({"nomad.applier.commit_backpressure_s": 0.0,
                          "nomad.applier.dispatch_failures": 0},
                         base)) == 0

    def test_depth_limit_driver_residence_band(self):
        from nomad_tpu.control.wiring import _depth_limit_driver as drv

        base = {"nomad.broker.acks": 0,
                "nomad.overload.shed.service": 0,
                "nomad.overload.shed.batch": 0,
                "nomad.broker.depth_sheds": 0}
        # Shedding while the queue clears fast -> grow.
        assert drv(_view({"nomad.broker.acks": 100,
                          "nomad.broker.depth": 10,
                          "nomad.overload.shed.service": 5,
                          "nomad.overload.shed.batch": 0,
                          "nomad.broker.depth_sheds": 0}, base)) == 1
        # Queue residence past the band -> shrink.
        assert drv(_view({"nomad.broker.acks": 10,
                          "nomad.broker.depth": 100,
                          "nomad.overload.shed.service": 5,
                          "nomad.overload.shed.batch": 0,
                          "nomad.broker.depth_sheds": 0}, base)) == -1
        # No acks -> no residence estimate -> hold.
        assert drv(_view({"nomad.broker.acks": 0,
                          "nomad.broker.depth": 100}, base)) == 0

    def test_brownout_driver_reads_wheel_pressure(self):
        from nomad_tpu.control.wiring import _brownout_ratio_driver as drv

        base = {"nomad.broker.acks": 0,
                "nomad.overload.shed.batch": 0}
        # A backlog of paced expiries keeps brownout engaged.
        assert drv(_view({"nomad.heartbeat.pending_expiries": 12,
                          "nomad.broker.acks": 100,
                          "nomad.broker.depth": 1}, base)) == -1

    def test_runner_depth_driver_learned_floor(self):
        from nomad_tpu.control.wiring import _make_depth_driver

        drv = _make_depth_driver()
        base = {}
        assert drv(_view({"nomad.runner.rtt_ms_ewma": 2.0},
                         base)) == 1      # floor = 2: healthy
        assert drv(_view({"nomad.runner.rtt_ms_ewma": 5.0},
                         base)) == 0      # 2.5x floor: hold band
        assert drv(_view({"nomad.runner.rtt_ms_ewma": 20.0},
                         base)) == -1     # 10x floor: retreat
        assert drv(_view({"nomad.runner.rtt_ms_ewma": 0.0},
                         base)) == 0      # no samples yet


class TestServerWiring:
    def test_server_controller_knobs_and_registry(self):
        from nomad_tpu.server import Server, ServerConfig

        srv = Server(ServerConfig(num_schedulers=0,
                                  control_enabled=True,
                                  control_interval=0.02,
                                  control_seed=11))
        try:
            assert srv.controller is not None
            knobs = srv.controller.stats()["knobs"]
            assert set(knobs) == {
                "broker.depth_limit", "overload.overload_ratio",
                "overload.brownout_ratio", "applier.max_window",
                "applier.max_inflight_commits", "applier.gather_s"}
            # Decisions mirror into the unified registry document.
            snap = srv.obs_registry.snapshot()
            assert "nomad.controller.ticks" in snap
            assert "nomad.controller.knobs.broker.depth_limit.value" \
                in snap
            wait_until(lambda:
                       srv.obs_registry.snapshot()
                       ["nomad.controller.ticks"] >= 2,
                       msg="server controller ticking")
        finally:
            srv.shutdown()
        assert not srv.controller.running()

    def test_depth_limit_actuator_moves_broker_and_pressure_source(self):
        from nomad_tpu.server import Server, ServerConfig

        srv = Server(ServerConfig(num_schedulers=0,
                                  control_enabled=True,
                                  broker_depth_limit=64))
        try:
            act = srv.controller.knob("broker.depth_limit")
            act.apply(128)
            # BOTH the broker's hard bound and the overload pressure
            # source's denominator moved (they must stay one number).
            assert srv.eval_broker.max_depth == 128
            assert srv.config.broker_depth_limit == 128
        finally:
            srv.shutdown()

    def test_set_ratios_preserves_the_invariant(self):
        from nomad_tpu.server.overload import OverloadController

        ctl = OverloadController(brownout_ratio=0.5, overload_ratio=1.0)
        ctl.set_ratios(overload=0.4)
        brown, over = ctl.ratios()
        assert over == 0.4 and brown <= over
        ctl.set_ratios(brownout=0.9)
        brown, over = ctl.ratios()
        assert brown <= over  # clamped, never inverted
        # The hysteresis scaling (enter/exit asymmetry) is untouched.
        assert ctl.hysteresis == 0.9

    def test_liveness_lane_is_out_of_the_controllers_reach(self):
        """Admission correctness invariants: however low the
        controller drives the thresholds, Node.Heartbeat bypasses
        admission entirely and force=True enqueues bypass the depth
        bound — a tuning decision can never shed liveness or diverge
        broker from state."""
        from nomad_tpu.server.eval_broker import EvalBroker
        from nomad_tpu.server.overload import (OVERLOAD, ErrOverloaded,
                                               OverloadController)
        from nomad_tpu.structs import Evaluation, generate_uuid

        ctl = OverloadController(brownout_ratio=0.5, overload_ratio=1.0)
        ctl.set_ratios(brownout=1e-6, overload=1e-6)  # floor of rails
        ctl.add_source("stuck", lambda: (1, 1))       # pressure = 1.0
        assert ctl.state() == OVERLOAD
        ctl.admit_rpc("Node.Heartbeat", {})           # never shed
        with pytest.raises(ErrOverloaded):
            ctl.admit_rpc("Job.Register", {"job": {"type": "service"}})

        broker = EvalBroker(admission=ctl, max_depth=1)
        broker.set_enabled(True)
        try:
            for _ in range(3):  # force: past admission AND the bound
                broker.enqueue(Evaluation(
                    id=generate_uuid(), priority=1, type="service",
                    triggered_by="test", job_id=generate_uuid()),
                    force=True)
            assert broker.stats()["depth"] == 3
        finally:
            broker.shutdown()


# ---------------------------------------------------------------------------
# 4. live commit pipeline: applier knobs move under a real stream
# ---------------------------------------------------------------------------

class TestApplierControl:
    def test_applier_controller_relieves_commit_backpressure(self):
        """A mis-set max_inflight_commits=1 under a live plan stream:
        the applier books backpressure wall, and the AIMD knob grows
        the commit pipeline until the wall subsides."""
        from nomad_tpu.server.eval_broker import EvalBroker
        from nomad_tpu.server.fsm import NomadFSM
        from nomad_tpu.server.plan_apply import PlanApplier
        from nomad_tpu.server.plan_queue import PlanQueue
        from nomad_tpu.server.raft import InmemRaft
        from nomad_tpu.structs import (ALLOC_CLIENT_STATUS_PENDING,
                                       ALLOC_DESIRED_STATUS_RUN,
                                       EVAL_TRIGGER_JOB_REGISTER,
                                       Allocation, Evaluation, Plan,
                                       Resources, codec, generate_uuid)

        broker = EvalBroker(nack_timeout=60.0)
        fsm = NomadFSM(eval_broker=broker)
        raft = InmemRaft(fsm)
        queue = PlanQueue()
        applier = PlanApplier(queue, broker, raft,
                              state_fn=lambda: fsm.state,
                              max_window=8, gather_s=0.002)
        applier.max_inflight_commits = 1
        broker.set_enabled(True)
        queue.set_enabled(True)
        applier.start()
        ctl = applier_controller(applier, queue, broker=broker, seed=3)
        try:
            raft.apply(codec.encode(
                codec.NODE_REGISTER_REQUEST,
                {"node": mock.node(0).to_dict()})).wait()
            node_id = fsm.state.nodes()[0].id
            ctl.tick()  # baseline
            for burst in range(6):
                futures = []
                for _ in range(8):
                    ev = Evaluation(
                        id=generate_uuid(), priority=50,
                        type="service",
                        triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                        job_id=generate_uuid())
                    broker.enqueue(ev, force=True)
                    got, token = broker.dequeue(["service"],
                                                timeout=10)
                    plan = Plan(eval_id=got.id, eval_token=token,
                                priority=50)
                    plan.node_allocation[node_id] = [Allocation(
                        id=generate_uuid(), node_id=node_id,
                        job_id=ev.job_id, task_group="web",
                        resources=Resources(cpu=1, memory_mb=1),
                        desired_status=ALLOC_DESIRED_STATUS_RUN,
                        client_status=ALLOC_CLIENT_STATUS_PENDING)]
                    futures.append((got, token, queue.enqueue(plan)))
                for got, token, fut in futures:
                    fut.wait(30)
                    broker.ack(got.id, token)
                ctl.tick()
            knob = ctl.stats()["knobs"]["applier.max_inflight_commits"]
            stats = applier.stats()
            # The stream committed, backpressure was observed, and the
            # knob either grew past the mis-set floor or the pipeline
            # never saturated (a fast host may drain depth-1 without
            # measurable wall) — in which case holding IS converged.
            assert stats["plans_committed"] == 48
            if stats["commit_backpressure_s"] > 0.01:
                assert knob["value"] > 1
        finally:
            ctl.stop()
            queue.set_enabled(False)
            broker.set_enabled(False)
            applier.shutdown(5.0)
            broker.shutdown()


# ---------------------------------------------------------------------------
# 5. the seeded chaos rig: depth retreat and recovery, no oscillation
# ---------------------------------------------------------------------------

def _pipeline_world(n_nodes, n_jobs):
    from nomad_tpu.scheduler.harness import Harness

    h = Harness()
    for i in range(n_nodes):
        h.state.upsert_node(h.next_index(), mock.node(i))
    jobs = []
    for _ in range(n_jobs):
        j = mock.job()
        h.state.upsert_job(h.next_index(), j)
        jobs.append(j)
    return h, jobs


def _mk_eval(job):
    from nomad_tpu.structs import (EVAL_TRIGGER_JOB_REGISTER, Evaluation,
                                   generate_uuid)

    return Evaluation(id=generate_uuid(), priority=job.priority,
                      type=job.type,
                      triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                      job_id=job.id)


class TestChaosDepthRetreat:
    def test_injected_dispatch_delay_forces_retreat_then_recovery(self):
        """The rig the tentpole names: seeded ``device.dispatch``
        delays inflate the runner's RTT EWMA; the AIMD depth knob
        retreats multiplicatively, then — when the injection stops and
        the EWMA decays back under the probe band — recovers
        additively, WITHOUT oscillating (reversal count bounded by the
        two phase changes; the hold band between 2x and 4x of the
        learned floor is what prevents flapping).

        The EWMA is fed what the rig injects, not the wall clock: every
        sample the runner takes reads a fixed healthy round trip plus
        the delays injected since the sample before.  On the wall
        clock a jit compile or a starved core landing in phase A set
        the learned floor so high that 0.25 s never read as four times
        it."""
        import threading

        from nomad_tpu.scheduler.executor import executor_override
        from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner

        h, jobs = _pipeline_world(8, 40)
        healthy_s, delay_s = 0.002, 0.25
        plan = FaultPlan(seed=5).add("device.dispatch", "delay",
                                     secs=delay_s, count=6)
        with executor_override("device"):
            runner = PipelinedEvalRunner(
                h.state.snapshot(), h, depth=8,
                state_refresh=lambda: h.state.snapshot())
            note_wall = runner._note_rtt
            fires_lock = threading.Lock()
            fires_seen = [0]

            def note_injected(_wall_seconds):
                with fires_lock:  # front and drain stage both sample
                    fires = plan.fire_count("device.dispatch")
                    fresh = fires - fires_seen[0]
                    fires_seen[0] = fires
                note_wall(healthy_s + delay_s * fresh)

            runner._note_rtt = note_injected
            # Warm the compile/prep caches.
            runner.process([_mk_eval(j) for j in jobs[:4]])
            with runner._count_lock:
                runner._rtt_ewma = 0.0  # drop warmup samples
            ctl = runner_controller(runner, seed=7, lo=1, hi=8)
            depth_seen = []

            def round_trip(batch, ticks=1):
                runner.process([_mk_eval(j) for j in batch])
                for _ in range(ticks):
                    ctl.tick()
                depth_seen.append(runner.depth)

            # Phase A (healthy): learn the floor.
            round_trip(jobs[4:8])
            round_trip(jobs[8:12])
            assert runner.depth >= 8 or runner.depth >= depth_seen[0]

            # Phase B (chaos): seeded dispatch delays, every dispatch.
            with faultinject.injected(plan):
                round_trip(jobs[12:15])
                round_trip(jobs[15:18])
            assert runner.depth < 8, depth_seen
            retreated_to = runner.depth

            # Phase C (recovery): clean dispatches decay the EWMA back
            # under the probe band; depth climbs additively.
            for lo in range(18, 38, 4):
                round_trip(jobs[lo:lo + 4])
            assert runner.depth > retreated_to, depth_seen

            # No oscillation: one retreat run + one recovery run.
            knob = ctl.stats()["knobs"]["pipeline.depth"]
            assert knob["reversals"] <= 2, (knob, depth_seen)
            assert knob["rail_hits"] <= 2, knob
        # Every eval still placed (the knob never touched correctness).
        assert all(e.status == "complete" for e in h.evals)


# ---------------------------------------------------------------------------
# 6. the operator drill: pin via the controller
# ---------------------------------------------------------------------------

class TestOperatorPin:
    def test_controller_pin_by_name(self):
        gauges = {"g": 1.0}
        ctl = Controller(lambda: dict(gauges), interval=0.05)
        state, act = _actuator(8)
        ctl.add_knob(act, law=AIMD(), driver=lambda v: +1)
        ctl.pin("k", 3)
        assert state["v"] == 3
        ctl.tick()
        ctl.tick()
        assert state["v"] == 3
        ctl.pin("k", None)
        ctl.tick()
        assert state["v"] == 4
