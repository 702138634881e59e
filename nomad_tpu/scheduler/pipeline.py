"""Staged eval pipeline: hide the device round-trip AND overlap host work.

Every synchronous dispatch costs a fixed round trip (enqueue, run,
device->host copy) regardless of compute size, so a strictly sequential
eval loop is latency-bound: prep -> RTT -> finish, one eval per RTT.
This runner splits the eval into two host stages running on two
threads, with up to ``depth`` device dispatches in flight between them:

  front stage (caller thread)   drain stage (worker thread)
  ---------------------------   ------------------------------------
  reconcile + prep (begin)      collect device results (blocks on the
  dispatch (non-blocking)         wire, GIL released)
  enqueue -> bounded window --> native bulk finish + Python tail
                                plan submit (FIFO = eval order)

While eval N's results cross the wire — and while its C finish loop and
plan submit run — evals N+1..N+depth are reconciled, prepped, and
dispatched, so steady-state throughput is bound by the slower of the
two host stages, not their sum, and never by the RTT.

Host-floor amortization: the drain stage pulls EVERY queued eval it can
and finishes them as one window — a single uuid slab
(structs.generate_uuids) and a single native call
(native/port_alloc.cpp bulk_finish_many) cover the whole window, so the
per-eval Python re-entry cost is paid once per window, not per eval.
Device-side, the dispatch constants (asks/feasibility/usage mirror) stay
resident across the window (DeviceArgs.dev_const + the statics device
cache); input buffers are NOT donated — the usage tensor is the shared
fleet-mirror buffer that in-flight dispatches still read
(models/fleet.py:770), so donation would corrupt the window.

Ordering guarantees, unchanged from the single-threaded runner:
per-job serialization (one in-flight eval per job per round, leftovers
run after a ``state_refresh``) and plan-commit ordering (the drain
stage submits strictly in eval order; even placement-less plans route
through it).

This is the eval-axis analogue of the reference's pipelined
verify/apply (/root/reference/nomad/plan_apply.go:13-37 — plan N+1
verified while plan N's raft apply is in flight) and of its worker-pool
concurrency (/root/reference/nomad/worker.go:50-437): many evals are
optimistically in flight against the same snapshot, and the plan
applier serializes commits.

Use BatchEvalRunner (scheduler/batch.py) when a whole batch is available
up front and shapes are homogeneous — one fused vmap dispatch beats a
pipeline.  Use PipelinedEvalRunner for streams: heterogeneous shapes,
latency-sensitive arrivals, or when plans must commit between evals.
"""
from __future__ import annotations

import logging
import queue
import threading
import time

from nomad_tpu import faultinject
from nomad_tpu.obs import trace as trace_mod
from nomad_tpu.parallel.devices import transient_device_fault

from .batch import BatchEvalRunner, _lane_spans, _tnow
from .breaker import ADMIT_HOST, ADMIT_PROBE, GLOBAL_BREAKER

logger = logging.getLogger("nomad_tpu.scheduler.pipeline")

_STOP = object()


# Score slack the half-open probe grants the device against the host
# scorer.  Scores live in [-penalty * copies, 18] and are built from two
# 10^x terms; a TPU evaluates 10^x a few 1e-6 relative off numpy —
# measured max |dscore| 5.0e-5 for the same node and usage on a v5e at
# the 10k-node shapes (PERF.md, bring-up) — so the slack sits ~20x above
# what rounding produces and ~4 orders below what a wrong node costs.
PROBE_SCORE_ATOL = 1e-3


def probe_agrees(args, chosen) -> bool:
    """The half-open probe's contract: would the host scorer have
    ranked every device pick best, within PROBE_SCORE_ATOL, at the step
    the device made it (ops/binpack_host check_*_host)?

    NOT node-for-node equality with a host re-run: on a TPU 10^x rounds
    differently from numpy, near-tied nodes may swap between the
    engines (chip_smoke.py measures how often: a handful per 1,000
    placements on a used fleet), and after one swap the two engines
    walk different, equally valid usage trajectories — so the host
    scores along the DEVICE's trajectory.  ``chosen`` is the device's
    per-placement node array (collect_device's first result)."""
    import numpy as np

    from nomad_tpu.ops.binpack_host import (check_rounds_host,
                                            check_sequence_host)

    statics = args.statics
    fleet = (statics.capacity, statics.reserved, args.view.usage,
             args.view.job_counts, args.feasible_h, args.asks,
             args.distinct)
    chosen = np.asarray(chosen)
    pen = np.float32(args.penalty)
    if not args.rounds_eligible:
        return check_sequence_host(
            *fleet, args.group_idx, args.valid, pen, chosen,
            atol=PROBE_SCORE_ATOL, n_real=statics.n_real)
    # rounds_to_placements hands a slot's copies their nodes in stream
    # order and leaves the unplaced tail at -1.
    picks = {}
    for slot, ps in args.slot_placements.items():
        nodes = chosen[np.asarray(ps, dtype=np.int64)]
        picks[slot] = nodes[nodes >= 0]
    return check_rounds_host(
        *fleet, args.counts, pen, picks, k_cap=args.k_cap,
        rounds=args.rounds, atol=PROBE_SCORE_ATOL, n_real=statics.n_real)


class _CollectWorker:
    """Long-lived watchdog worker for deadline-bounded device collects.

    The drain stage feeds it one callable at a time via ``inq`` and
    waits on ``outq`` with the deadline; a ``None`` on ``inq`` exits
    the thread.  The runner replaces the worker after a timeout — a
    hung device call cannot be interrupted, so the old worker keeps its
    references only until that call returns, then sees the sentinel
    and dies (no unbounded thread accumulation under a fault burst).
    """

    def __init__(self) -> None:
        self.inq: queue.Queue = queue.Queue()
        self.outq: queue.Queue = queue.Queue()
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="device-collect")
        self.thread.start()

    def _run(self) -> None:
        while True:
            # faultlint-ok(unbounded-wait): idle watchdog-worker
            # parking — exit rides the None sentinel; the collect
            # DEADLINE lives on the outq.get in
            # _collect_device_bounded, not here.
            fn = self.inq.get()
            if fn is None:
                return
            try:
                self.outq.put((True, fn()))
            except BaseException as e:
                self.outq.put((False, e))

    def join(self, timeout: "float | None" = None) -> None:
        """Reap after the exit sentinel.  Only the clean-shutdown path
        may join — an abandoned (hung-collect) worker is deliberately
        left to die on its own when the device call returns."""
        self.thread.join(timeout)


class _Item:
    """One eval moving front -> drain.  ``handles`` is None for
    placement-less plans (submit-only).  ``probe`` marks the breaker's
    half-open probe: the drain stage has the host scorer check its
    device result (probe_agrees) before closing the breaker."""

    __slots__ = ("sched", "place", "args", "handles", "start", "probe")

    def __init__(self, sched, place, args, handles, start,
                 probe: bool = False) -> None:
        self.sched = sched
        self.place = place
        self.args = args
        self.handles = handles
        self.start = start
        self.probe = probe


class PipelinedEvalRunner(BatchEvalRunner):
    """Processes a list of evaluations with up to ``depth`` device
    dispatches in flight and the two host stages overlapped.

    Inherits the batch runner's per-job serialization (one in-flight
    eval per job; leftovers run after a ``state_refresh``), status
    handling, and submit/retry logic.  Unlike the batch runner, every
    eval gets its own dispatch, so evals whose plans already carry
    deltas (migrations, in-place updates) pipeline like any other.

    ``latencies`` records per-eval wall seconds (begin -> plan
    submitted).  ``stage_times`` accumulates per-stage wall seconds
    (begin/dispatch/collect/finish/submit) across the run — the
    single-eval host-floor profile.  ``host_dispatches`` /
    ``device_dispatches`` count which executor each dispatch actually
    used (NOMAD_TPU_EXECUTOR forces it; scheduler/executor.py).
    """

    def __init__(self, state, planner, depth: int = 4,
                 state_refresh=None, breaker=None,
                 device_deadline: "float | None" = None) -> None:
        super().__init__(state, planner, state_refresh=state_refresh)
        self.depth = max(1, depth)
        self.latencies: list[float] = []
        self.stage_times = {"begin": 0.0, "dispatch": 0.0, "collect": 0.0,
                            "finish": 0.0, "submit": 0.0}
        self.windows: list[int] = []  # drained-window sizes (diagnostics)
        # Device-executor circuit breaker (scheduler/breaker.py): failed
        # or deadline-blown device dispatches re-run on the host twin
        # and trip the breaker, which then holds the executor on host
        # with periodic half-open re-probes.  Shared process-wide by
        # default — device health is a machine property, not a runner's.
        self.breaker = breaker if breaker is not None else GLOBAL_BREAKER
        # Optional per-collect watchdog (seconds): None = no watchdog
        # thread (zero overhead; only raised errors trip the breaker).
        self.device_deadline = device_deadline
        # Evals re-run on host after a device failure.  ONE producer:
        # every increment goes through _record_rerun (called from both
        # stages, so it takes _count_lock); the registry exports this
        # counter and the breaker exports its own transition counts —
        # no number has two producers (obs/registry.py).
        self.breaker_reruns = 0
        self._count_lock = threading.Lock()
        # Dispatch/collect RTT EWMA (seconds; device dispatches only) —
        # the feedback control plane's congestion gauge for the AIMD
        # depth knob (control/wiring.wire_runner): injected
        # device.dispatch delay or a genuinely slow chip inflates it,
        # and the learned-floor driver retreats ``depth``.  Guarded by
        # _count_lock (front and drain threads both feed samples).
        self._rtt_ewma = 0.0
        # Live in-flight gate: ``depth`` is a CONTROL KNOB now — the
        # controller adjusts it mid-stream, so the bound is enforced by
        # this counter + condition instead of a fixed-maxsize queue
        # (a Queue's maxsize is frozen at construction).
        self._inflight = 0
        self._inflight_cond = threading.Condition(threading.Lock())
        self.parity_checks = 0    # probe evals parity-asserted host/dev
        # Lazy long-lived watchdog worker for deadline-bounded collects
        # (drain thread only; replaced after a timeout, see
        # _collect_device_bounded).
        self._collect_worker: "_CollectWorker | None" = None
        self._err_lock = threading.Lock()
        self._drain_err: BaseException | None = None
        # Registry provider (obs/registry.py): the LIVE runner's stats
        # under nomad.runner.* — replace-on-name keeps exactly one, and
        # the weakref means a retired runner is never pinned (its state
        # snapshot is a whole store generation) just to serve metrics.
        import weakref

        from nomad_tpu.obs import REGISTRY
        ref = weakref.ref(self)
        REGISTRY.register(
            "runner",
            lambda: (lambda r: r.stats() if r is not None else {})(
                ref()))

    def process(self, evals: list) -> None:
        from nomad_tpu.utils.gctune import gc_pause

        with gc_pause():
            self._process_staged(evals)

    # -- front stage ------------------------------------------------------
    def _process_staged(self, evals: list) -> None:
        this_round, leftovers = self._split_rounds(evals)
        q: queue.Queue = queue.Queue()
        drain = threading.Thread(target=self._drain_loop, args=(q,),
                                 name="eval-pipeline-drain", daemon=True)
        drain.start()
        times = self.stage_times
        try:
            for ev in this_round:
                if self._failed():
                    break
                start = time.perf_counter()
                sched = self._begin_eval(ev, finish_noop=False)
                t_begin = time.perf_counter()
                times["begin"] += t_begin - start
                if sched is None:
                    # Terminal without a plan (bad trigger/status error):
                    # nothing to submit, latency is begin time alone.
                    self.latencies.append(t_begin - start)
                    continue
                if sched.deferred is None:
                    # Placement-less plan: submit-only item, routed
                    # through the drain stage to keep commit order.
                    self._admit_inflight()
                    q.put(_Item(sched, None, None, None, start))
                    continue
                # The permit is held from here until the drain consumes
                # the item; if anything raises before the put (a
                # dispatch whose host fallback ALSO fails), release it
                # — _inflight is runner-lifetime state now, and a
                # leaked permit would shrink every later stream's
                # effective depth.
                self._admit_inflight()
                try:
                    place, args = sched.deferred
                    t_disp = _tnow()
                    handles, probe = self._dispatch(sched, args)
                    if sched.dispatched_host:
                        self.host_dispatches += 1
                    else:
                        self.device_dispatches += 1
                        if sched.dispatched_sharded:
                            self.sharded_dispatches += 1
                        self._note_rtt(time.perf_counter() - t_begin)
                    _lane_spans("sched.dispatch", [sched], t_disp,
                                _tnow(), host=sched.dispatched_host)
                    times["dispatch"] += time.perf_counter() - t_begin
                    q.put(_Item(sched, place, args, handles, start,
                                probe=probe))
                except BaseException:
                    self._release_inflight()
                    raise
        finally:
            q.put(_STOP)
            drain.join()
            self._stop_collect_worker()
        with self._err_lock:
            err = self._drain_err
        if err is not None:
            raise err
        if leftovers:
            self._process_leftovers(leftovers)

    def _failed(self) -> bool:
        with self._err_lock:
            return self._drain_err is not None

    def _dispatch(self, sched, args) -> tuple:
        """Route one eval's dispatch through the executor policy AND the
        circuit breaker.  Returns (handles, probe): evals the breaker
        holds run the host twin (the same math; see probe_agrees); a
        half-open probe runs the device and is parity-checked in the
        drain stage; a dispatch that raises a runtime fault
        (``transient_device_fault``) trips the breaker and falls back to
        host immediately — anything else propagates."""
        if sched.choose_host_executor(args, pipelined=True):
            sched.dispatched_host = True
            return sched.dispatch_host(args), False
        admit = self.breaker.admit()
        if admit == ADMIT_HOST:
            sched.dispatched_host = True
            return sched.dispatch_host(args), False
        probe = admit == ADMIT_PROBE
        try:
            if faultinject.ACTIVE:
                faultinject.fire("device.dispatch")
            # force=True: the executor decision was made above (policy
            # + breaker); re-evaluating it inside dispatch_device could
            # route a half-open probe to the host twin and orphan it.
            return sched.dispatch_device(args, pipelined=True,
                                         force=True), probe
        except Exception as e:
            if not transient_device_fault(e):
                raise
            logger.warning("device dispatch failed; re-running eval on "
                           "the host twin", exc_info=True)
            self.breaker.record_failure(probe=probe)
            self._record_rerun()
            sched.dispatched_host = True
            return sched.dispatch_host(args), False

    def _record_rerun(self) -> None:
        """The single producer of ``breaker_reruns`` (cross-thread:
        front stage on dispatch faults, drain stage on collect faults)."""
        with self._count_lock:
            self.breaker_reruns += 1

    def _note_rtt(self, seconds: float) -> None:
        """Feed one device dispatch/collect wall sample into the RTT
        EWMA (the control plane's congestion gauge)."""
        with self._count_lock:
            prev = self._rtt_ewma
            self._rtt_ewma = seconds if prev <= 0.0 \
                else 0.8 * prev + 0.2 * seconds

    def _admit_inflight(self) -> None:
        """Block until the in-flight window has room under the LIVE
        ``depth`` knob (re-read each pass: the control plane adjusts it
        mid-stream).  A dead drain stage still admits — the front loop
        notices ``_failed()`` and stops, and the teardown put must
        never deadlock behind a gate nobody will drain."""
        while True:
            bound = max(1, int(self.depth))  # re-read: a live knob
            with self._inflight_cond:
                if self._inflight < bound:
                    self._inflight += 1
                    return
                self._inflight_cond.wait(0.05)
            if self._failed():
                with self._inflight_cond:
                    self._inflight += 1
                return

    def _release_inflight(self) -> None:
        with self._inflight_cond:
            self._inflight -= 1
            self._inflight_cond.notify_all()

    def stats(self) -> dict:
        """Registry provider (obs/registry.py): the runner's dispatch
        mix, stage walls, windows, and breaker interactions."""
        with self._count_lock:
            reruns = self.breaker_reruns
            rtt_ewma = self._rtt_ewma
        dispatches = self.host_dispatches + self.device_dispatches
        return {
            **super().stats(),
            # Control-plane gauges: the live depth knob, the fraction
            # of dispatches that actually rode the device, and the
            # dispatch/collect RTT EWMA the AIMD depth driver reads.
            "depth": self.depth,
            "device_fraction": self.device_dispatches / dispatches
            if dispatches else 0.0,
            "rtt_ms_ewma": round(rtt_ewma * 1000.0, 4),
            "breaker_reruns": reruns,
            "parity_checks": self.parity_checks,
            "evals": len(self.latencies),
            "windows": len(self.windows),
            "stage_times_ms": {k: round(v * 1000.0, 3)
                               for k, v in self.stage_times.items()},
        }

    # -- drain stage ------------------------------------------------------
    def _drain_loop(self, q: queue.Queue) -> None:
        stop_seen = False
        try:
            while True:
                item = q.get()
                if item is _STOP:
                    return
                self._release_inflight()
                window = [item]
                # Opportunistic window: everything already queued drains
                # as ONE batch (shared uuid slab, one native call).
                while True:
                    try:
                        nxt = q.get_nowait()
                    except queue.Empty:
                        break
                    if nxt is _STOP:
                        stop_seen = True
                        break
                    self._release_inflight()
                    window.append(nxt)
                self._drain_window(window)
                if stop_seen:
                    return
        except BaseException as e:
            with self._err_lock:
                self._drain_err = e
            # Keep consuming so the front stage never deadlocks on a
            # full window; items are discarded (their evals get no
            # status — the front stops and the error propagates).  If
            # the window-gather already swallowed the sentinel there is
            # nothing left to wait for — blocking on q.get() here WAS a
            # deadlock (the front is in drain.join() by then).
            if not stop_seen:
                while q.get() is not _STOP:
                    self._release_inflight()

    def _drain_window(self, window: list) -> None:
        times = self.stage_times
        self.windows.append(len(window))

        # 1) collect: block on each dispatch's results, FIFO.  Result
        # copies were started at dispatch (copy_to_host_async), so
        # waiting on eval N overlaps N+1's transfer too.  A device
        # collect that fails or blows the deadline re-runs on the host
        # twin and trips the breaker (the window keeps draining).
        t0 = time.perf_counter()
        work = [it for it in window if it.handles is not None]
        results = {}
        for it in work:
            t_col = _tnow()
            results[id(it)] = self._collect_item(it)
            _lane_spans("sched.collect", [it.sched], t_col, _tnow())
        t1 = time.perf_counter()
        times["collect"] += t1 - t0

        # 2) finish: the shared windowed-finish sequence — one uuid slab
        # + one native call + Python tails (BatchEvalRunner._finish_lanes
        # is the single implementation).
        self._finish_lanes([(it.sched, it.place, it.args)
                            + tuple(results[id(it)]) for it in work])
        t2 = time.perf_counter()
        times["finish"] += t2 - t1

        # 3) submit, strictly in eval order (noop items interleave at
        # their original position), as ONE group through the planner's
        # window path when it has one — the drain window is exactly the
        # commit window the group-commit applier amortizes.
        self._submit_window([it.sched for it in window])
        now = time.perf_counter()
        for it in window:
            self.latencies.append(now - it.start)
        times["submit"] += now - t2

    # -- device failure handling (breaker) ---------------------------------
    def _collect_item(self, it: _Item) -> tuple:
        """Collect one item's results, routing device outcomes through
        the circuit breaker.  Probe items are additionally checked by
        the host scorer (probe_agrees) before the breaker closes."""
        sched = it.sched
        if sched.dispatched_host:
            # faultlint-ok(uninjectable-io): host-lane collect (the
            # work never went to the device); the device seam consults
            # device.collect in _collect_device_bounded.
            return sched.collect_device(it.args, it.handles)
        try:
            t_col = time.perf_counter()
            res = self._collect_device_bounded(it)
            self._note_rtt(time.perf_counter() - t_col)
        except Exception as e:
            if not transient_device_fault(e):
                raise
            logger.warning("device collect failed (%s); re-running eval "
                           "on the host twin", e)
            self.breaker.record_failure(probe=it.probe)
            self._record_rerun()
            return self._host_rerun(it)
        if it.probe:
            # A disagreement here means the device path is corrupting
            # plans and MUST fail loudly, not degrade — an explicit
            # raise (not an assert, which -O would strip) so the probe
            # can never close the breaker unverified.
            if not probe_agrees(it.args, res[0]):
                self.breaker.record_failure(probe=it.probe)
                raise RuntimeError(
                    "device/host parity violation on breaker probe")
            self.parity_checks += 1
            self.breaker.record_success(probe=True)
            return res
        self.breaker.record_success()
        return res

    def _collect_device_bounded(self, it: _Item) -> tuple:
        """Device collect with the optional watchdog deadline: a hung
        collect raises TimeoutError.  One long-lived worker is reused
        across collects (no thread churn on the drain hot path) and
        replaced only after a timeout — the abandoned worker drains its
        hung call whenever the device returns, then exits via the
        sentinel so it never lingers past that."""
        def _collect():
            if faultinject.ACTIVE:
                faultinject.fire("device.collect")
            return it.sched.collect_device(it.args, it.handles)

        if self.device_deadline is None:
            return _collect()
        worker = self._collect_worker
        if worker is None:
            worker = self._collect_worker = _CollectWorker()
        worker.inq.put(_collect)
        try:
            ok, val = worker.outq.get(timeout=self.device_deadline)
        except queue.Empty:
            # Hung: abandon this worker (its queues go with it, so the
            # stale result can never be mistaken for a later eval's)
            # and tell it to exit once the device call finally returns.
            self._collect_worker = None
            worker.inq.put(None)
            raise TimeoutError(
                f"device collect exceeded deadline "
                f"({self.device_deadline}s)") from None
        if not ok:
            raise val
        return val

    def _stop_collect_worker(self) -> None:
        worker = self._collect_worker
        if worker is not None:
            self._collect_worker = None
            worker.inq.put(None)
            worker.join(2.0)

    def _host_rerun(self, it: _Item) -> tuple:
        """Re-run one eval's placement on the host twin kernels."""
        handles = it.sched.dispatch_host(it.args)
        # faultlint-ok(uninjectable-io): host-twin rerun AFTER a device
        # fault — injecting here would fault the very fallback the
        # breaker depends on.
        return it.sched.collect_device(it.args, handles)
