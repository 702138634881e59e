"""Batched optimistic scheduling: many evaluations, one device dispatch.

This is the TPU-native replacement for the reference's worker-pool
concurrency (reference nomad/worker.go:50-437 — NumCPU goroutines each
processing one eval at a time against its own snapshot).  Here a batch of
evaluations is reconciled on host, their placement sequences are stacked
along a vmap axis, and a single device dispatch plans ALL of them against
the same state snapshot.  Exactly like the reference's optimistic
concurrency, plans may conflict; the plan applier serializes commits and
rejected plans are retried individually (reference nomad/plan_apply.go).

Fast-path contract: an eval joins the fused dispatch only if its plan has no
deltas yet (no migrations/in-place updates), so every lane shares the same
base usage tensor — lanes diverge only through their own placements.  Evals
with plan deltas fall back to their own dispatch (still device-side).
"""
from __future__ import annotations

import numpy as np

from types import SimpleNamespace
from typing import Callable, Optional

from nomad_tpu.obs import trace as trace_mod
from nomad_tpu.structs import (
    EVAL_STATUS_COMPLETE,
    EVAL_STATUS_FAILED,
    Evaluation,
)

from .generic import VALID_GENERIC_TRIGGERS
from .interfaces import SetStatusError
from .jax_binpack import JaxBinPackScheduler, fetch_results
from .util import set_status

def _tnow() -> float:
    """Tracer-epoch now, 0.0 when tracing is off (obs/trace.py)."""
    t = trace_mod.tracer()
    return t.now() if t is not None else 0.0


def pad_lanes(n: int) -> int:
    """Next power of two >= n (>= 1): the lane-axis bucket of the fused
    dispatch — the vmapped kernels trace once per distinct lane count,
    so the batch size must be bucketed exactly like the group and
    placement axes (models/fleet._pad_to) or a drifting storm recompiles
    per size."""
    return 1 << max(0, (n - 1).bit_length())


def _lane_spans(name: str, scheds, t0: float, t1: float,
                span_ids: Optional[dict] = None, **tags) -> None:
    """One span per lane sharing the window's [t0, t1] — fused stages
    (dispatch, finish, submit) run once for the whole window, and every
    member eval's tree records the window it rode (the shared
    timestamps make the fusion visible in the exported trace).
    ``span_ids`` (eval id -> span id) pins the ids of lanes whose stage
    already has children (``sched.status`` under ``sched.submit``)."""
    tracer = trace_mod.tracer() if trace_mod.ENABLED else None
    if tracer is None:
        # Includes a concurrent disable() racing the ENABLED check:
        # degrade to untraced, never fail the lane.
        return
    for sched in scheds:
        ev = sched.eval
        if ev is not None and ev.trace:
            tracer.record(name, t0, t1 - t0, parent_ctx=ev.trace,
                          span_id=span_ids.get(ev.id) if span_ids
                          else None, eval_id=ev.id, **tags)


def _stage_spans(name: str, scheds, clock, span_ids: Optional[dict] = None,
                 **tags) -> None:
    """``_lane_spans`` over the stage ``clock`` (a ``StageClock`` of the
    calling thread, None while tracing is off) closes now: one span a
    lane over the stage's one interval, each carrying its one
    ``cpu_s`` / ``blocked_s`` pair (readers count a window once)."""
    if clock is None:
        return
    t0, dur, pair = clock.lap()
    _lane_spans(name, scheds, t0, t0 + dur, span_ids, **pair, **tags)


def dispatch_tags(rounds_mode: bool, rounds: int, engine: str,
                  cost: int, lanes: int, slots: int, twin=None) -> dict:
    """What a lane's ``sched.dispatch`` span says of the kernel it rode
    (nothing while tracing is off).  ``mode`` is the kernel the
    dispatch ran — ``rounds`` (one scoring pass per slot and top-k
    round) or ``sequence`` (one pass per placement; ``_fit_rounds``
    says when) — with ``rounds`` the round count of that dispatch (a
    fused window runs its widest lane's; 0 on the sequence kernel);
    ``engine`` is who ran it: ``host`` (the numpy twin), ``device``
    (the XLA kernel on one chip) or ``sharded`` (over a mesh), as
    ``scheduler/executor.py`` chose; ``cost`` is the estimate the
    choice was made on (``JaxBinPackScheduler.host_wins``) and
    ``lanes`` how many lanes shared that choice (a fused window's, 1
    for a lone eval); ``slots`` is THIS lane's real slot count
    (``DeviceArgs.n_groups``: its task groups after those of one ask
    have deduped), where ``cost`` counts the padded axis.  On the host
    engine, with ``twin`` the lane's scheduler: ``twin_rows`` of
    ``twin_rows_full``, the rows the twin's rounds passes scored for it
    (``place_rounds_host``'s candidate sets, summed over slots and
    rounds) of those whole passes score (both 0 on the sequence
    kernel)."""
    if not trace_mod.ENABLED:
        return {}
    tags = {"mode": "rounds" if rounds_mode else "sequence",
            "rounds": rounds if rounds_mode else 0, "engine": engine,
            "cost": cost, "lanes": lanes, "slots": slots}
    if twin is not None and engine == "host":
        tags["twin_rows"] = twin.twin_rows
        tags["twin_rows_full"] = twin.twin_rows_full
    return tags


# Fused window shapes ``(b_pad, g_pad, n_pad)`` whose lane stacks a
# runner keeps (``_WindowStaging``), the least recently used dropped
# first: a storm meets one to three (lane buckets, slot axes).
STAGED_SHAPES = 4
# A lane position's job-count rows where it wrote none.
_NO_ROWS = np.zeros(0, dtype=np.int64)


class _WindowStaging:
    """A fused window's two fleet-wide operands at one shape, kept from
    window to window and rewritten only where its lanes changed: the
    feasibility stack ``[b_pad, g_pad, n_pad]`` and the job counts
    ``[b_pad, n_pad]``.  A lane position's mask rows are rewritten when
    the lane's ``feas_key`` (its slot keys and ``g_pad``, in one fleet
    generation: what the rows are a function of) is not the key they
    hold; its job counts by clearing the rows it wrote last time and
    writing the lane's own non-nought rows.  Pad lanes and a lane's pad
    slots keep the mask rows they held: their counts are nought
    (``valid`` False on the sequence kernel), so they place nothing,
    and no caller reads what they score.  Pad lanes' job counts are
    cleared, so with no real lane counted the stack is all nought.

    Beside the host buffers, device arrays: the feasibility stack as
    last uploaded, passed again while no position was rewritten, and a
    stack of nought job counts, passed while no real lane holds a
    count.  Host buffers are rewritten only by the next window, after
    this one fetched its results, so no upload is in flight then."""

    def __init__(self, b_pad: int, g_pad: int, n_pad: int) -> None:
        self.feasible = np.zeros((b_pad, g_pad, n_pad), dtype=bool)
        self.keys: list = [None] * b_pad
        self.job_counts = np.zeros((b_pad, n_pad), dtype=np.int32)
        self.rows: list = [_NO_ROWS] * b_pad
        self.feasible_d = None
        self.nought_d = None

    def stage(self, pending: list) -> tuple[int, bool]:
        """Bring the host buffers to ``pending``'s lanes: (real lane
        positions whose mask rows were rewritten, whether a real lane
        holds a job count)."""
        restaged = 0
        for b, (_s, _p, a) in enumerate(pending):
            if a.feas_key is None or a.feas_key != self.keys[b]:
                self.feasible[b, :a.g_pad] = a.feasible_h
                self.keys[b] = a.feas_key
                restaged += 1
        if restaged:
            # The device stack no longer holds what the host buffer does.
            self.feasible_d = None
        counted = False
        jc = self.job_counts
        for b, held in enumerate(self.rows):
            if len(held):
                jc[b, held] = 0
            rows = _NO_ROWS
            if b < len(pending):
                view_jc = pending[b][2].view.job_counts
                rows = np.flatnonzero(view_jc != 0)
                if len(rows):
                    jc[b, rows] = view_jc[rows]
                    counted = True
            self.rows[b] = rows
        return restaged, counted

    def resident(self, restaged: int, counted: bool) -> tuple[bool, bool]:
        """May the window pass the device feasibility stack as it is,
        and the device stack of nought job counts?"""
        from nomad_tpu.parallel.devices import on_default_platform

        def held(d) -> bool:
            return d is not None and on_default_platform(d)
        return (not restaged and held(self.feasible_d),
                not counted and held(self.nought_d))


class BatchEvalRunner:
    """Fuses a batch of evaluations into one device dispatch.

    Per-job serialization: the eval broker guarantees at most one in-flight
    eval per job, so batches it hands out never collide.  When called
    directly with several evals for the SAME job, only the first joins each
    round; the rest run in follow-up rounds against a refreshed snapshot
    (``state_refresh``) so they see the earlier round's commits — without a
    refresh hook the leftovers would double-place, so they are then failed
    rather than silently over-scheduled.

    Retries (``process``): with a refresh hook, lanes whose plans came
    back partial re-plan together in up to ``FUSED_RETRY_ROUNDS`` fused
    rounds, each on a snapshot taken after the round before it; the
    stragglers then re-plan one by one, each on a snapshot taken right
    before it, so it sees every earlier straggler's commits and plans
    once.  Without a hook there are no rounds: a partial plan re-plans
    at once on the state its scheduler holds by then (the snapshot its
    own ``submit_plan`` handed back).
    """

    # Fused rounds a batch may run (the first included) before its
    # stragglers re-plan one by one, each on the store as it is then.
    FUSED_RETRY_ROUNDS = 2

    def __init__(self, state, planner,
                 state_refresh: Optional[Callable] = None) -> None:
        self.state = state
        self.planner = planner
        self.state_refresh = state_refresh
        # Dispatch mix: kernel calls by the executor that actually ran
        # them (a fused device dispatch is ONE call for all its lanes;
        # the host twin runs one call per lane).  ``sharded`` counts
        # the device calls that rode a mesh.  Written by the thread
        # that drives ``process``; the registry reads plain ints.
        self.host_dispatches = 0
        self.device_dispatches = 0
        self.sharded_dispatches = 0
        self.fused_batches = 0   # fused windows planned, either executor
        # The same mix by the work: lanes (evals) the device engine
        # placed; the twin runs one call a lane, so its lanes ARE
        # ``host_dispatches``.
        self.device_lanes = 0
        # The slot axis of every kernel call, either engine: the real
        # slots its lanes carried, and the slots of the padded axes it
        # was shaped to (``g_pad`` a lane; a fused device window scans
        # ``b_pad`` x ``g_pad``, the twin skips the padding).
        self.slots = 0
        self.padded_slots = 0
        # One-by-one re-plans (``_retry_sequential``) and the attempts
        # they took: equal while every re-plan starts from a snapshot
        # that holds the re-plans before it.
        self.replans = 0
        self.replan_attempts = 0
        # Its schedulers' ``usage_walks`` (scheduler/jax_binpack.py):
        # views built from every allocation in the store.  A snapshot
        # older than the usage mirror is another worker's doing, never
        # this runner's own.
        self.usage_walks = 0
        # Its schedulers' ``fit_rows`` / ``fit_rows_full``: rows the
        # preps' fit walks (``_fit_rounds``) examined, and the rows
        # they would have examined had none stopped early.
        self.fit_rows = 0
        self.fit_rows_full = 0
        # Its schedulers' ``twin_rows`` / ``twin_rows_full``: rows the
        # numpy twin's rounds passes scored, and the rows they would
        # have scored had every candidate set been the fleet.
        self.twin_rows = 0
        self.twin_rows_full = 0
        # Its schedulers' ``feasible_rows`` / ``feasible_rows_full`` /
        # ``distinct_slots``: the rows the preps' slot masks admitted,
        # ``n_real`` a slot, and the slots under ``distinct_hosts``.
        self.feasible_rows = 0
        self.feasible_rows_full = 0
        self.distinct_slots = 0
        # Finish: per-node network states built, and how many of those
        # walked the node's allocations because the usage mirror's
        # occupancy could not serve them (nomad.finish.*).
        self.finish_node_inits = 0
        self.finish_node_walks = 0
        # Fused device windows' kept lane stacks by shape, for the fleet
        # generation ``staging_gen`` (``_WindowStaging``), and how often
        # they were reused: real lane positions whose mask rows were
        # rewritten, and windows that passed the device feasibility
        # stack or the nought job counts as they were.
        self.staging: dict = {}
        self.staging_gen = None
        self.window_lanes_restaged = 0
        self.window_feas_reuses = 0
        self.window_jc_reuses = 0
        # Tracing only: eval id -> span id of the stage span (sched.begin
        # / sched.submit) that eval is under right now; the planner's
        # ``sched.status`` span (server/worker.py) takes it as parent.
        self.stage_span: dict = {}

    def _note_dispatch(self, sched) -> None:
        """Fold one scheduler's own kernel-call counts (its single-eval
        dispatches and finish-loop host re-plans) and its whole-store
        usage walks, fit-walk rows and twin rows into the mix."""
        calls = sched.kernel_calls
        self.host_dispatches += calls["host"]
        self.device_dispatches += calls["device"]
        self.sharded_dispatches += calls["sharded"]
        self.device_lanes += calls["device"]
        sched.kernel_calls = dict.fromkeys(calls, 0)
        self.slots += sched.kernel_slots["real"]
        self.padded_slots += sched.kernel_slots["padded"]
        sched.kernel_slots = dict.fromkeys(sched.kernel_slots, 0)
        self.usage_walks += sched.usage_walks
        sched.usage_walks = 0
        self.fit_rows += sched.fit_rows
        self.fit_rows_full += sched.fit_rows_full
        sched.fit_rows = sched.fit_rows_full = 0
        self.twin_rows += sched.twin_rows
        self.twin_rows_full += sched.twin_rows_full
        sched.twin_rows = sched.twin_rows_full = 0
        self.feasible_rows += sched.feasible_rows
        self.feasible_rows_full += sched.feasible_rows_full
        self.distinct_slots += sched.distinct_slots
        sched.feasible_rows = sched.feasible_rows_full = 0
        sched.distinct_slots = 0

    def _note_finish(self, scheds: list) -> dict:
        """Fold the schedulers' node-init counts into nomad.finish.*;
        returns them as the tags of the ``sched.finish`` span."""
        inits = sum(s.net_inits for s in scheds)
        walks = sum(s.net_walks for s in scheds)
        for s in scheds:
            s.net_inits = s.net_walks = 0
        self.finish_node_inits += inits
        self.finish_node_walks += walks
        return {"node_inits": inits, "walked": walks}

    def stats(self) -> dict:
        """Registry provider (obs/registry.py): the dispatch mix."""
        return {
            "host_dispatches": self.host_dispatches,
            "device_dispatches": self.device_dispatches,
            "sharded_dispatches": self.sharded_dispatches,
            "fused_batches": self.fused_batches,
            "host_lanes": self.host_dispatches,
            "device_lanes": self.device_lanes,
            "slots": self.slots,
            "padded_slots": self.padded_slots,
            "replans": self.replans,
            "replan_attempts": self.replan_attempts,
            "usage_walks": self.usage_walks,
            "fit_rows": self.fit_rows,
            "fit_rows_full": self.fit_rows_full,
            "twin_rows": self.twin_rows,
            "twin_rows_full": self.twin_rows_full,
            "feasible_rows": self.feasible_rows,
            "feasible_rows_full": self.feasible_rows_full,
            "distinct_slots": self.distinct_slots,
            "window_lanes_restaged": self.window_lanes_restaged,
            "window_feas_reuses": self.window_feas_reuses,
            "window_jc_reuses": self.window_jc_reuses,
        }

    def _staging_take(self, shape: tuple, gen: int) -> _WindowStaging:
        """Check out the kept lane stacks of one window shape (new ones
        where none are kept) for one window; all are dropped when the
        fleet generation moves.  ``_staging_keep`` takes them back once
        the window fetched its results: a window that raises before
        leaves none behind, so no later window reads buffers it left
        half written or a device stack still in transfer."""
        if gen != self.staging_gen:
            self.staging.clear()
            self.staging_gen = gen
        return self.staging.pop(shape, None) or _WindowStaging(*shape)

    def _staging_keep(self, shape: tuple, staging: _WindowStaging) -> None:
        """Keep a window's lane stacks: ``STAGED_SHAPES`` at most, the
        least recently used dropped first."""
        if len(self.staging) >= STAGED_SHAPES:
            del self.staging[next(iter(self.staging))]
        self.staging[shape] = staging    # the most recently used last

    def finish_stats(self) -> dict:
        """Registry provider: how often the finish seeds a node from
        the usage mirror (``node_inits`` − ``node_walks``) against how
        often it walks the node's allocations."""
        return {
            "node_inits": self.finish_node_inits,
            "node_walks": self.finish_node_walks,
        }

    def _split_rounds(self, evals: list[Evaluation]
                      ) -> tuple[list, list]:
        """Serialize by job: one eval per job per round; the rest run in
        follow-up rounds against a refreshed snapshot."""
        seen_jobs: set = set()
        this_round, leftovers = [], []
        for ev in evals:
            if ev.job_id in seen_jobs:
                leftovers.append(ev)
            else:
                seen_jobs.add(ev.job_id)
                this_round.append(ev)
        return this_round, leftovers

    def _begin_eval(self, ev: Evaluation, finish_noop: bool = True):
        """Instantiate and reconcile one eval up to its deferred device
        args.  Returns the scheduler ready to dispatch, or None when the
        eval finished without needing a device dispatch (bad trigger,
        status error, or a plan with no placements).

        ``finish_noop=False`` returns the scheduler for a
        placement-less plan instead of submitting it here (deferred is
        None): the staged pipeline routes even those submits through
        its drain stage so plan-commit order stays eval order."""
        tracer = trace_mod.tracer() if trace_mod.ENABLED else None
        if tracer is not None:
            if not ev.trace:
                # Harness/bench evals arrive without a server-stamped
                # anchor: root their tree here so scheduler stages
                # still form one tree per eval.
                ev.trace = tracer.anchor("eval.created",
                                         eval_id=ev.id,
                                         eval_type=ev.type)
            clock = trace_mod.StageClock(tracer)
            sid = self.stage_span[ev.id] = tracer.new_id()
            sched = None
            try:
                sched = self._begin_eval_inner(ev, finish_noop)
                return sched
            finally:
                del self.stage_span[ev.id]
                # ``slots``: the real slots this lane takes to a kernel;
                # ``fit_rows`` of ``fit_rows_full``: the rows its prep's
                # fit walk examined, of those a whole walk examines;
                # ``feasible_rows`` of ``feasible_rows_full``: the rows
                # its slots' masks admit, of ``n_real`` a slot, and
                # ``distinct_slots`` (none where the eval needs no
                # placement).
                tags = {"slots": sched.deferred[1].n_groups,
                        "fit_rows": sched.fit_rows,
                        "fit_rows_full": sched.fit_rows_full,
                        "feasible_rows": sched.feasible_rows,
                        "feasible_rows_full": sched.feasible_rows_full,
                        "distinct_slots": sched.distinct_slots} \
                    if sched is not None and sched.deferred is not None \
                    else {}
                t0, dur, pair = clock.lap()
                tracer.record("sched.begin", t0, dur,
                              parent_ctx=ev.trace, span_id=sid,
                              eval_id=ev.id, **pair, **tags)
        return self._begin_eval_inner(ev, finish_noop)

    def _begin_eval_inner(self, ev: Evaluation, finish_noop: bool = True):
        sched = JaxBinPackScheduler(self.state, self.planner,
                                    batch=(ev.type == "batch"))
        sched.eval = ev
        if ev.triggered_by not in VALID_GENERIC_TRIGGERS:
            set_status(self.planner, ev, None, EVAL_STATUS_FAILED,
                       f"scheduler cannot handle '{ev.triggered_by}' "
                       "evaluation reason")
            return None
        sched.defer_device = True
        try:
            sched._begin()
        except SetStatusError as e:
            set_status(self.planner, ev, None, e.eval_status, str(e))
            return None
        sched.defer_device = False
        if sched.deferred is None:
            if not finish_noop:
                return sched
            # No placements needed: submit stops/updates directly.
            self._finish(sched)
            return None
        return sched

    def process(self, evals: list[Evaluation]) -> None:
        from nomad_tpu.utils.gctune import gc_pause

        with gc_pause():
            pending = list(evals)
            # Fused retry rounds: lanes whose plans came back partial or
            # rejected re-plan TOGETHER against a refreshed snapshot —
            # under contention the applier's serialized conflicts, not
            # planning, dominate, and one fused round retries them all
            # for one dispatch.  Without a refresh hook ``_process``
            # retries each such lane itself, at once; with one, the
            # stragglers after the round cap take the same exact
            # per-eval retry (the single-eval worker path's terminal
            # guarantee), each from the store as it is by then: the
            # stragglers before it have committed, and a snapshot that
            # lacks them would send it to the nodes they just filled.
            rounds = self.FUSED_RETRY_ROUNDS \
                if self.state_refresh is not None else 1
            for _ in range(rounds):
                retries = [] if self.state_refresh is not None else None
                self._process(pending, retries)
                if not retries:
                    return
                pending = retries
                self.state = self.state_refresh()
            for ev in pending:
                # ``retry.refresh``: the snapshot a straggler plans on,
                # a leaf under its anchor just before its ``sched.retry``.
                clock = trace_mod.stage_clock() if trace_mod.ENABLED \
                    else None
                self.state = self.state_refresh()
                _stage_spans("retry.refresh", [SimpleNamespace(eval=ev)],
                             clock)
                self._retry_sequential(self.state, ev)

    def _retry_sequential(self, state, ev: Evaluation) -> None:
        """Exact per-eval retry (fresh scheduler, full process)."""
        retry = JaxBinPackScheduler(state, self.planner,
                                    batch=(ev.type == "batch"))
        clock = trace_mod.stage_clock() if trace_mod.ENABLED else None
        retry.process(ev)
        self.replans += 1
        self.replan_attempts += retry.attempts
        # The kernel calls of this re-plan by engine (each plans the
        # eval once more: a lane of its own), how often ``retry_max``
        # ran it, the views it built by walking the whole store, the
        # seconds its ``dispatch_host`` calls spent in the numpy twin
        # with the real slots they carried (a re-plan has no
        # ``sched.dispatch`` span of its own), and the rows its preps'
        # fit walks examined, its twin's rounds passes scored and its
        # slots' masks admitted, before they are folded.
        calls = {"host_calls": retry.kernel_calls["host"],
                 "device_calls": retry.kernel_calls["device"],
                 "attempts": retry.attempts,
                 "usage_walks": retry.usage_walks,
                 "twin_s": retry.twin_s,
                 "twin_slots": retry.twin_slots,
                 "fit_rows": retry.fit_rows,
                 "fit_rows_full": retry.fit_rows_full,
                 "twin_rows": retry.twin_rows,
                 "twin_rows_full": retry.twin_rows_full,
                 "feasible_rows": retry.feasible_rows,
                 "feasible_rows_full": retry.feasible_rows_full,
                 "distinct_slots": retry.distinct_slots} \
            if trace_mod.ENABLED else {}
        self._note_dispatch(retry)
        self._note_finish([retry])
        # One span over the whole re-plan, parent of its attempts'
        # stages.  Its status write is a sibling ``sched.status`` under
        # the eval's anchor, not a child.
        tracer = trace_mod.tracer() if clock is not None else None
        if tracer is None or not ev.trace:
            return
        r0, r_dur, r_pair = clock.lap()
        sid = tracer.new_id()
        under = {"trace_id": ev.trace.get("trace_id"), "span_id": sid}
        for name, attempt, (t0, dur, pair), facts in retry.stage_log:
            args = facts.pop("args", None)
            if args is not None:    # ``retry.dispatch``: the kernel call
                facts.update(dispatch_tags(
                    args.rounds_eligible, args.rounds, facts["engine"],
                    retry.dispatch_cost(args), 1, args.n_groups))
            tracer.record(name, t0, dur, parent_ctx=under, eval_id=ev.id,
                          attempt=attempt, **pair, **facts)
        _lane_spans("sched.retry", [retry], r0, r0 + r_dur, {ev.id: sid},
                    **r_pair, **calls)

    def _process(self, evals: list[Evaluation],
                 retries: Optional[list] = None) -> None:
        from nomad_tpu.ops.binpack import place_sequence_batch

        this_round, leftovers = self._split_rounds(evals)

        pending = []  # (scheduler, place, DeviceArgs)
        for ev in this_round:
            sched = self._begin_eval(ev)
            if sched is None:
                continue
            place, args = sched.deferred
            if sched.plan.node_update or sched.plan.node_allocation:
                # Plan already carries deltas (migrations, in-place
                # updates): base usage differs, run its own dispatch.
                self._run_single(sched, place, args, retries)
                continue
            pending.append((sched, place, args))

        if not pending:
            if leftovers:
                self._process_leftovers(leftovers)
            return

        g_max = max(a.g_pad for _, _, a in pending)
        p_max = max(a.p_pad for _, _, a in pending)
        statics = pending[0][2].statics
        B = len(pending)
        # The lane axis is bucketed to a power of two exactly like the
        # group/placement axes (g_pad/p_pad): the vmapped kernels trace
        # per distinct lane count, and a storm whose batch size drifts
        # 3, 5, 6, ... would recompile per size (~0.5s each) — the
        # recompile-churn class devlint's provenance pass flags.  Pad
        # lanes have counts 0 (valid False) and place nothing, whatever
        # mask rows they hold; results are consumed per real lane only.
        B_pad = pad_lanes(B)
        rounds_ok = all(a.rounds_eligible for _, _, a in pending)
        k_cap = max(a.k_cap for _, _, a in pending)
        rounds = max(a.rounds for _, _, a in pending)

        # Executor policy (JaxBinPackScheduler.host_executor: the one
        # comparison, and the same NOMAD_TPU_EXECUTOR override): a
        # fused dispatch pays one device round trip + a [B, G, N]
        # upload; below the break-even the numpy kernels finish before
        # the request would even reach the device.  The fused kernel
        # scans the padded slot axis, so the estimate counts g_max.
        # The host path reads each lane's arrays directly — no stacking.
        steps = rounds * g_max if rounds_ok else p_max
        fused_cost = B * steps * statics.n_real
        self.fused_batches += 1
        if JaxBinPackScheduler.host_executor(fused_cost):
            self._finish_fused_host(pending, rounds_ok, k_cap, rounds,
                                    fused_cost, retries)
            if leftovers:
                self._process_leftovers(leftovers)
            return

        # The window's stages (tracing only): ``whole`` is the lanes'
        # ``sched.dispatch``; ``part`` laps ``window.stack`` and
        # ``window.upload``, once a window, in the trace its
        # ``device.dispatch`` joins.
        tracer = trace_mod.tracer() if trace_mod.ENABLED else None
        whole = part = None
        if tracer is not None:
            whole = trace_mod.StageClock(tracer)
            part = trace_mod.StageClock(tracer)
        # Mesh resolution rides the ONE authority (parallel/mesh.py):
        # multi-chip agents automatically get the 2-D (lanes, fleet)
        # storm layout when the shape splits, NOMAD_TPU_MESH overrides.
        from nomad_tpu.parallel.mesh import dispatch_mesh

        mesh = dispatch_mesh(B_pad, statics.n_pad)
        # Harmonize pad shapes across lanes, stack, one dispatch.  The
        # fleet-wide stacks come from the runner's kept staging of this
        # shape, rewritten where the lanes changed; a mesh dispatch
        # stacks afresh (its wrappers place host operands themselves).
        shape = (B_pad, g_max, statics.n_pad)
        staging = self._staging_take(shape, statics.gen) if mesh is None \
            else _WindowStaging(*shape)
        restaged, counted = staging.stage(pending)
        feas_resident, jc_resident = staging.resident(restaged, counted)
        self.window_lanes_restaged += restaged
        self.window_feas_reuses += feas_resident
        self.window_jc_reuses += jc_resident
        feasible, job_counts = staging.feasible, staging.job_counts
        asks = np.zeros((B_pad, g_max, pending[0][2].asks.shape[1]),
                        dtype=np.float32)
        distinct = np.zeros((B_pad, g_max), dtype=bool)
        group_idx = np.zeros((B_pad, p_max), dtype=np.int32)
        valid = np.zeros((B_pad, p_max), dtype=bool)
        counts = np.zeros((B_pad, g_max), dtype=np.int32)
        for b, (_s, _p, a) in enumerate(pending):
            asks[b, :a.g_pad] = a.asks
            distinct[b, :a.g_pad] = a.distinct
            group_idx[b, :a.p_pad] = a.group_idx
            valid[b, :a.p_pad] = a.valid
            counts[b, :a.g_pad] = a.counts

        penalty = np.zeros(B_pad, dtype=np.float32)
        penalty[:B] = [a.penalty for _, _, a in pending]
        if part is not None:
            t0, dur, pair = part.lap()
            tracer.record(
                "window.stack", t0, dur, parent_ctx=tracer.ctx(), lanes=B,
                b_pad=B_pad, g_pad=g_max, n_pad=statics.n_pad,
                bytes=sum(x.nbytes for x in (
                    feasible, asks, distinct, group_idx, valid,
                    job_counts, counts, penalty)),
                lanes_restaged=restaged, feas_resident=int(feas_resident),
                jc_resident=int(jc_resident), **pair)

        self.device_dispatches += 1
        self.device_lanes += B
        if mesh is not None:
            self.sharded_dispatches += 1
        self.slots += sum(a.n_groups for _, _, a in pending)
        self.padded_slots += B_pad * g_max
        engine = "device" if mesh is None else "sharded"

        def dispatch_spans() -> None:
            """One ``sched.dispatch`` a lane over the window's one
            interval and its one ``cpu_s`` / ``blocked_s`` pair; the
            lanes differ in ``slots`` alone."""
            if whole is None:
                return
            t0, dur, pair = whole.lap()
            for sched, _p, a in pending:
                _lane_spans("sched.dispatch", [sched], t0, t0 + dur,
                            fused=B, **pair, **dispatch_tags(
                                rounds_ok, rounds, engine, fused_cost, B,
                                a.n_groups))

        def fetch(late, *arrays) -> list:
            """``fetch_results``, its seconds on the window's
            ``device.dispatch`` span (``late``: that span's tags); the
            window's kept lane stacks are handed back after it."""
            t0 = _tnow()
            out = fetch_results(*arrays)
            if late is not None:
                late["fetch_s"] = _tnow() - t0
            if mesh is None:
                self._staging_keep(shape, staging)
            return out
        # All fused lanes share the same snapshot base usage (fast-path
        # contract above); use the resident device copies when available
        # (single-device mirror copy, or on a mesh the sharded statics +
        # sharded usage mirror) so fleet tensors are not re-uploaded per
        # dispatch.
        view0 = pending[0][2].view
        if mesh is not None:
            capacity_d, reserved_d = \
                statics.device_capacity_reserved_sharded(mesh)
            base_usage = None
            if view0.usage_device is not None and \
                    statics.mirror is not None:
                base_usage = statics.mirror.device_usage_sharded(
                    mesh, view0.usage)
            if base_usage is None:
                base_usage = view0.usage  # mirror moved on: host upload
        else:
            from nomad_tpu.parallel.devices import moved_bytes, put_counted

            if part is not None:
                part.lap()
                moved = moved_bytes("h2d")
            capacity_d, reserved_d = statics.device_capacity_reserved()
            base_usage = put_counted(view0.dispatch_usage())
            # The per-dispatch lane stacks are host arrays: place them
            # EXPLICITLY (counted) instead of letting jit commit them
            # implicitly — the fused dispatch's h2d bytes are part of
            # its honest cost, and the transfer-guard sanitizer rejects
            # the implicit form.  The staging's device stacks are passed
            # as they are where they may be; its host buffers are
            # uploaded as copies, since later windows write them again.
            # (The sharded wrappers below _put their operands
            # themselves.)
            if not feas_resident:
                staging.feasible_d = put_counted(feasible, copy=True)
            feasible = staging.feasible_d
            if counted:
                job_counts = put_counted(job_counts, copy=True)
            else:
                if not jc_resident:
                    staging.nought_d = put_counted(job_counts, copy=True)
                job_counts = staging.nought_d
            asks = put_counted(asks)
            distinct = put_counted(distinct)
            group_idx = put_counted(group_idx)
            valid = put_counted(valid)
            counts = put_counted(counts)
            penalty = put_counted(penalty)
            if part is not None:
                t0, dur, pair = part.lap()
                tracer.record("window.upload", t0, dur,
                              parent_ctx=tracer.ctx(),
                              h2d_bytes=moved_bytes("h2d") - moved, **pair)
        from nomad_tpu.parallel.devices import NO_DISPATCH, device_dispatch

        if rounds_ok:
            # Fast path: top-k rounds — device steps scale with unique
            # groups x rounds, not with placements.
            from .jax_binpack import rounds_to_placements

            if mesh is not None:
                from nomad_tpu.parallel.mesh import (
                    _place_rounds_batch_sharded_jit as program,
                    place_rounds_batch_sharded)
            else:
                from nomad_tpu.ops.binpack import \
                    place_rounds_batch as program
            with (device_dispatch(program, lanes=B, b_pad=B_pad,
                                  g_pad=g_max, k_cap=k_cap, rounds=rounds,
                                  n_pad=statics.n_pad,
                                  slots=sum(a.n_groups
                                            for _, _, a in pending))
                  if trace_mod.ENABLED else NO_DISPATCH) as late:
                if mesh is not None:
                    chosen_s, score_s, _u = place_rounds_batch_sharded(
                        mesh, capacity_d, reserved_d, base_usage,
                        job_counts, feasible, asks, distinct, counts,
                        penalty, k_cap=k_cap, rounds=rounds)
                else:
                    chosen_s, score_s, _u = program(
                        capacity_d, reserved_d, base_usage, job_counts,
                        feasible, asks, distinct, counts, penalty,
                        k_cap=k_cap, rounds=rounds)
                chosen_s, score_s = fetch(late, chosen_s, score_s)
            dispatch_spans()
            done = []
            for b, (sched, place, args) in enumerate(pending):
                chosen, scores = rounds_to_placements(
                    args, chosen_s[b], score_s[b])
                done.append((sched, place, args, chosen, scores))
            self._finish_window(done, retries)
        else:
            if mesh is not None:
                from nomad_tpu.parallel.mesh import (
                    _place_sequence_batch_sharded_jit as program,
                    place_sequence_batch_sharded)
            else:
                program = place_sequence_batch
            with (device_dispatch(program, lanes=B, b_pad=B_pad,
                                  g_pad=g_max, p_pad=p_max,
                                  n_pad=statics.n_pad)
                  if trace_mod.ENABLED else NO_DISPATCH) as late:
                if mesh is not None:
                    chosen, scores, _usage = place_sequence_batch_sharded(
                        mesh, capacity_d, reserved_d, base_usage,
                        job_counts, feasible, asks, distinct, group_idx,
                        valid, penalty)
                else:
                    chosen, scores, _usage = program(
                        capacity_d, reserved_d, base_usage, job_counts,
                        feasible, asks, distinct, group_idx, valid,
                        penalty)
                chosen, scores = fetch(late, chosen, scores)
            dispatch_spans()
            self._finish_window(
                [(sched, place, args, chosen[b], scores[b])
                 for b, (sched, place, args) in enumerate(pending)],
                retries)

        if leftovers:
            self._process_leftovers(leftovers)

    def _finish_fused_host(self, pending, rounds_ok, k_cap,
                           rounds, fused_cost, retries=None) -> None:
        """Host-executor twin of the fused dispatch: every lane plans
        against the same snapshot base usage via the numpy kernels, one
        lane at a time (each lane's kernel is vectorized over nodes),
        reading the lanes' own arrays — no [B, G, N] stacking."""
        from nomad_tpu.ops.binpack_host import (place_rounds_host,
                                                place_sequence_host)
        from .jax_binpack import rounds_to_placements

        statics = pending[0][2].statics
        base_usage = pending[0][2].view.usage  # host array
        n_real = statics.n_real
        done = []
        for sched, place, args in pending:
            clock = trace_mod.stage_clock() if trace_mod.ENABLED else None
            if rounds_ok:
                chosen_s, score_s, _u = place_rounds_host(
                    statics.capacity, statics.reserved, base_usage,
                    args.view.job_counts, args.feasible_h, args.asks,
                    args.distinct, args.counts, float(args.penalty),
                    k_cap=k_cap, rounds=rounds, n_real=n_real,
                    scorer=statics.host_scorer, tally=sched)
                chosen, scores = rounds_to_placements(
                    args, chosen_s, score_s)
            else:
                chosen, scores, _u = place_sequence_host(
                    statics.capacity, statics.reserved, base_usage,
                    args.view.job_counts, args.feasible_h, args.asks,
                    args.distinct, args.group_idx, args.valid,
                    float(args.penalty), n_real=n_real)
            _stage_spans("sched.dispatch", [sched], clock, host=True,
                         **dispatch_tags(
                             rounds_ok, rounds, "host", fused_cost,
                             len(pending), args.n_groups, sched))
            self.host_dispatches += 1
            self.slots += args.n_groups
            self.padded_slots += args.g_pad
            done.append((sched, place, args, chosen, scores))
        self._finish_window(done, retries)

    def _process_leftovers(self, leftovers: list) -> None:
        if self.state_refresh is None:
            for ev in leftovers:
                set_status(self.planner, ev, None, EVAL_STATUS_FAILED,
                           "duplicate eval for job in one batch and no "
                           "state refresh available")
            return
        self.state = self.state_refresh()
        self.process(leftovers)

    def _run_single(self, sched, place, args, retries=None) -> None:
        clock = trace_mod.stage_clock() if trace_mod.ENABLED else None
        handles = sched.dispatch_device(args)
        # faultlint-ok(uninjectable-io): batch-lane device round-trip;
        # fault rehearsal (and the recovery path it needs) rides the
        # pipelined lane's device.dispatch/collect seam — a documented
        # gap, not an oversight.
        chosen, scores = sched.collect_device(args, handles)
        _stage_spans("sched.dispatch", [sched], clock, **dispatch_tags(
            args.rounds_eligible, args.rounds,
            "host" if sched.dispatched_host else
            "sharded" if sched.dispatched_sharded else "device",
            sched.dispatch_cost(args), 1, args.n_groups, sched))
        sched.finish_deferred(place, args, chosen, scores)
        self._note_dispatch(sched)
        _stage_spans("sched.finish", [sched], clock,
                     **self._note_finish([sched]))
        self._finish(sched, retries)

    @staticmethod
    def _window_net_seed(lanes: list) -> "dict | None":
        """One copy of the usage mirror's port/bandwidth occupancy for
        a whole window (UsageMirror.net_occupancy over every lane's
        chosen nodes) when its lanes plan on one snapshot — a fused
        window's do, and they mostly pick the same nodes; None
        otherwise: each lane then copies its own."""
        if len(lanes) < 2:
            return None
        first, _place, args0, *_ = lanes[0]
        if any(s.state is not first.state or a.statics is not args0.statics
               for s, _p, a, *_r in lanes):
            return None
        from nomad_tpu.models.fleet import mirror_for

        touched: set = set()
        for *_x, chosen, _scores in lanes:
            touched.update(chosen if type(chosen) is list
                           else chosen.tolist())
        return mirror_for(args0.statics).net_occupancy(first.state,
                                                       touched)

    def _finish_lanes(self, lanes: list) -> None:
        """Windowed finish for a list of lanes in lane order — ONE
        shared uuid slab (structs.generate_uuids), ONE copy of the
        mirror's occupancy (``_window_net_seed``) and ONE native call
        (native/port_alloc.cpp bulk_finish_many) cover every lane's
        happy-path prefix, then each lane's Python tail runs.  The one
        implementation of the windowed finish sequence, shared by the
        fused batch runner and the staged pipeline's drain stage.
        ``lanes`` is [(sched, place, args, chosen, scores), ...];
        semantics per lane are identical to ``finish_deferred``."""
        from nomad_tpu.structs import generate_uuids

        from .jax_binpack import _native_bulk

        clock = trace_mod.stage_clock() if trace_mod.ENABLED else None

        uuid_slab = generate_uuids(
            sum(len(place) for _, place, *_ in lanes))
        net_seed = self._window_net_seed(lanes)
        states = []
        nargs = []
        off = 0
        for sched, place, args, chosen, scores in lanes:
            fs = sched._finish_prepare(place, args, chosen, scores,
                                       uuid_slab[off:off + len(place)],
                                       net_seed)
            off += len(place)
            states.append(fs)
            nargs.append(sched._finish_native_args(fs))
        native = _native_bulk()
        # Columnar lanes (fs.slab set) batch through ONE
        # bulk_finish_many call; legacy object lanes (columnar contract
        # disabled) and mixed windows fall back to per-lane calls.
        if native is not None and hasattr(native, "bulk_finish_many") \
                and len(lanes) > 1 and all(a is not None for a in nargs) \
                and all(fs.slab is not None for fs in states):
            outs = native.bulk_finish_many(nargs)
            for (sched, *_rest), fs, out in zip(lanes, states, outs):
                sched._finish_consume_native(fs, out)
        else:
            for (sched, *_rest), fs, a in zip(lanes, states, nargs):
                if a is not None:
                    if fs.slab is not None:
                        sched._finish_consume_native(
                            fs, native.bulk_finish_cols(*a))
                    else:
                        sched._finish_consume_native(
                            fs, native.bulk_finish(*a))
        for (sched, *_rest), fs in zip(lanes, states):
            sched._finish_python_tail(fs)
        scheds = [s for s, *_r in lanes]
        _stage_spans("sched.finish", scheds, clock,
                     window=len(lanes), **self._note_finish(scheds))

    def _finish_window(self, done: list, retries=None) -> None:
        """Windowed finish + group submit for fused lanes
        (``_finish_lanes``), then every lane's plan submits as one group
        through the planner's window path (``submit_plans``) so the
        commit point is paid once per window, not per lane."""
        if not done:
            return
        self._finish_lanes(done)
        for sched, *_rest in done:
            self._note_dispatch(sched)  # finish-loop host re-plans
        self._submit_window([sched for sched, *_rest in done], retries)

    def _submit_window(self, scheds: list, retries=None) -> None:
        """Submit a window of finished lanes' plans, preserving lane
        order and per-lane status semantics (see ``_finish``).  Uses the
        planner's group path when it has one; per-plan submits
        otherwise."""
        tracer = trace_mod.tracer() if trace_mod.ENABLED else None
        sub_ids = clock = None
        if tracer is not None:
            clock = trace_mod.StageClock(tracer)
            # Pin each lane's sched.submit span id up front: the status
            # writes inside the window are its children.
            sub_ids = {s.eval.id: tracer.new_id() for s in scheds
                       if s.eval is not None}
            self.stage_span.update(sub_ids)
        try:
            self._submit_window_inner(scheds, retries)
        finally:
            if sub_ids is not None:
                for eval_id in sub_ids:
                    self.stage_span.pop(eval_id, None)
        _stage_spans("sched.submit", scheds, clock, sub_ids,
                     window=len(scheds))

    def _submit_window_inner(self, scheds: list, retries=None) -> None:
        submitters = []
        for sched in scheds:
            ev = sched.eval
            try:
                done = sched._submit_begin()
            except SetStatusError as e:  # pragma: no cover - defensive
                set_status(self.planner, ev, sched.next_eval,
                           e.eval_status, str(e))
                continue
            if done is not None:
                set_status(self.planner, ev, sched.next_eval,
                           EVAL_STATUS_COMPLETE)
                continue
            submitters.append(sched)
        if not submitters:
            return
        group = getattr(self.planner, "submit_plans", None)
        if group is not None and len(submitters) > 1:
            outs = group([s.plan for s in submitters])
        else:
            outs = [self.planner.submit_plan(s.plan)
                    for s in submitters]
        for sched, (result, state) in zip(submitters, outs):
            ev = sched.eval
            try:
                ok = sched._submit_finish(result, state)
            except SetStatusError as e:  # pragma: no cover - defensive
                set_status(self.planner, ev, sched.next_eval,
                           e.eval_status, str(e))
                continue
            if ok:
                set_status(self.planner, ev, sched.next_eval,
                           EVAL_STATUS_COMPLETE)
            elif retries is not None:
                retries.append(ev)  # no status yet: a later round owns it
            else:
                self._retry_sequential(sched.state, ev)

    def _finish(self, sched, retries=None) -> None:
        """Submit the plan; on rejection/partial commit either queue the
        eval for the next FUSED retry round (``retries`` list supplied)
        or fall back to the sequential retry loop (fresh scheduler,
        full process)."""
        ev = sched.eval
        try:
            ok = sched._submit()
        except SetStatusError as e:  # pragma: no cover - defensive
            set_status(self.planner, ev, sched.next_eval, e.eval_status,
                       str(e))
            return
        if ok:
            set_status(self.planner, ev, sched.next_eval,
                       EVAL_STATUS_COMPLETE)
        elif retries is not None:
            retries.append(ev)  # no status yet: a later round owns it
        else:
            self._retry_sequential(sched.state, ev)
