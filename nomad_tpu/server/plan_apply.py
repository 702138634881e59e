"""Plan applier: the serialization point of optimistic concurrency.

Capability parity with /root/reference/nomad/plan_apply.go: a single leader
thread pops plans off the PlanQueue, verifies the eval token is outstanding,
evaluates every touched node against a state snapshot (node ready +
AllocsFit), partially accepts (or wholly rejects for AllAtOnce plans) with a
RefreshIndex that forces schedulers to refresh stale state, then applies the
accepted allocs through raft.  Verification of plan N+1 overlaps the raft
apply of plan N via an optimistic overlay snapshot (plan_apply.go:39-124).
"""
from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Optional

from nomad_tpu.structs import (
    NODE_STATUS_READY,
    Allocation,
    Plan,
    PlanResult,
    allocs_fit,
    codec,
    filter_terminal_allocs,
    remove_allocs,
)

from nomad_tpu.obs import flight as flight_mod
from nomad_tpu.obs import trace as trace_mod
from nomad_tpu.utils.metrics import metrics

logger = logging.getLogger("nomad_tpu.server.plan_apply")

_MISS = object()


class OptimisticSnapshot:
    """Read view = base snapshot + not-yet-committed alloc upserts.

    Lets the applier verify plan N+1 while plan N's raft apply is still in
    flight (the reference mutates its state snapshot in place; our MVCC
    snapshots are immutable, so this overlay provides the same effect)."""

    def __init__(self, base) -> None:
        self.base = base
        self._overlay: dict = {}        # alloc id -> Allocation
        self._by_node: dict = {}        # node id -> [alloc ids]

    def upsert_allocs(self, allocs: list) -> None:
        overlay = self._overlay
        by_node = self._by_node
        for a in allocs:
            aid = a.id
            if aid not in overlay:
                by_node.setdefault(a.node_id, []).append(aid)
            overlay[aid] = a

    # -- read API used by plan evaluation ---------------------------------
    def node_by_id(self, node_id: str):
        return self.base.node_by_id(node_id)

    def allocs_by_node(self, node_id: str) -> list:
        base = self.base.allocs_by_node(node_id)
        if not self._overlay:
            return base
        merged = {a.id: a for a in base}
        for aid in self._by_node.get(node_id, ()):
            merged[aid] = self._overlay[aid]
        return list(merged.values())

    def get_index(self, table: str) -> int:
        return self.base.get_index(table)


def evaluate_plan(snap, plan: Plan) -> PlanResult:
    """Determine the committable portion of a plan
    (plan_apply.go:171-233).

    The per-node verdicts come from a vectorized pass over the fleet
    mirror when the snapshot supports it (one numpy fit row + O(plan)
    port/bandwidth bookkeeping per node, see _evaluate_plan_vec);
    nodes the vector pass cannot serve — and any snapshot without a
    mirror — fall back to the scalar allocs_fit/NetworkIndex walk,
    which stays the semantic truth."""
    import time as _time
    _start = _time.perf_counter()
    result = PlanResult(failed_allocs=list(plan.failed_allocs))

    node_ids = set(plan.node_update) | set(plan.node_allocation)
    # Evict-only plans are trivially acceptable per node; don't spin up
    # (or permanently enable) the mirror's net tracking for them.
    verdicts = _evaluate_plan_vec(snap, plan, node_ids) \
        if any(plan.node_allocation.values()) else None
    for node_id in node_ids:
        ok = verdicts.get(node_id) if verdicts is not None else None
        if ok is None:
            ok = _evaluate_node_plan(snap, plan, node_id)
        if ok:
            if plan.node_update.get(node_id):
                result.node_update[node_id] = plan.node_update[node_id]
            if plan.node_allocation.get(node_id):
                result.node_allocation[node_id] = \
                    plan.node_allocation[node_id]
            continue

        # Scheduler had stale data: RefreshIndex forces a fresh view.
        result.refresh_index = max(snap.get_index("nodes"),
                                   snap.get_index("allocs"))
        if plan.all_at_once:
            result.node_update = {}
            result.node_allocation = {}
            return result
        # Partial acceptance: skip this node only.
    metrics.measure_since("nomad.plan.evaluate", _start)
    return result


def _evaluate_plan_vec(snap, plan: Plan, node_ids) -> Optional[dict]:
    """Vectorized node verdicts: {node_id: True/False/None} or None when
    the snapshot cannot take the vector path at all.  ``None`` verdicts
    punt single nodes to the exact scalar walk.

    Capability parity with the per-node loop of
    /root/reference/nomad/plan_apply.go:238-284, restructured for
    throughput: instead of rebuilding a Resources sum and a NetworkIndex
    per node per plan, the fleet UsageMirror keeps per-node usage rows,
    port counts and bandwidth sums synced incrementally from the store
    changelog, so one plan's verification costs O(plan size), not
    O(allocs on touched nodes).  Dimension sums ride float32 like every
    other fleet tensor (exact for values < 2^24, i.e. any realistic
    node).  Nodes with multi-network topologies, mixed-ip/device alloc
    offers, or overlay (in-flight apply) deltas keep the scalar truth.
    """
    base = snap
    overlay = None
    if isinstance(snap, OptimisticSnapshot):
        overlay = snap
        base = snap.base
    if getattr(base, "_t", None) is None:
        return None
    from nomad_tpu.models.fleet import alloc_vec, fleet_cache, mirror_for

    statics = fleet_cache.statics_for(base)
    mirror = mirror_for(statics)
    capacity = statics.capacity
    reserved = statics.reserved
    index_of = statics.index_of
    overlay_nodes = overlay._by_node if overlay is not None else {}

    # The net dicts are mutated in place by concurrent worker syncs;
    # hold the mirror for the whole composite read (the usage array is
    # copy-on-write, but alloc_rows/node_ports/net_rows are not).
    with mirror.lock:
        if not mirror.sync_net(base):
            return None  # snapshot older than the mirror: scalar truth
        usage = mirror.usage

        verdicts: dict = {}
        for nid in node_ids:
            placements = plan.node_allocation.get(nid)
            if not placements:
                verdicts[nid] = True  # evict-only plans always fit
                continue
            node = snap.node_by_id(nid)
            if node is None or node.status != NODE_STATUS_READY \
                    or node.drain:
                verdicts[nid] = False
                continue
            ni = index_of.get(nid, -1)
            if ni < 0 or overlay_nodes.get(nid):
                verdicts[nid] = None  # not in fleet / in-flight overlay
                continue

            # --- resource fit: mirror row + plan deltas (the 4 dims
            # Resources.superset checks) -----------------------------
            removed_ids = {a.id for a in plan.node_update.get(nid, ())}
            removed_ids.update(a.id for a in placements)  # in-place upd
            used = reserved[ni] + usage[ni]
            for a in placements:
                used = used + alloc_vec(a)
            for aid in removed_ids:
                row = mirror.alloc_rows.get(aid)
                if row is not None and row[0] == ni:
                    used = used - row[1]
            cap = capacity[ni]
            if not (used[0] <= cap[0] and used[1] <= cap[1]
                    and used[2] <= cap[2] and used[3] <= cap[3]):
                verdicts[nid] = False
                continue

            # --- port collisions + bandwidth (exact, incremental) ----
            verdicts[nid] = _verify_node_net(
                mirror, statics, node, ni, placements, removed_ids)
    return verdicts


def _node_net_static(statics, node, ni: int):
    """The node-static half of the port/bandwidth verdict, cached on
    the fleet statics: ``(reserved ports, reserved mbits, bandwidth
    capacity, (ip, device))``.  None when the node needs the scalar
    NetworkIndex walk; False when nothing can ever fit on it."""
    cache = statics.net_static
    static = cache.get(ni, _MISS)
    if static is not _MISS:
        return static
    from nomad_tpu.models.fleet import net_base_for

    base = net_base_for(statics, ni, node)
    if base is None:
        static = None  # multi-network node: exact path
    else:
        frozen_used, bw_reserved, bw_avail, ip, device = base
        static = (frozen_used, bw_reserved, bw_avail, (ip, device))
        # The node's own reserved networks must ride the same (ip,
        # device) too: the scalar walk accounts reserved ports per-ip
        # and reserved bandwidth per-device, so an off-network
        # reservation (or one with no device — whose bandwidth the
        # scalar path books against a zero-capacity device) needs the
        # exact walk.
        if node.reserved is not None and node.reserved.networks:
            total_reserved_ports = 0
            for rn in node.reserved.networks:
                if rn.ip != ip or rn.device != device:
                    static = None
                    break
                total_reserved_ports += len(rn.reserved_ports)
            else:
                if total_reserved_ports > len(frozen_used):
                    static = False  # reserved ports self-collide
    cache[ni] = static
    return static


def _verify_node_net(mirror, statics, node, ni: int, placements,
                     removed_ids) -> Optional[bool]:
    """Exact port/bandwidth verdict for one node from the mirror's
    incremental per-node state: True fit, False reject, None = topology
    needs the scalar NetworkIndex walk.  Caller holds the mirror lock."""
    from nomad_tpu.models.fleet import _net_row

    static = _node_net_static(statics, node, ni)
    if not static:
        return static
    frozen_used, bw_reserved, bw_avail, node_key = static

    # Existing offers must all live on the node's (ip, device) for the
    # merged per-node counting to be sound; odd rows force the exact walk.
    keys = mirror.node_net_keys.get(ni)
    if keys and (len(keys) > 1 or next(iter(keys)) != node_key):
        return None

    removed_ports: dict = {}
    removed_mbits = 0
    for aid in removed_ids:
        nr = mirror.net_rows.get(aid)
        if nr is not None and nr[0] == ni:
            for p in nr[1]:
                removed_ports[p] = removed_ports.get(p, 0) + 1
            removed_mbits += nr[2]

    pc = mirror.node_ports.get(ni, {})
    # Collisions among the POST-removal live set (or between a live
    # alloc and the node's reserved ports) reject the plan the same way
    # the scalar walk's collide flag does: an eviction in this plan may
    # free the colliding port, so counts are checked net of removals.
    if mirror.node_dup.get(ni):
        for p, c in pc.items():
            if c - removed_ports.get(p, 0) > 1:
                return False
    if frozen_used and pc:
        it = (p for p in frozen_used if p in pc) \
            if len(frozen_used) <= len(pc) \
            else (p for p in pc if p in frozen_used)
        for p in it:
            if pc.get(p, 0) - removed_ports.get(p, 0) > 0:
                return False

    placed_mbits = 0
    staged: set = set()
    for a in placements:
        row = _net_row(a)
        if row is None:
            continue
        ports, mbits, key = row
        if key != node_key:
            return None  # offer off the node's network: exact path
        placed_mbits += mbits
        for p in ports:
            if p in staged:
                return False  # duplicate within the plan itself
            staged.add(p)
            live = pc.get(p, 0) - removed_ports.get(p, 0)
            if live > 0 or p in frozen_used:
                return False  # collides with a live alloc / reserved port

    bw = bw_reserved + mirror.node_bw.get(ni, 0) \
        - removed_mbits + placed_mbits
    if bw > bw_avail:
        return False  # bandwidth exceeded
    return True


def _evaluate_node_plan(snap, plan: Plan, node_id: str) -> bool:
    """Is the plan valid for one node? (plan_apply.go:238-284)."""
    placements = plan.node_allocation.get(node_id, [])
    if not placements:
        return True  # evict-only plans always fit

    node = snap.node_by_id(node_id)
    if node is None or node.status != NODE_STATUS_READY or node.drain:
        return False

    existing = filter_terminal_allocs(snap.allocs_by_node(node_id))
    remove = list(plan.node_update.get(node_id, ())) + list(placements)
    proposed = remove_allocs(existing, remove) + list(placements)

    fit, _dim, _util = allocs_fit(node, proposed)
    return fit


class _Committer:
    """ONE long-lived FIFO thread executing the commit tail of each
    window — wire encode, raft dispatch, commit wait, future responds —
    in window order, off the applier thread.

    This deepens the reference's verify/apply overlap (plan_apply.go:
    68-85): the applier thread's serialized section is token fence +
    window verify + overlay fold, while the encode, the
    raft apply and (with InmemRaft) the synchronous FSM decode +
    batched store upsert — the priciest per-plan stages of the whole
    pipeline — ride here.  FIFO preserves the dispatch order and the
    one-apply-in-flight discipline (each job awaits its commit before
    the next job starts); ``wait_depth_below`` is the applier's
    backpressure so the optimistic overlay stays bounded.  It also
    replaces the per-window respond thread (a waived deliberate leak
    in LINT_ALLOWLIST until this round): at partitioned commit rates —
    hundreds of windows per second — thread creation itself was a top
    pipeline cost."""

    def __init__(self, name: str = "plan-committer") -> None:
        self.name = name
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: deque = deque()
        self._inflight = 0   # queued + executing jobs
        self._stopped = False
        self._thread: Optional[threading.Thread] = None

    def submit(self, fn) -> None:
        with self._cond:
            if self._stopped:
                raise RuntimeError("committer stopped")
            self._queue.append(fn)
            self._inflight += 1
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name=self.name)
                self._thread.start()
            self._cond.notify_all()

    def drained(self) -> bool:
        """True when every submitted commit has fully resolved — the
        applier's signal that its optimistic overlay can be dropped
        for a fresh post-commit snapshot."""
        with self._lock:
            return self._inflight == 0

    def inflight(self) -> int:
        """Queued + executing commit jobs — the control plane's
        commit-pipeline occupancy gauge."""
        with self._lock:
            return self._inflight

    def wait_depth_below(self, n: int,
                         timeout: Optional[float] = None) -> None:
        end = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while self._inflight >= n and not self._stopped:
                if end is not None:
                    remaining = end - time.monotonic()
                    if remaining <= 0:
                        return
                    self._cond.wait(remaining)
                else:
                    # faultlint-ok(unbounded-wait): timeout=None branch
                    # kept for teardown; every request-path caller
                    # passes a budget (60s depth gate, 30s drain) and
                    # stop() flips _stopped under notify_all.
                    self._cond.wait()

    def wait_drained(self, timeout: Optional[float] = None) -> None:
        self.wait_depth_below(1, timeout)

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._stopped:
                    # faultlint-ok(unbounded-wait): idle committer
                    # parking — submit() and stop() both notify; the
                    # per-commit waits are the budgeted ones.
                    self._cond.wait()
                if not self._queue:
                    return  # stopped AND drained: futures never drop
                fn = self._queue.popleft()
            try:
                fn()
            except Exception:
                logger.exception("plan committer: commit job failed")
            finally:
                with self._cond:
                    self._inflight -= 1
                    self._cond.notify_all()

    def stop(self, timeout: float = 2.0) -> None:
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
            _thread = self._thread
        if _thread is not None and \
                _thread is not threading.current_thread():
            _thread.join(timeout)


class PlanApplier:
    """Single leader thread draining the plan queue in group-commit
    windows.

    Each iteration pops every pending plan (up to ``max_window``,
    gathering briefly under saturation so windows drain full), fences
    the whole window's tokens in ONE broker call, verifies it with the
    cross-plan conflict pass (ops/plan_conflict.evaluate_window — the
    array pass over the window's claims, the per-claim walk by
    claim-graph component, nearest-deadline component first, byte-exact
    eval order within each component), and hands the commit tail to
    the ``_Committer``: ALL accepted portions as ONE raft apply
    carrying a multi-plan FSM message — amortizing the Raft/FSM/native
    overhead that made the serialized commit the contended storm's
    floor.  Per-plan futures are responded with results identical to
    sequential application in eval order; the overlapped verify/apply
    snapshot-overlay semantics extend to batches (the next window
    verifies against the in-flight windows' overlay)."""

    # A verify+commit window past this wall is a wedged leader, not a
    # big window: trip the flight recorder (when one is installed).
    WINDOW_STALL_S = 30.0

    def __init__(self, plan_queue, eval_broker, raft, state_fn,
                 max_window: int = 64, gather_s: float = 0.02,
                 deadline_horizon: float = 0.25) -> None:
        self.plan_queue = plan_queue
        self.eval_broker = eval_broker
        self.raft = raft
        self.state_fn = state_fn  # () -> StateStore (the FSM's live store)
        self.max_window = max(1, max_window)
        # Window gather budget: when the previous drain left a backlog
        # (saturation), wait up to this long for the queue to refill a
        # full window before draining — group-commit pacing.  An idle
        # leader (no backlog) never pays it.
        self.gather_s = gather_s
        # Plans whose deadline falls inside this horizon are promoted
        # to the front of the drained window (plan_queue.drain_pending)
        # and their components verify first.
        self.deadline_horizon = deadline_horizon
        self._committer = _Committer()
        # Commit-pipeline depth bound: at most this many windows may be
        # queued/executing in the committer before the applier blocks —
        # bounds the optimistic overlay (and how far a verify can run
        # ahead of committed state).
        self.max_inflight_commits = 2
        self._thread: Optional[threading.Thread] = None
        # Group-commit observability.
        self._stats_lock = threading.Lock()
        self.commits = 0            # raft applies dispatched
        self.plans_committed = 0    # plans carried by those applies
        self.conflict_fallbacks = 0  # window plans that needed the
        #                              exact per-plan walk (prefix
        #                              conflict with an earlier plan)
        self.expired_drops = 0      # plans whose propagated deadline
        #                             passed before verification — the
        #                             leader never burns a verify+commit
        #                             on a result nobody is waiting for
        self.components_verified = 0  # claim-graph components walked
        self.component_plans = 0      # plans those components carried
        # Control-plane gauges: wall the applier spent blocked on a
        # full commit pipeline (the max_inflight_commits AIMD's grow
        # signal — sustained backpressure means more run-ahead would
        # overlap more), and raft DISPATCH failures (its cut signal).
        self.commit_backpressure_s = 0.0
        self.dispatch_failures = 0
        self.gather_wall_s = 0.0  # wall spent in the window gather
        # Set by a committer job whose raft DISPATCH failed (nothing
        # entered the log): the overlay folded that window's allocs
        # before hand-off, so the applier must serialize the pipeline
        # out and take a fresh snapshot before trusting it again.
        self._dispatch_failed = False
        # Recent drained window sizes, BOUNDED: a leader drains windows
        # for its whole tenure, so an unbounded list is a slow leak.
        self.windows = deque(maxlen=256)

    def start(self) -> None:
        self._thread = threading.Thread(target=self.run, daemon=True,
                                        name="plan-applier")
        self._thread.start()

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    def shutdown(self, timeout: float = 2.0) -> None:
        """Terminal teardown: reap the committer (the applier thread
        itself exits when the queue is disabled)."""
        self._committer.stop(timeout)
        self.join(timeout)

    def run(self) -> None:
        snap: Optional[OptimisticSnapshot] = None
        while True:
            t_deq = time.monotonic()
            pending = self.plan_queue.dequeue(0)
            deq_wait = time.monotonic() - t_deq
            if pending is None:
                return  # queue disabled: leadership lost
            gather_s = self.gather_s  # re-read: a live control knob
            if gather_s > 0.0 and deq_wait < 0.002 and \
                    (self.plan_queue.depth() > 0
                     or self.plan_queue.await_depth(1, 0.002) > 0):
                # Two-phase adaptive gather.  This dequeue returned
                # without blocking, so a stream MAY be in flight; if a
                # backlog remains behind the popped plan — or anything
                # arrives within a 2 ms probe — gather toward a full
                # window instead of burning a whole commit cycle
                # (snapshot, verify, raft entry, FSM decode, respond)
                # on a sliver.  A lone submitter in a submit->wait->
                # resubmit loop pays at most the 2 ms probe (its plan
                # is the one in flight, so nothing else can arrive),
                # and an idle leader (blocking dequeues) pays nothing.
                # The gather wall is booked: the control plane's gather
                # driver shrinks a horizon that burns wall without
                # buying occupancy (control/wiring.py).
                t_gather = time.monotonic()
                self.plan_queue.await_depth(self.max_window - 1,
                                            gather_s)
                with self._stats_lock:
                    self.gather_wall_s += time.monotonic() - t_gather
            window = [pending]
            window += self.plan_queue.drain_pending(
                self.max_window - 1, horizon=self.deadline_horizon)
            try:
                # Stall watchdog (obs/flight.py): a window that
                # overstays WINDOW_STALL_S trips an incident dump with
                # the applier's stack in it — the leader's serialized
                # commit point wedging is exactly the failure that is
                # undebuggable after the fact.  No-op when no flight
                # recorder is installed.
                # extra_fn: the incident dump names WHAT was being
                # verified when the window wedged — its evals, not just
                # "the window".
                with flight_mod.guard(
                        "applier.window", self.WINDOW_STALL_S,
                        extra_fn=lambda: {"verifying": {
                            "plans": len(window),
                            "eval_ids": [p.plan.eval_id
                                         for p in window]}}):
                    snap = self._apply_window(window, snap)
            except Exception as e:
                # Popped futures must ALWAYS be responded: an applier
                # dying with them in hand would park their workers
                # forever (workers probe queue liveness, and the queue
                # is still alive — only this thread died).  Members the
                # window already answered keep their result (done()
                # guard: a second respond racing a waiter's read could
                # hand back torn fields); the rest get the error, which
                # is truthful — _apply_window answers every committed
                # member itself before anything else can raise.
                logger.exception("plan applier: unexpected failure")
                for pend in window:
                    if not pend.done():
                        pend.respond(None, e)
                # In-flight applies live in the committer pipeline:
                # drain it before dropping the overlay, or the next
                # window's fresh snapshot could miss a commit and
                # re-admit its conflicts.
                self._committer.wait_drained(timeout=30.0)
                snap = None

    def _fence_window(self, window) -> list:
        """Token fencing, the whole window in ONE broker call
        (``outstanding_many`` reads the token mirror behind its leaf
        lock; per-plan ``outstanding`` queued the applier behind the
        submitter herd's enqueue/dequeue/ack convoy once per plan): the
        eval must be outstanding and the token must match (guards
        split-brain schedulers, plan_apply.go:53).  Responds the future
        of every plan that fails and returns the rest.

        Deadline drop first (overload control plane): a plan whose
        propagated deadline passed gets an ``ErrDeadlineExceeded``
        response without any verification — by then the submitter's
        wait has expired and the broker's nack timer has (or is about
        to) redeliver the eval, so a commit here would only race the
        retry toward double placement while burning the leader."""
        from .overload import ErrDeadlineExceeded

        tokens = self.eval_broker.outstanding_many(
            [p.plan.eval_id for p in window])
        now = time.monotonic()
        pendings = []
        expired = 0
        for pending in window:
            plan = pending.plan
            if plan.deadline and now > plan.deadline:
                expired += 1
                pending.respond(None, ErrDeadlineExceeded(
                    f"plan for eval {plan.eval_id} expired in queue"))
                continue
            token = tokens.get(plan.eval_id)
            if token is None:
                pending.respond(None, RuntimeError(
                    "evaluation is not outstanding"))
                continue
            if plan.eval_token != token:
                pending.respond(None, RuntimeError(
                    "evaluation token does not match"))
                continue
            pendings.append(pending)
        if expired:
            with self._stats_lock:
                self.expired_drops += expired
        return pendings

    def _apply_window(self, window, snap):
        """Fence and verify one drained window and hand its commit tail
        to the committer; returns the optimistic overlay carried to the
        next iteration (the verify/apply overlap)."""
        from nomad_tpu.ops.plan_conflict import (
            _accepted_allocs,
            evaluate_window,
        )

        pendings = self._fence_window(window)
        if not pendings:
            return snap
        tracer = trace_mod.tracer() if trace_mod.ENABLED else None
        if tracer is not None:
            # Queue-wait spans: enqueue (PlanFuture.trace_t0) -> window
            # pop, one per plan, parented to the plan's eval anchor.
            now = tracer.now()
            for pend in pendings:
                if pend.plan.trace and pend.trace_t0 is not None:
                    tracer.record("plan.queued", pend.trace_t0,
                                  now - pend.trace_t0,
                                  parent_ctx=pend.plan.trace,
                                  eval_id=pend.plan.eval_id)

        # If every in-flight apply finished, drop the stale overlay;
        # else keep verifying against the optimistic view (this is the
        # verify/apply overlap, plan_apply.go:68-85, extended to whole
        # windows and to the committer pipeline's bounded queue of
        # windows).
        with self._stats_lock:
            dispatch_failed = self._dispatch_failed
        if dispatch_failed:
            # A hand-off's dispatch failed AFTER its allocs folded
            # into the overlay: those folds are phantoms (nothing
            # entered the log).  Serialize the pipeline out — other
            # in-flight windows' folds are real and must land before a
            # fresh snapshot can replace them — and clear the flag only
            # once DRAINED: windows already queued behind the failure
            # were verified against the phantoms, and their commit jobs
            # must still see the flag to refuse them.
            self._committer.wait_drained(timeout=60.0)
            with self._stats_lock:
                self._dispatch_failed = False
            snap = None
        elif snap is not None and self._committer.drained():
            snap = None
        if snap is None:
            snap = OptimisticSnapshot(self.state_fn().snapshot())

        t_verify = tracer.now() if tracer is not None else 0.0
        outcomes = evaluate_window(snap, [p.plan for p in pendings])
        info = outcomes.info
        if tracer is not None:
            # Span taxonomy: one applier.window span per member plan
            # (shared t0/dur, tagged window size + component count, and
            # the plan's claims with how many of them the per-claim
            # walk decided), and under it one applier.verify span
            # carrying the member's COMPONENT timing — so a trace shows
            # both the group-commit amortization (shared window walls)
            # and which component each eval's verify actually rode.
            dur_verify = tracer.now() - t_verify
            # perf_counter epoch -> tracer epoch for component t0s.
            perf_off = time.perf_counter() - tracer.now()
            for pending, outcome in zip(pendings, outcomes):
                if not pending.plan.trace:
                    continue
                wctx = tracer.record(
                    "applier.window", t_verify, dur_verify,
                    parent_ctx=pending.plan.trace,
                    eval_id=pending.plan.eval_id,
                    window=len(pendings),
                    components=info["components"] if info else 1,
                    claims=outcome.claims, walked=outcome.walked)
                if info is not None:
                    k = outcome.component
                    tracer.record(
                        "applier.verify",
                        info["comp_t0s"][k] - perf_off,
                        info["comp_walls"][k],
                        parent_ctx=wctx,
                        eval_id=pending.plan.eval_id,
                        component=k,
                        size=info["sizes"][info["order"][k]],
                        fallback=outcome.fallback)
                else:
                    tracer.record(
                        "applier.verify", t_verify, dur_verify,
                        parent_ctx=wctx,
                        eval_id=pending.plan.eval_id,
                        component=0, fallback=outcome.fallback)
        committers = []  # (pending, result) with state to commit
        fallbacks = 0
        for pending, outcome in zip(pendings, outcomes):
            if outcome.fallback:
                fallbacks += 1
            if outcome.result.is_noop():
                pending.respond(outcome.result, None)
            else:
                committers.append((pending, outcome.result))
        with self._stats_lock:
            self.windows.append(len(pendings))
            self.conflict_fallbacks += fallbacks
            if info is not None:
                self.components_verified += info["components"]
                self.component_plans += len(pendings)
        if not committers:
            return snap

        alloc_lists = [_accepted_allocs(result)
                       for _pending, result in committers]

        # The commit tail — wire encode, raft dispatch, commit wait,
        # responds — rides the FIFO committer pipeline, off this
        # thread.  The accepted portions are ALREADY folded into
        # ``snap`` (evaluate_window mutates the caller-owned overlay in
        # eval order — its documented contract), so the next window's
        # verify sees them without any re-fold here.  Bound the
        # pipeline depth, then hand off.
        t_bp = time.perf_counter()
        self._committer.wait_depth_below(self.max_inflight_commits,
                                         timeout=60.0)
        with self._stats_lock:
            # Backpressure wall (the wait above): the controller's
            # grow signal for max_inflight_commits.
            self.commit_backpressure_s += time.perf_counter() - t_bp
        try:
            self._committer.submit(
                lambda: self._commit_job(committers, alloc_lists,
                                         tracer))
        except Exception:
            # Committer gone (teardown): commit inline — futures
            # must always resolve.
            self._commit_job(committers, alloc_lists, tracer)
        return snap

    def _dispatch_window(self, committers, alloc_lists, tracer):
        """Encode one window's accepted portions and dispatch ONE raft
        apply; returns the apply future, or None after answering every
        member future with the dispatch error.

        ONE raft apply for the whole window, sub-plans in eval order
        (the FSM's batched upsert preserves last-writer-wins order, so
        final state is byte-identical to per-plan applies in eval
        order).  A single committer keeps the legacy single-plan wire
        format.  Columnar contract: slab-backed allocs ride the log as
        [slab, row, delta] references against one shared column record
        per slab (the job dict crosses the wire ONCE per slab, not once
        per alloc) — structs/alloc_slab.SlabWireEncoder; plain allocs
        keep the per-alloc dict encoding.  Returns (future, t_apply) —
        (None, 0.0) after answering every member future with the
        dispatch error."""
        from nomad_tpu.structs.alloc_slab import (
            encode_alloc_update,
            encode_plan_batch,
        )

        t_enc = tracer.now() if tracer is not None else 0.0
        if len(committers) == 1:
            msg_type, payload = (codec.ALLOC_UPDATE_REQUEST,
                                 encode_alloc_update(alloc_lists[0]))
        else:
            msg_type, payload = (codec.PLAN_BATCH_APPLY_REQUEST,
                                 encode_plan_batch(alloc_lists))
        t_apply = 0.0
        if tracer is not None:
            # Ship each sub-plan's context INSIDE the log entry (the
            # `_trace` payload key, ignored by decode): the FSM decode
            # and the batched store upsert run on the raft thread — or
            # on a follower — with no ambient context, and this is how
            # their spans join each eval's tree.
            env = [dict(pend.plan.trace, eval_id=pend.plan.eval_id)
                   if pend.plan.trace else None
                   for pend, _result in committers]
            if any(e is not None for e in env):
                payload["_trace"] = env
            t_apply = tracer.now()
        entry = codec.encode(msg_type, payload)
        if tracer is not None:
            # ``plan.encode``: accepted portions -> log entry (either
            # wire format), one span per member plan over the window's
            # one interval, as ``raft.apply`` is.
            dur = tracer.now() - t_enc
            for pend, _result in committers:
                if pend.plan.trace:
                    tracer.record("plan.encode", t_enc, dur,
                                  parent_ctx=pend.plan.trace,
                                  eval_id=pend.plan.eval_id,
                                  plans=len(committers), bytes=len(entry))
        try:
            future = self.raft.apply(entry)
        except Exception as e:
            # Flag BEFORE responding: a submitter that observes the
            # error and retries must find the next window already
            # committed to dropping this window's phantom overlay
            # folds (the verify folds before hand-off).
            with self._stats_lock:
                self._dispatch_failed = True
                self.dispatch_failures += 1
            for pending, _result in committers:
                pending.respond(None, e)
            return None, 0.0
        with self._stats_lock:
            self.commits += 1
            self.plans_committed += len(committers)
        return future, t_apply

    # Commit-wait poll slice: the raft-commit wait is re-armed in
    # bounded slices so the waiter can probe queue liveness between
    # them instead of parking forever on an orphaned future.
    COMMIT_WAIT_POLL = 5.0

    def _wait_commit(self, future):
        """Bounded raft-commit wait.  A commit can legitimately outlast
        any fixed budget, so the wait is supervised rather than capped:
        poll in COMMIT_WAIT_POLL slices and give up only when the plan
        queue has been disabled (leadership revoked or teardown) with
        the future still unresolved — raft_net responds its outstanding
        futures on step-down, so nothing will ever set that one."""
        while True:
            try:
                return future.wait(self.COMMIT_WAIT_POLL)
            except TimeoutError:
                if future.done():
                    raise     # the future RESPONDED with a timeout error
                if not self.plan_queue.enabled():
                    raise TimeoutError(
                        "plan queue disabled while awaiting raft commit")

    def _await_and_respond(self, future, committers, t_apply,
                           tracer) -> None:
        """The respond tail: wait out one window's commit and answer
        every member future.  From dispatch on, the entry is committed
        (or committing): failures here must not surface as plan errors
        beyond the commit wait itself — a worker retrying an
        already-applied plan would double-place."""
        try:
            index, _ = self._wait_commit(future)
        except Exception as e:
            for pend, _res in committers:
                pend.respond(None, e)
            return
        if tracer is not None:
            # raft.apply dispatch -> committed, one span per member
            # plan (shared t0/dur, like the verify spans).
            dur = tracer.now() - t_apply
            for pend, _res in committers:
                if pend.plan.trace:
                    tracer.record("raft.apply", t_apply, dur,
                                  parent_ctx=pend.plan.trace,
                                  eval_id=pend.plan.eval_id,
                                  window=len(committers), index=index)
        for pend, res in committers:
            res.alloc_index = index
            pend.respond(res, None)

    def _commit_job(self, committers, alloc_lists, tracer) -> None:
        """One committer-pipeline job: encode, dispatch, await, respond
        — the whole commit tail of one window, in FIFO window order.

        Poison check first: FIFO means every PRIOR window's dispatch
        outcome is known when this job runs, so if one failed, this
        window's verdicts were computed against overlay folds that
        never entered the log — committing them could durably
        over-commit (e.g. a placement that fit only because a phantom
        eviction freed the node).  Refuse with a retryable error
        instead; the applier drains the pipeline and re-verifies
        retries against a fresh snapshot (the ``_dispatch_failed``
        handling at the top of ``_apply_window``)."""
        with self._stats_lock:
            poisoned = self._dispatch_failed
        if poisoned:
            err = RuntimeError(
                "plan verified against a commit window whose dispatch "
                "failed; state refreshed — retry")
            for pend, _res in committers:
                pend.respond(None, err)
            return
        future, t_apply = self._dispatch_window(committers,
                                                alloc_lists, tracer)
        if future is None:
            return  # dispatch failed: futures answered, flag raised
        self._await_and_respond(future, committers, t_apply, tracer)

    def stats(self) -> dict:
        """Group-commit counters: commits, plans carried, mean window
        occupancy, conflict fallbacks, and the partitioned-verify
        fields (components walked, mean plans per component)."""
        with self._stats_lock:
            commits = self.commits
            plans = self.plans_committed
            windows = list(self.windows)
            fallbacks = self.conflict_fallbacks
            expired = self.expired_drops
            components = self.components_verified
            comp_plans = self.component_plans
            backpressure_s = self.commit_backpressure_s
            dispatch_failures = self.dispatch_failures
            gather_wall_s = self.gather_wall_s
        return {
            "gather_wall_s": gather_wall_s,
            # The live knob positions (the control plane's actuators
            # move them; their gauges ride beside the counters so a
            # trajectory is readable straight off the registry).
            "max_window": self.max_window,
            "max_inflight_commits": self.max_inflight_commits,
            "gather_s": self.gather_s,
            "deadline_horizon": self.deadline_horizon,
            "commit_backpressure_s": backpressure_s,
            "dispatch_failures": dispatch_failures,
            "commit_inflight": self._committer.inflight(),
            "commits": commits,
            "plans_committed": plans,
            "batch_occupancy": plans / commits if commits else 0.0,
            "conflict_fallbacks": fallbacks,
            "expired_drops": expired,
            "components": components,
            "component_occupancy":
                comp_plans / components if components else 0.0,
            "windows": windows,
        }
