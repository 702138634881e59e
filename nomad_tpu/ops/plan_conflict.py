"""Partitioned cross-plan conflict windows for the group-commit applier.

The leader's plan applier is the serialization point of optimistic
concurrency (server/plan_apply.py): under a contended storm it pays one
verify + one commit per plan.  ``evaluate_window`` verifies a whole
*window* of pending plans, and is the one way a window is verified:

  - the window's placement claims become a table of columns
    (``_Claims``): a slab-backed plan (structs/alloc_slab.py) fills its
    rows from the slab's columns in one gather, any other plan through
    ``alloc_vec`` / ``_net_row``;
  - ONE array pass over that table (``_array_pass``) computes every
    (plan, node) claim's verdict under the optimistic assumption that
    every earlier claim of the window on the same node was accepted:
    fit and bandwidth as prefix sums per node in eval order over the
    base snapshot's incremental usage mirror (models/fleet.py
    UsageMirror), ports against the node's live and reserved ports and
    the earlier claims'.  A node's verdicts depend only on earlier
    accepted claims on the same node, so on a node where every claim
    passes and nothing is out of the ordinary the optimistic verdicts
    ARE the sequential ones: final, with nothing to fold.  (The pass
    has a fixed cost: a window of fewer than ``ARRAY_PASS_MIN_CLAIMS``
    claims skips it and walks every claim; one small plan alone goes
    through ``plan_apply.evaluate_plan``, which is also the scalar
    reference every parity rig replays);
  - every other node — a rejection in its sequence, an eviction or
    in-place update, an id claimed twice, an in-flight apply's
    allocation, an odd network — takes the per-claim walk, PARTITIONED
    into connected components of the claim graph
    (``partition_window``: plans are vertices, joined when they claim a
    node in common).  Plans in different components touch disjoint
    node sets and therefore *cannot* conflict — each component
    verifies independently, inline on the caller's thread, while eval
    order is preserved exactly *within* each component;
  - order sensitivity within a component's walked nodes rides a
    *component overlay* (``_WindowState``) over a read-only per-window
    ``_Frame`` copied from the mirror: each plan's walked accepted
    portion is folded into the overlay before the next plan's verdicts
    — so plan i's claims are checked against committed state plus
    every earlier claim that could possibly interact with them,
    exactly the state sequential application would have reached;
  - claims the incremental path cannot serve (node not in the fleet,
    odd network topology) punt to the exact scalar walk against a
    component-local OptimisticSnapshot carrying the same folds, exactly
    as the per-plan verifier punts them.

The mirror is locked for the gathers, the probes of the live port sets
and the frame copy (the walked nodes only), and RELEASED before any
component walks, so concurrent worker-side syncs are never blocked
behind a window verify.  Verify is host code: it dispatches nothing to
a device and moves nothing across the host/device seam.

Deadline-aware component scheduling: components walk in the order of
their nearest member deadline (then window position) — under saturation
a near-deadline plan's component verifies first, which together with
the plan queue's deadline-promoted drain keeps ``expired_drops`` at 0.

A plan whose claims overlap an earlier plan in the window (the
order-sensitive prefix conflict) is reported as a ``fallback`` and
counted by the applier's ``conflict_fallbacks`` stat.  Two overlapping
plans are by construction in the same component.

Results are identical to calling ``evaluate_plan`` per plan in eval
order with the accepted portion of each plan folded into the view before
the next — the property the group-commit parity rigs
(tests/test_plan_batch.py) lock down for the array pass and the
all-walk path.
"""
from __future__ import annotations

import itertools
import operator
import time
from typing import Optional

import numpy as np

from nomad_tpu.structs import NODE_STATUS_READY, PlanResult

from nomad_tpu.utils.metrics import metrics

_MISS = object()

# The array pass has a fixed cost (some sixty numpy calls a window, a
# gather a plan) that a window of few claims does not pay back unless
# nearly all of them are on nodes it can decide, which it cannot know
# beforehand: under this many (plan, node) claims a window walks them
# all (PERF.md section 6, PR 29, has the measurements).
ARRAY_PASS_MIN_CLAIMS = 512


class WindowOutcome:
    """One plan's verdict within a window."""

    __slots__ = ("result", "fallback", "component", "claims", "walked")

    def __init__(self, result: PlanResult, fallback: bool,
                 component: int = 0, claims: int = 0,
                 walked: int = 0) -> None:
        self.result = result
        # True when this plan's claims overlapped an earlier plan in the
        # window (or an in-flight apply) — the order-sensitive prefix
        # conflict: its verdicts came from the component overlay, not
        # the clean dense pass.
        self.fallback = fallback
        # Scheduling-order index of the claim-graph component this plan
        # verified in (0 on the per-plan path).
        self.component = component
        # The plan's (plan, node) claims, and those of them the
        # per-claim walk decided (the array pass decided the rest).
        self.claims = claims
        self.walked = walked


class WindowVerdicts(list):
    """The outcomes list plus window-level partition/scheduling info
    (``.info`` — None on the per-plan path)."""

    def __init__(self, outcomes, info: Optional[dict] = None) -> None:
        super().__init__(outcomes)
        self.info = info


class _OverGet:
    """dict-shaped ``.get`` view: window overrides chained over the base
    frame's dict.  An override of None is a tombstone (entry removed
    within the window)."""

    __slots__ = ("over", "base")

    def __init__(self, over: dict, base: dict) -> None:
        self.over = over
        self.base = base

    def get(self, key, default=None):
        v = self.over.get(key, _MISS)
        if v is _MISS:
            return self.base.get(key, default)
        return default if v is None else v


class _DupGet:
    """``node_dup``-shaped view: duplicate-port counts recomputed from
    the window's materialized per-node port dicts, base passthrough for
    untouched nodes.  Port dicts are tens of entries, so the recompute
    is cheaper than incremental bookkeeping is error-prone."""

    __slots__ = ("ports", "base")

    def __init__(self, ports: dict, base: dict) -> None:
        self.ports = ports
        self.base = base

    def get(self, ni, default=None):
        pc = self.ports.get(ni)
        if pc is None:
            return self.base.get(ni, default)
        dup = sum(1 for c in pc.values() if c > 1)
        return dup if dup else default


class _Frame:
    """Read-only per-window copy of the mirror state the component
    walks consume, restricted to the window's touched nodes and claimed
    alloc ids.  Copied under the mirror lock, read without it — the
    lock is released before any component verifies, so worker-side
    mirror syncs never queue behind a window, and the component walks
    never read mirror state the lock discipline guards."""

    __slots__ = ("alloc_rows", "net_rows", "node_ports", "node_bw",
                 "node_net_keys", "node_dup")

    def __init__(self, mirror, ids, nis) -> None:
        alloc_rows = {}
        net_rows = {}
        m_rows = mirror.alloc_rows
        m_net = mirror.net_rows
        nis = set(nis)  # caller's set stays untouched; adds are O(1)
        for aid in ids:
            row = m_rows.get(aid)
            if row is not None:
                alloc_rows[aid] = (row[0], row[1])
                nis.add(row[0])
            nr = m_net.get(aid)
            if nr is not None:
                net_rows[aid] = nr
                nis.add(nr[0])
        self.alloc_rows = alloc_rows
        self.net_rows = net_rows
        self.node_ports = {}
        self.node_bw = {}
        self.node_net_keys = {}
        self.node_dup = {}
        for ni in nis:
            pc = mirror.node_ports.get(ni)
            if pc is not None:
                self.node_ports[ni] = dict(pc)
            bw = mirror.node_bw.get(ni)
            if bw:
                self.node_bw[ni] = bw
            keys = mirror.node_net_keys.get(ni)
            if keys is not None:
                self.node_net_keys[ni] = dict(keys)
            dup = mirror.node_dup.get(ni)
            if dup:
                self.node_dup[ni] = dup


class _WindowState:
    """Component overlay over a window ``_Frame``: base state plus the
    accepted portions of earlier plans in the component (and the
    in-flight apply's allocs that touch it), exposing exactly the reads
    the verifier needs — the same
    ``net_rows/node_ports/node_dup/node_bw/node_net_keys`` surface
    ``plan_apply._verify_node_net`` consumes, plus per-node 4-dim usage
    deltas for the fit check.  Never mutates the frame: per-node dicts
    are copied on first window write."""

    def __init__(self, frame, index_of) -> None:
        from nomad_tpu.models.fleet import _net_row, alloc_vec

        self._net_row = _net_row
        self._alloc_vec = alloc_vec
        self.m = frame
        self.index_of = index_of
        self.usage_delta: dict = {}   # ni -> [f, f, f, f]
        self._rows: dict = {}         # aid -> (ni, vec) | None
        self._net_over: dict = {}     # aid -> net row | None
        self._ports: dict = {}        # ni -> merged {port: count}
        self._bw: dict = {}           # ni -> merged mbits
        self._keys: dict = {}         # ni -> merged {(ip, dev): count}
        # The verifier-facing surface:
        self.net_rows = _OverGet(self._net_over, frame.net_rows)
        self.node_ports = _OverGet(self._ports, frame.node_ports)
        self.node_bw = _OverGet(self._bw, frame.node_bw)
        self.node_net_keys = _OverGet(self._keys, frame.node_net_keys)
        self.node_dup = _DupGet(self._ports, frame.node_dup)

    # -- removal accounting (the caller's removed_ids walk) ---------------
    def alloc_row(self, aid):
        """(ni, vec) of a live alloc — window override first, then the
        frame — or None when absent/removed."""
        v = self._rows.get(aid, _MISS)
        if v is not _MISS:
            return v
        return self.m.alloc_rows.get(aid)

    # -- copy-on-write materialization ------------------------------------
    def _ports_for(self, ni) -> dict:
        pc = self._ports.get(ni)
        if pc is None:
            pc = self._ports[ni] = dict(self.m.node_ports.get(ni, ()))
        return pc

    def _keys_for(self, ni) -> dict:
        keys = self._keys.get(ni)
        if keys is None:
            keys = self._keys[ni] = dict(
                self.m.node_net_keys.get(ni, ()))
        return keys

    def _bw_add(self, ni, mbits) -> None:
        self._bw[ni] = self.node_bw.get(ni, 0) + mbits

    # -- folds -------------------------------------------------------------
    def fold(self, alloc) -> None:
        """Apply one accepted alloc (placement or eviction) to the
        component overlay — the same old-row-out/new-row-in transition
        the mirror's own delta sync performs on commit."""
        aid = alloc.id
        old = self.alloc_row(aid)
        if old is not None:
            ni0, vec0 = old
            d = self.usage_delta.setdefault(ni0, [0.0] * 4)
            d[0] -= float(vec0[0])
            d[1] -= float(vec0[1])
            d[2] -= float(vec0[2])
            d[3] -= float(vec0[3])
        self._rows[aid] = None
        nr = self.net_rows.get(aid)
        if nr is not None:
            ni0, ports, mbits, key = nr
            if mbits:
                self._bw_add(ni0, -mbits)
            keys = self._keys_for(ni0)
            c = keys.get(key, 0) - 1
            if c > 0:
                keys[key] = c
            else:
                keys.pop(key, None)
            if ports:
                pc = self._ports_for(ni0)
                for p in ports:
                    c = pc.get(p, 0) - 1
                    if c > 0:
                        pc[p] = c
                    else:
                        pc.pop(p, None)
        self._net_over[aid] = None

        if alloc.terminal_status():
            return
        ni = self.index_of.get(alloc.node_id, -1)
        if ni < 0:
            return
        vec = self._alloc_vec(alloc)
        self._rows[aid] = (ni, vec)
        d = self.usage_delta.setdefault(ni, [0.0] * 4)
        d[0] += float(vec[0])
        d[1] += float(vec[1])
        d[2] += float(vec[2])
        d[3] += float(vec[3])
        row = self._net_row(alloc)
        if row is not None:
            ports, mbits, key = row
            self._net_over[aid] = (ni, ports, mbits, key)
            if mbits:
                self._bw_add(ni, mbits)
            keys = self._keys_for(ni)
            keys[key] = keys.get(key, 0) + 1
            if ports:
                pc = self._ports_for(ni)
                for p in ports:
                    pc[p] = pc.get(p, 0) + 1


def _ready(node) -> bool:
    """Can this node take placements at all?"""
    return node is not None and node.status == NODE_STATUS_READY \
        and not node.drain


def _touched(plan) -> set:
    return set(plan.node_update) | set(plan.node_allocation)


def _accepted_allocs(result) -> list:
    allocs = []
    for updates in result.node_update.values():
        allocs.extend(updates)
    for placements in result.node_allocation.values():
        allocs.extend(placements)
    allocs.extend(result.failed_allocs)
    return allocs


def partition_window(plans: list, plan_nodes=None) -> list:
    """Connected components of the window's claim graph: plans are
    vertices, joined when they claim (place on OR evict from) a node in
    common.  Returns a list of components, each an ascending list of
    plan indices, ordered by first member — so concatenating them in
    order visits a conflict-free permutation of the window.

    Union-find over a node-id -> first-claimant map: O(total claims)
    with near-constant find, cheap enough to run on every window."""
    n = len(plans)
    parent = list(range(n))

    def find(i: int) -> int:
        while parent[i] != i:
            parent[i] = parent[parent[i]]  # path halving
            i = parent[i]
        return i

    owner: dict = {}
    if plan_nodes is None:
        plan_nodes = [_touched(p) for p in plans]
    for i, nodes in enumerate(plan_nodes):
        for nid in nodes:
            j = owner.get(nid)
            if j is None:
                owner[nid] = i
            else:
                ri, rj = find(i), find(j)
                if ri != rj:
                    # Union by MIN root: a component's root is always
                    # its earliest plan, keeping output deterministic.
                    if rj < ri:
                        ri, rj = rj, ri
                    parent[rj] = ri
    comps: dict = {}
    for i in range(n):
        comps.setdefault(find(i), []).append(i)
    return [comps[r] for r in sorted(comps)]


def evaluate_window(snap, plans: list) -> WindowVerdicts:
    """Verify a window of plans; returns one WindowOutcome per plan,
    results identical to sequential ``evaluate_plan`` + fold-into-
    overlay per plan in eval order.

    ``snap`` may be an OptimisticSnapshot carrying an in-flight apply's
    overlay; it is MUTATED — each plan's accepted portion is folded in so
    the caller's overlay ends up exactly as sequential application would
    leave it.

    The window's placement claims are read as columns and ONE array
    pass decides every claim on a node where it can prove the answer
    (``_array_pass``): every claim of the window on that node fits
    under the all-earlier-claims-accepted prefix, and nothing on the
    node is out of the ordinary.  The claims of every other node — a
    rejection in the node's sequence, an eviction or in-place update,
    an id claimed twice, an in-flight apply's allocation, an odd
    network — take the per-claim walk (``_walk_component``), in eval
    order within their claim-graph component, the component with the
    nearest member deadline first.  A window of fewer than
    ``ARRAY_PASS_MIN_CLAIMS`` claims is too small for the pass to pay:
    all its claims walk, one plan alone through ``evaluate_plan``.
    Each outcome says how many claims its plan made and how many of
    them a walk decided; the totals are the counters
    ``nomad.plan.claims`` / ``nomad.plan.claims_walked``.
    """
    from nomad_tpu.server.plan_apply import (
        OptimisticSnapshot,
        evaluate_plan,
    )

    overlay = snap if isinstance(snap, OptimisticSnapshot) \
        else OptimisticSnapshot(snap)
    start = time.perf_counter()
    if len(plans) == 1 and \
            len(_touched(plans[0])) < ARRAY_PASS_MIN_CLAIMS:
        # One small plan: no cross-plan structure to exploit and too
        # few claims for the array pass.  The per-plan path carries its
        # own vectorized fit (plan_apply._evaluate_plan_vec), and every
        # claim is its walk's.
        outcomes = None
    else:
        # Only a caller-owned overlay needs the fold; a throwaway one
        # built here is dead work.
        outcomes = _evaluate_window_vec(overlay, plans,
                                        fold=overlay is snap)
    if outcomes is None:
        # That, or no incremental mirror for this snapshot: per-plan
        # exact path against the running overlay, still in eval order.
        outcomes = WindowVerdicts([])
        dirty: set = {n for n in overlay._by_node if n}
        for plan in plans:
            nodes = _touched(plan)
            result = evaluate_plan(overlay, plan)
            outcomes.append(WindowOutcome(
                result, bool(nodes & dirty),
                claims=len(nodes), walked=len(nodes)))
            if overlay is snap or len(plans) > 1:
                overlay.upsert_allocs(_accepted_allocs(result))
            # Same fallback definition as the vec path's `claimed`:
            # every node an earlier plan TOUCHED (accepted or not), so
            # the stat means one thing regardless of which path ran.
            dirty |= nodes
    metrics.measure_since("nomad.plan.evaluate_window", start)
    metrics.incr_counter("nomad.plan.claims",
                         sum(o.claims for o in outcomes))
    metrics.incr_counter("nomad.plan.claims_walked",
                         sum(o.walked for o in outcomes))
    return outcomes


class _Prep:
    """Everything the component walks share, frozen by the coordinator
    before any component starts: the walked claims' records, the frame,
    and the in-flight overlay's contents.  Read-only once built.

    ``walk[i]`` maps a node id to the record of plan i's claim there,
    for the claims the walk decides: True (evicts only: always fits),
    False (node missing or not ready), None (node not in the fleet: the
    scalar walk), or ``(ni, node, placements, removed ids, used, caps)``
    with the dense base fit's numbers.  A claim with no record was
    decided by the array pass: accepted."""

    __slots__ = ("plans", "plan_nodes", "walk", "frame", "index_of",
                 "statics", "base", "refresh_index", "inflight",
                 "inflight_nodes", "inflight_by_node", "inflight_by_id")


class _Claims:
    """The window's placement claims as columns, built once a window
    and before the mirror is locked.  A pair is one (plan, node) claim
    that places something, in eval order; a row is one placement, rows
    of a pair adjacent; a port belongs to a row.

    A plan whose placements are rows of one ``AllocSlab``, none with a
    heavy field reassigned, fills its rows from the slab's columns in
    one gather (``AllocSlab.verify_columns``); any other plan fills them
    through ``alloc_vec`` / ``_net_row``, one allocation at a time — the
    same numbers either way."""

    __slots__ = ("pair_plan", "pair_nid", "pair_lists", "pair_row0",
                 "row_pair", "row_vec", "row_mbits", "row_netted",
                 "row_ips", "row_devs", "row_ids", "port_row", "ports",
                 "update_claims", "update_ids", "failed_ids")

    def __init__(self, plans: list) -> None:
        pair_plan: list = []
        self.pair_nid = pair_nid = []
        self.pair_lists = pair_lists = []
        pair_cnt: list = []
        chunks: list = []  # a verify_columns tuple a plan
        self.row_ids = row_ids = []
        # (plan, node) claims that evict or update something, and the
        # ids of everything a plan names besides its placements.
        self.update_claims = update_claims = []
        self.update_ids = update_ids = []
        self.failed_ids = failed_ids = []
        get_id = operator.itemgetter("id")
        for i, plan in enumerate(plans):
            for nid, updates in plan.node_update.items():
                if updates:
                    update_claims.append((i, nid))
                    update_ids.extend(a.id for a in updates)
            failed_ids.extend(a.id for a in plan.failed_allocs)
            na = plan.node_allocation
            lists = [pl for pl in na.values() if pl]
            if not lists:
                continue
            flat = list(itertools.chain.from_iterable(lists))
            dicts = list(map(vars, flat))
            slab = dicts[0].get("_slab")
            cols = None
            if slab is not None:
                # Two dict probes a row prove it is a row of the plan's
                # slab with no heavy field reassigned (a field only
                # materialised reads as the columns do); every number
                # then comes off columns.
                rows = [d["_srow"] for d in dicts
                        if d.get("_slab") is slab and "_hmut" not in d]
                if len(rows) == len(dicts):
                    cols = slab.verify_columns(
                        np.asarray(rows, dtype=np.int64))
            chunks.append(cols if cols is not None
                          else _object_columns(flat))
            row_ids.extend(map(get_id, dicts))
            pair_plan.extend(itertools.repeat(i, len(lists)))
            pair_nid.extend(na if len(lists) == len(na) else
                            (nid for nid, pl in na.items() if pl))
            pair_lists.extend(lists)
            pair_cnt.extend(map(len, lists))
        self.pair_plan = np.asarray(pair_plan, dtype=np.int64)
        cnt = np.asarray(pair_cnt, dtype=np.int64)
        self.pair_row0 = np.cumsum(cnt) - cnt
        self.row_pair = np.repeat(np.arange(len(cnt)), cnt)
        self.row_vec, self.row_mbits, self.row_netted, pcnt, \
            self.ports = (np.concatenate([c[k] for c in chunks])
                          for k in range(5))
        self.row_ips = [ip for c in chunks for ip in c[5]]
        self.row_devs = [dev for c in chunks for dev in c[6]]
        self.port_row = np.repeat(np.arange(len(self.row_pair)), pcnt)


def _object_columns(allocs: list) -> tuple:
    """``AllocSlab.verify_columns`` for allocations read one at a time,
    through ``alloc_vec`` / ``_net_row`` as the walk reads them."""
    from nomad_tpu.models.fleet import _net_row, alloc_vec

    k = len(allocs)
    vec = np.empty((k, 4), dtype=np.float32)
    mbits = np.zeros(k, dtype=np.int64)
    netted = np.zeros(k, dtype=bool)
    cnt = np.zeros(k, dtype=np.int64)
    ports: list = []
    ips: list = [None] * k
    devs: list = [None] * k
    for r, a in enumerate(allocs):
        vec[r] = alloc_vec(a)[:4]
        row = _net_row(a)
        if row is not None:
            netted[r] = True
            mbits[r] = row[1]
            cnt[r] = len(row[0])
            ports.extend(row[0])
            ips[r], devs[r] = row[2]
    return (vec, mbits, netted, cnt, np.asarray(ports, dtype=np.int64),
            ips, devs)


def _walk_all_records(prep, mirror) -> None:
    """A record for every claim of the window, so that all of them
    walk: classify each, one dense base-fit gather (usage + reserved +
    sum-of-placements: the 4 dims Resources.superset checks, float32
    like the mirror rows), and the frame over every touched node.
    Caller holds the mirror lock."""
    from nomad_tpu.models.fleet import alloc_vec

    base = prep.base
    statics = prep.statics
    index_of = prep.index_of
    pairs: list = []     # (plan_i, nid, ni, node, placements, removed)
    vec_rows: list = []  # placement resource vectors
    vec_pair: list = []  # pair index per vec row
    frame_ids: set = set(prep.inflight_by_id)
    frame_nis: set = set()
    for i, plan in enumerate(prep.plans):
        records = prep.walk[i]
        for nid in prep.plan_nodes[i]:
            placements = plan.node_allocation.get(nid)
            removed = {a.id for a in plan.node_update.get(nid, ())}
            frame_ids |= removed
            ni = index_of.get(nid, -1)
            if ni >= 0:
                frame_nis.add(ni)
            if not placements:
                if removed:
                    records[nid] = True  # evict-only: always fits
                continue
            frame_ids.update(a.id for a in placements)
            node = base.node_by_id(nid)
            if not _ready(node):
                records[nid] = False
            elif ni < 0:
                records[nid] = None  # not in fleet: exact walk
            else:
                removed.update(a.id for a in placements)  # in-place upd
                for a in placements:
                    vec_pair.append(len(pairs))
                    vec_rows.append(alloc_vec(a))
                pairs.append((i, nid, ni, node, placements, removed))
    if pairs:
        ni_arr = np.fromiter((p[2] for p in pairs), dtype=np.int64,
                             count=len(pairs))
        delta = np.zeros((len(pairs), 4), dtype=np.float32)
        np.add.at(delta, np.asarray(vec_pair, dtype=np.int64),
                  np.asarray(vec_rows, dtype=np.float32)[:, :4])
        used = mirror.usage[ni_arr, :4] + statics.reserved[ni_arr, :4] \
            + delta
        for pair, used_p, caps_p in zip(
                pairs, used.tolist(),
                statics.capacity[ni_arr, :4].tolist()):
            prep.walk[pair[0]][pair[1]] = pair[2:] + (used_p, caps_p)
    # The in-flight apply's allocs fold into component overlays, so
    # their frame rows (and nodes) must ride along too.
    for nid in prep.inflight_nodes:
        ni = index_of.get(nid, -1)
        if ni >= 0:
            frame_nis.add(ni)
    prep.frame = _Frame(mirror, frame_ids, frame_nis)


def _array_pass(prep, mirror, comps: list) -> bool:
    """The window's claims as columns and one array pass over them:
    fills ``prep.walk`` with the records of the claims that must walk
    and ``prep.frame`` with the mirror state their walk reads; every
    claim without a record is decided here, accepted.  Returns False
    when the snapshot cannot take the incremental path.

    The pass computes, for every (plan, node) claim that places
    something, what the sequential order would see IF every earlier
    claim of the window on that node had been accepted: the node's
    usage + reserved + the prefix sum of those claims against its
    capacity (asks are whole numbers under 2^24, so the float64 sums
    are exact), reserved + live + prefix bandwidth against the NIC, and
    each port against the node's live and reserved ports and the ports
    of the earlier claims.  A node's verdicts depend only on earlier
    ACCEPTED claims on the same node, so where all of a node's claims
    pass, the optimistic answers are the exact ones: those claims are
    accepted, and nothing is folded for them.

    Every other node is walked, all its claims: a node whose sequence
    holds a rejection; a node a plan evicts from or updates in place (an
    id that is live in the mirror); an id claimed twice in the window;
    a node an in-flight apply placed on, or an id it carries; a node
    that is missing, not ready, out of the fleet, multi-network,
    reserving off its own network, holding odd or doubled-up offers; an
    offer off the node's (ip, device); and every node of a component
    that holds an ``all_at_once`` plan, if any node of that component is
    walked (a rejection there takes the plan's other claims back).

    The mirror is locked for the sync, the gathers and the probes of
    the live port sets, and for the frame — which copies the walked
    nodes only; the table is built before it."""
    from nomad_tpu.server.plan_apply import _node_net_static

    plans = prep.plans
    plan_nodes = prep.plan_nodes
    base = prep.base
    statics = prep.statics
    index_of = prep.index_of
    by_id = prep.inflight_by_id

    tab = _Claims(plans)
    n_pairs = len(tab.pair_nid)
    pair_ni = np.fromiter(
        map(index_of.get, tab.pair_nid, itertools.repeat(-1)),
        dtype=np.int64, count=n_pairs)
    # The window's nodes, each once: ``pair_ord`` is a claim's node as
    # an ordinal into them.
    uniq_ni, pair_ord = np.unique(pair_ni, return_inverse=True)
    uniq_l = uniq_ni.tolist()
    n_nodes = len(uniq_l)
    # walk_ord[k]: node k's claims take the per-claim walk.
    walk_ord = np.zeros(n_nodes, dtype=bool)
    nodes_u: list = [None] * n_nodes
    reserved_ports: list = [frozenset()] * n_nodes
    bw_fixed = np.zeros(n_nodes, dtype=np.int64)  # reserved, then + live
    bw_avail = np.zeros(n_nodes, dtype=np.int64)
    ip_u: list = [None] * n_nodes
    dev_u: list = [None] * n_nodes
    node_by_id = base.node_by_id
    node_ids = statics.node_ids
    for k, ni in enumerate(uniq_l):
        walk_ord[k] = True
        if ni < 0:
            continue  # not in the fleet: each claim finds its own node
        node = nodes_u[k] = node_by_id(node_ids[ni])
        if not _ready(node):
            continue
        static = _node_net_static(statics, node, ni)
        if static:
            reserved_ports[k], bw_fixed[k], bw_avail[k], \
                (ip_u[k], dev_u[k]) = static
            walk_ord[k] = False

    def walk_nodes(nis) -> None:
        """Mark the window's nodes among ``nis`` as walked."""
        nis = np.asarray(nis, dtype=np.int64)
        pos = np.searchsorted(uniq_ni, nis)
        pos[pos == n_nodes] = 0
        walk_ord[pos[uniq_ni[pos] == nis]] = True

    walk_all = False
    walk_nodes([index_of.get(nid, -1)
                for nid in itertools.chain(
                    (nid for _i, nid in tab.update_claims),
                    prep.inflight_nodes)])
    # An id named twice in the window is folded twice: its claims walk.
    all_ids = tab.row_ids + tab.update_ids + tab.failed_ids
    dup_ids: set = set()
    if len(set(all_ids)) != len(all_ids):
        seen: set = set()
        dup_ids = {aid for aid in all_ids if aid in seen or seen.add(aid)}
        walk_all = walk_all or not dup_ids.isdisjoint(tab.failed_ids)
    row_ord = pair_ord[tab.row_pair]

    def walk_rows_of(ids) -> None:
        """Walk the node of every placement whose id is in ``ids``."""
        walk_ord[row_ord[[r for r, aid in enumerate(tab.row_ids)
                          if aid in ids]]] = True

    if dup_ids:
        walk_rows_of(dup_ids)
    if by_id and not by_id.keys().isdisjoint(tab.row_ids):
        walk_rows_of(by_id)
    walk_all = walk_all or not by_id.keys().isdisjoint(tab.failed_ids)
    # An offer off its node's (ip, device) — NET_KEY_ODD is one — needs
    # the scalar walk.
    row_ord_l = row_ord.tolist()
    node_ips = list(map(ip_u.__getitem__, row_ord_l))
    node_devs = list(map(dev_u.__getitem__, row_ord_l))
    if node_ips != tab.row_ips or node_devs != tab.row_devs:
        on_net = np.fromiter(map(operator.eq, tab.row_ips, node_ips),
                             dtype=bool, count=len(row_ord_l)) \
            & np.fromiter(map(operator.eq, tab.row_devs, node_devs),
                          dtype=bool, count=len(row_ord_l))
        walk_ord[row_ord[tab.row_netted & ~on_net]] = True
    # A port claimed twice on one node inside the window.
    port_ord = row_ord[tab.port_row]
    port_keys = np.sort((port_ord << 32) | (tab.ports & 0xFFFFFFFF))
    walk_ord[port_keys[1:][port_keys[1:] == port_keys[:-1]] >> 32] = True
    # Own asks a claim, and its place in its node's sequence: claims
    # sorted by node, eval order kept within a node.
    delta = np.add.reduceat(tab.row_vec, tab.pair_row0, axis=0)
    pair_mbits = np.add.reduceat(tab.row_mbits, tab.pair_row0)
    order = np.argsort(pair_ord, kind="stable")
    ord_s = pair_ord[order]
    first = np.flatnonzero(np.r_[True, ord_s[1:] != ord_s[:-1]])
    seg = np.repeat(np.arange(len(first)),
                    np.diff(np.r_[first, n_pairs]))

    def prefix(values):
        """Sum of ``values`` (claims sorted by node) over the claims of
        the same node up to and including each."""
        cs = np.cumsum(values, axis=0)
        return cs - (cs - values)[first][seg]

    records = prep.walk
    frame_ids: set = set(tab.update_ids)
    frame_ids.update(by_id)

    # The net dicts are mutated in place by concurrent worker syncs;
    # hold the mirror for the composite read — but ONLY for the gathers,
    # the probes and the frame copy: the walks run lock-free against
    # the frame.
    with mirror.lock:
        if not mirror.sync_net(base):
            return False  # snapshot older than the mirror
        # Live occupancy of the window's nodes.
        keys_of = mirror.node_net_keys
        dup_of = mirror.node_dup
        bw_of = mirror.node_bw
        ports_of = mirror.node_ports
        live_ports: list = [()] * n_nodes
        for k, ni in enumerate(uniq_l):
            if walk_ord[k]:
                continue
            keys = keys_of.get(ni)
            if (keys and (len(keys) > 1
                          or (ip_u[k], dev_u[k]) not in keys)) \
                    or dup_of.get(ni):
                walk_ord[k] = True  # odd or doubled-up live offers
                continue
            pc = ports_of.get(ni)
            if pc:
                if not pc.keys().isdisjoint(reserved_ports[k]):
                    walk_ord[k] = True  # live port on a reserved one
                    continue
                live_ports[k] = pc
            bw_fixed[k] += bw_of.get(ni, 0)
        # A claimed port that is live or reserved on its node.
        taken = np.fromiter(
            (p in live_ports[k] or p in reserved_ports[k]
             for k, p in zip(port_ord.tolist(), tab.ports.tolist())),
            dtype=bool, count=len(port_ord))
        walk_ord[port_ord[taken]] = True
        # An id that is live in the mirror is an in-place update (an
        # id with a net row has a usage row).
        live = mirror.alloc_rows.keys()
        if not live.isdisjoint(tab.row_ids):
            walk_rows_of(mirror.alloc_rows)
        walk_all = walk_all or not live.isdisjoint(tab.failed_ids)
        # Bandwidth: reserved + live + this and the earlier claims.
        bw = bw_fixed[ord_s] + prefix(pair_mbits[order])
        walk_ord[ord_s[bw > bw_avail[ord_s]]] = True

        # Dense fit inputs over every claim at once: the 4 dims
        # Resources.superset checks, float32 like the mirror rows
        # (exact for values < 2^24, i.e. any realistic node).
        used = mirror.usage[pair_ni, :4] \
            + statics.reserved[pair_ni, :4] + delta
        caps = statics.capacity[pair_ni, :4]
        d64 = delta[order].astype(np.float64)
        fits = (used[order] + (prefix(d64) - d64)
                <= caps[order]).all(axis=1)
        # Close the set of walked nodes over the fit verdicts and the
        # all_at_once rule, and write the walked claims' records.
        walk_ord[ord_s[~fits]] = True
        if walk_all:
            walk_ord[:] = True
        pair_walk = walk_ord[pair_ord]
        if any(p.all_at_once for p in plans):
            walked = {tab.pair_nid[p]
                      for p in np.flatnonzero(pair_walk).tolist()}
            walked.update(nid for _i, nid in tab.update_claims)
            for comp in comps:
                if any(plans[i].all_at_once for i in comp) and any(
                        not plan_nodes[i].isdisjoint(walked)
                        for i in comp):
                    for i in comp:
                        walked |= plan_nodes[i]
            pair_walk = np.fromiter(map(walked.__contains__, tab.pair_nid),
                                    dtype=bool, count=n_pairs)
        walked_pairs = np.flatnonzero(pair_walk)
        for p, i, k, used_p, caps_p in zip(
                walked_pairs.tolist(),
                tab.pair_plan[walked_pairs].tolist(),
                pair_ord[walked_pairs].tolist(),
                used[walked_pairs].tolist(), caps[walked_pairs].tolist()):
            nid = tab.pair_nid[p]
            ni = uniq_l[k]
            node = nodes_u[k] if ni >= 0 else node_by_id(nid)
            if not _ready(node):
                records[i][nid] = False
                continue
            if ni < 0:
                records[i][nid] = None  # not in fleet: exact walk
                continue
            placements = tab.pair_lists[p]
            removed = {a.id for a in plans[i].node_update.get(nid, ())}
            removed.update(a.id for a in placements)  # in-place upd
            frame_ids.update(removed)
            records[i][nid] = (ni, node, placements, removed,
                               used_p, caps_p)
        for i, nid in tab.update_claims:
            records[i].setdefault(nid, True)  # evict-only: always fits
        frame_nis = {ni for ni, w in zip(uniq_l, walk_ord.tolist())
                     if w and ni >= 0}
        # The in-flight apply's allocs fold into component overlays, so
        # their frame rows (and nodes) must ride along too.
        for nid in prep.inflight_nodes:
            ni = index_of.get(nid, -1)
            if ni >= 0:
                frame_nis.add(ni)
        prep.frame = _Frame(mirror, frame_ids, frame_nis)

    return True


def _evaluate_window_vec(overlay, plans: list,
                         fold: bool = True) -> Optional[WindowVerdicts]:
    """One window through the incremental path: the array pass over
    the window's claims (``_array_pass``) decides what it can prove,
    the per-claim walk (``_walk_component``, a claim-graph component at
    a time, in eval order) decides the rest.  A window of fewer than
    ``ARRAY_PASS_MIN_CLAIMS`` claims walks them all
    (``_walk_all_records``): the pass has a fixed cost that so few
    claims do not pay back.  Returns None when the snapshot cannot take
    the incremental path at all."""
    from nomad_tpu.models.fleet import fleet_cache, mirror_for

    base = overlay.base
    if getattr(base, "_t", None) is None:
        return None
    plan_nodes = [_touched(p) for p in plans]
    if not any(any(p.node_allocation.values()) for p in plans):
        # Evict/update-only window: every per-node verdict is True by
        # definition; don't spin up the mirror's net tracking for it.
        # The fallback stat keeps the uniform definition (claims
        # overlapping an earlier plan's touched nodes) even though the
        # verdicts here are state-independent.
        outcomes = WindowVerdicts([])
        claimed = {n for n in overlay._by_node if n}
        for plan, nodes in zip(plans, plan_nodes):
            result = PlanResult(
                node_update={k: v for k, v in plan.node_update.items()
                             if v},
                node_allocation={k: v for k, v
                                 in plan.node_allocation.items() if v},
                failed_allocs=list(plan.failed_allocs))
            outcomes.append(WindowOutcome(result, bool(nodes & claimed),
                                          claims=len(nodes)))
            if fold:
                overlay.upsert_allocs(_accepted_allocs(result))
            claimed |= nodes
        return outcomes

    statics = fleet_cache.statics_for(base)
    mirror = mirror_for(statics)
    index_of = statics.index_of

    # Components are computed up front (pure on the plans): the
    # all_at_once rule of the array pass needs each plan's.
    comps = partition_window(plans, plan_nodes)

    prep = _Prep()
    prep.plans = plans
    prep.plan_nodes = plan_nodes
    prep.base = base
    prep.statics = statics
    prep.index_of = index_of
    prep.refresh_index = max(overlay.get_index("nodes"),
                             overlay.get_index("allocs"))
    prep.inflight = list(overlay._overlay.values())
    prep.inflight_nodes = {n for n in overlay._by_node if n}
    # Indexed ONCE per window: each component slices the in-flight
    # overlay by ITS nodes/ids in O(component), not O(overlay) — a
    # per-component scan would re-grow the O(window^2) fold churn the
    # partition exists to remove.  Entries carry their overlay
    # insertion ordinal so component folds keep the sequential order.
    prep.inflight_by_node = by_node = {}
    prep.inflight_by_id = by_id = {}
    for k, a in enumerate(prep.inflight):
        by_node.setdefault(a.node_id, []).append((k, a))
        by_id[a.id] = (k, a)

    prep.walk = [dict() for _ in plans]
    if sum(map(len, plan_nodes)) < ARRAY_PASS_MIN_CLAIMS:
        with mirror.lock:
            if not mirror.sync_net(base):
                return None  # snapshot older than the mirror: scalar truth
            _walk_all_records(prep, mirror)
    elif not _array_pass(prep, mirror, comps):
        return None

    # Walk the components.  Mirror lock released — the walks read only
    # the frame, the base snapshot, and prep.  Nearest member deadline
    # first (ties by window position), so a near-deadline plan's
    # component is never last in line.
    def comp_key(k: int) -> tuple:
        deadline = min((plans[i].deadline for i in comps[k]
                        if plans[i].deadline), default=float("inf"))
        return (deadline, comps[k][0])

    order_c = sorted(range(len(comps)), key=comp_key)
    results = [_walk_component(prep, comps[k]) for k in order_c]

    slots: list = [None] * len(plans)
    comp_walls: list = []
    comp_t0s: list = []
    accepted_by_plan: list = [None] * len(plans)
    for ordinal, (entries, comp_t0, comp_wall) in enumerate(results):
        comp_walls.append(comp_wall)
        comp_t0s.append(comp_t0)
        for i, outcome, accepted in entries:
            outcome.component = ordinal
            slots[i] = outcome
            accepted_by_plan[i] = accepted
    if fold:
        # Fold every accepted portion into the caller's overlay in eval
        # order — the exact end state sequential application leaves.
        for i in range(len(plans)):
            overlay.upsert_allocs(accepted_by_plan[i])
    info = {
        "components": len(comps),
        "sizes": [len(c) for c in comps],
        "order": order_c,
        "comp_walls": comp_walls,
        "comp_t0s": comp_t0s,  # perf_counter epoch (span conversion)
    }
    return WindowVerdicts(slots, info)


def _walk_component(prep, comp: list) -> tuple:
    """In-order verdicts of one claim-graph component: the per-claim
    walk, against the component's own overlay, for the claims that have
    a record in ``prep.walk``; every other claim was decided by the
    array pass (accepted) and only joins its plan's result.  Returns
    ([(plan_index, WindowOutcome, accepted)], t0_perf_counter,
    wall_seconds).  Reads only frozen prep state + the base snapshot."""
    from nomad_tpu.server.plan_apply import (
        OptimisticSnapshot,
        _evaluate_node_plan,
        _verify_node_net,
    )

    t0 = time.perf_counter()
    plans = prep.plans
    statics = prep.statics
    inflight_nodes = prep.inflight_nodes
    wm = _WindowState(prep.frame, prep.index_of)
    comp_view: Optional[OptimisticSnapshot] = None
    accepted_log: list = []

    if prep.inflight and any(prep.walk[i] for i in comp):
        # Only the in-flight allocs this component's walk can see:
        # anything on its walked nodes, or anything its walked claims
        # replace/evict by id — gathered via the per-window indexes in
        # O(component), folded in the overlay's insertion order (the
        # fold order sequential application used).
        picked: dict = {}
        by_id = prep.inflight_by_id
        for i in comp:
            for nid, record in prep.walk[i].items():
                for k, a in prep.inflight_by_node.get(nid, ()):
                    picked[k] = a
                ids = [a.id for a in plans[i].node_update.get(nid, ())]
                if type(record) is tuple:
                    ids.extend(record[3])
                for aid in ids:
                    entry = by_id.get(aid)
                    if entry is not None:
                        picked[entry[0]] = entry[1]
        for k in sorted(picked):
            wm.fold(picked[k])  # in-flight apply: committed state

    def view() -> OptimisticSnapshot:
        # Exact-walk punts are rare; the component's OptimisticSnapshot
        # is built lazily on the first one, seeded to the state the
        # shared sequential overlay would hold at this point.
        nonlocal comp_view
        if comp_view is None:
            comp_view = OptimisticSnapshot(prep.base)
            comp_view.upsert_allocs(prep.inflight)
            for accepted in accepted_log:
                comp_view.upsert_allocs(accepted)
        return comp_view

    entries: list = []
    claimed: set = set()
    last = comp[-1]
    for i in comp:
        plan = plans[i]
        records = prep.walk[i]
        nodes = prep.plan_nodes[i]
        updates = plan.node_update
        placed = plan.node_allocation
        fallback = (not nodes.isdisjoint(claimed)) or \
                   (not nodes.isdisjoint(inflight_nodes))
        result = PlanResult(failed_allocs=list(plan.failed_allocs))
        # What the walk accepted: folded for the plans after this one.
        # (What the array pass accepted is in their prefix already.)
        fold_updates: list = []
        fold_placed: list = []
        if not records:
            # The array pass decided every claim of this plan.
            result.node_allocation = {nid: placed[nid] for nid in nodes
                                      if placed.get(nid)}
        for nid in (nodes if records else ()):
            record = records.get(nid, _MISS)
            if record is _MISS:
                ok = True  # the array pass decided it
            elif record is None:
                # Vector-ineligible claim: exact walk against the
                # component view (identical to the sequential verdict).
                ok = _evaluate_node_plan(view(), plan, nid)
            elif type(record) is tuple:
                ni, node, placements, removed, used, caps = record
                u0, u1, u2, u3 = used
                d = wm.usage_delta.get(ni)
                if d is not None:
                    u0 += d[0]
                    u1 += d[1]
                    u2 += d[2]
                    u3 += d[3]
                for aid in removed:
                    row = wm.alloc_row(aid)
                    if row is not None and row[0] == ni:
                        vec = row[1]
                        u0 -= float(vec[0])
                        u1 -= float(vec[1])
                        u2 -= float(vec[2])
                        u3 -= float(vec[3])
                ok = (u0 <= caps[0] and u1 <= caps[1] and u2 <= caps[2]
                      and u3 <= caps[3])
                if ok:
                    # Port collisions + bandwidth: exact, against
                    # frame + component overlay (None punts the node
                    # to the scalar walk).
                    ok = _verify_node_net(wm, statics, node, ni,
                                          placements, removed)
                    if ok is None:
                        ok = _evaluate_node_plan(view(), plan, nid)
            else:
                ok = record
            if ok:
                if updates.get(nid):
                    result.node_update[nid] = updates[nid]
                    fold_updates.extend(updates[nid])
                if placed.get(nid):
                    result.node_allocation[nid] = placed[nid]
                    if record is not _MISS:
                        fold_placed.extend(placed[nid])
                continue
            result.refresh_index = prep.refresh_index
            if plan.all_at_once:
                result.node_update = {}
                result.node_allocation = {}
                fold_updates = []
                fold_placed = []
                break
        accepted = _accepted_allocs(result)
        accepted_log.append(accepted)
        if comp_view is not None:
            comp_view.upsert_allocs(accepted)
        if i != last:
            for alloc in itertools.chain(fold_updates, fold_placed,
                                         result.failed_allocs):
                wm.fold(alloc)
        claimed |= nodes
        entries.append((i, WindowOutcome(
            result, fallback, claims=len(nodes), walked=len(records)),
            accepted))
    return entries, t0, time.perf_counter() - t0
