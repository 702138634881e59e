"""The plain reference: what a placement has to satisfy, in float64 numpy.

Imports nothing of the program and takes nothing the program made
except its ANSWERS (the committed allocations as read back).  Fleet and
jobs come from the configuration file, the traffic file and ``--seed``.

Semantics (Nomad 0.1.2, ``nomad/structs/funcs.go`` AllocsFit/ScoreFit and
``scheduler/rank.go`` job anti-affinity, as the project states them):

- a node holds a set of allocations iff, in every dimension,
  reserved + sum(asks) <= capacity, bandwidth included, and no port is
  handed out twice;
- a placement of an ask on a node scores BestFit v3,
  ``clip(20 - (10^(1-u_cpu/avail_cpu) + 10^(1-u_mem/avail_mem)), 0, 18)``
  with u = reserved + used + ask and avail = capacity - reserved, minus
  the anti-affinity penalty times the job's allocations already there;
- a scheduler takes, for each group-slot in job order, the best-scoring
  feasible nodes (as many as it still has copies to place), against ONE
  snapshot of the committed state plus its own earlier picks.

Under concurrent clients the snapshot a plan was made on is not in the
answer, so ``check_plan`` finds it: of the committed prefixes between
the job's registration and the plan's commit, the one under which the
scores the program RECORDED for its picks agree best with this file's.
A program that scores wrongly agrees with none of them.
"""
from __future__ import annotations

import random
import uuid

import numpy as np

DIMS = ("cpu", "memory_mb", "disk_mb", "iops", "mbits", "port_slots")
PORT_SLOTS = 40000.0          # dynamic ports 20000..59999
MIN_DYNAMIC_PORT, MAX_DYNAMIC_PORT = 20000, 60000
PENALTY = {"service": 10.0, "batch": 5.0}
NEG = -1.0e30


def seeded_uuid(rng: random.Random) -> str:
    return str(uuid.UUID(int=rng.getrandbits(128), version=4))


def make_fleet(config: dict, seed: int, n_nodes: int) -> dict:
    """ids + capacity/reserved [n, 6] of ``n_nodes`` nodes of the
    configuration's one machine shape; ids are drawn from the seed."""
    rng = random.Random(f"{seed}:fleet")
    shape = config["node"]
    res = shape["reserved"]
    cap = [shape["cpu"], shape["memory_mb"], shape["disk_mb"],
           shape["iops"], shape["mbits"], PORT_SLOTS]
    rsv = [res.get("cpu", 0), res.get("memory_mb", 0),
           res.get("disk_mb", 0), res.get("iops", 0), res.get("mbits", 0),
           float(len(res.get("ports", [])))]
    return {
        "ids": [seeded_uuid(rng) for _ in range(n_nodes)],
        "capacity": np.tile(np.asarray(cap, dtype=np.float64), (n_nodes, 1)),
        "reserved": np.tile(np.asarray(rsv, dtype=np.float64), (n_nodes, 1)),
        "reserved_ports": list(res.get("ports", [])),
    }


def group_ask(group: dict) -> np.ndarray:
    """[6] ask of one copy of a plain group spec."""
    ports = len(group.get("dynamic_ports", ()))
    return np.asarray([group["cpu"], group["memory_mb"],
                       group.get("disk_mb", 0), group.get("iops", 0),
                       group.get("mbits", 0), ports], dtype=np.float64)


# ---------------------------------------------------------------------------
# fit: the guarantee, exact
# ---------------------------------------------------------------------------

def oversubscribed_nodes(fleet: dict, allocs: dict) -> tuple:
    """(count of nodes whose committed allocations do not fit, a few
    examples).  ``allocs``: columns ``node`` [A] int (index into the
    fleet, -1 unknown), ``vec`` [A, 6], ``ports`` list of int lists."""
    n = len(fleet["ids"])
    node = allocs["node"]
    used = np.zeros((n, len(DIMS)), dtype=np.float64)
    known = node >= 0
    np.add.at(used, node[known], allocs["vec"][known])
    over = ((used + fleet["reserved"]) > fleet["capacity"]).any(axis=1)
    # Ports: one (node, port) pair per hand-out; a pair seen twice, a
    # node-reserved port or one outside the dynamic range is a collision.
    pair_node, pair_port = [], []
    for i, ports in enumerate(allocs["ports"]):
        if ports and node[i] >= 0:
            pair_node.extend([node[i]] * len(ports))
            pair_port.extend(ports)
    if pair_node:
        pn = np.asarray(pair_node, dtype=np.int64)
        pp = np.asarray(pair_port, dtype=np.int64)
        bad = (pp < MIN_DYNAMIC_PORT) | (pp >= MAX_DYNAMIC_PORT) | \
            np.isin(pp, fleet["reserved_ports"])
        key = pn * 100000 + pp
        uniq, counts = np.unique(key, return_counts=True)
        over[(uniq[counts > 1] // 100000)] = True
        over[pn[bad]] = True
    idx = np.nonzero(over)[0]
    return int(len(idx)), [int(i) for i in idx[:5]]


# ---------------------------------------------------------------------------
# scores
# ---------------------------------------------------------------------------

class Scorer:
    """BestFit v3 over node rows, in ``dtype`` (float64 is the
    reference; a lower one is the control put in the program's place:
    every input is rounded to it and every operation done in it)."""

    def __init__(self, fleet: dict, dtype=np.float64) -> None:
        self.dtype = np.dtype(dtype)
        t = self.dtype.type
        self.capacity = fleet["capacity"].astype(self.dtype)
        self.base = fleet["reserved"].astype(self.dtype)
        self.avail_cpu = (self.capacity[:, 0] - self.base[:, 0])
        self.avail_mem = (self.capacity[:, 1] - self.base[:, 1])
        self.ten, self.one, self.twenty = t(10), t(1), t(20)
        self.lo, self.hi = t(0), t(18)

    def scores(self, usage, jc, ask, penalty, rows=None):
        """(masked score, fits) of placing ``ask`` on each node (or on
        ``rows`` only) given ``usage`` [n, 6] and the job's counts
        ``jc`` [n]; nodes that do not fit score NEG."""
        t = self.dtype.type
        sl = slice(None) if rows is None else rows
        util = self.base[sl] + usage[sl].astype(self.dtype) \
            + ask.astype(self.dtype)
        fits = (util <= self.capacity[sl]).all(axis=-1)
        total = np.power(self.ten, self.one - util[..., 0] / self.avail_cpu[sl]) \
            + np.power(self.ten, self.one - util[..., 1] / self.avail_mem[sl])
        score = np.clip(self.twenty - total, self.lo, self.hi)
        score = score - t(penalty) * jc[sl].astype(self.dtype)
        return np.where(fits, score.astype(np.float64), NEG), fits

    def rank(self, masked):
        """What a scheduler with this scorer orders the nodes by."""
        return masked


class WorstFirst(Scorer):
    """A fault planted in the reference put in the program's place: it
    scores every node as the reference does and takes the WORST feasible
    ones.  Only ``score_regret`` can catch it."""

    def rank(self, masked):
        return np.where(masked > NEG / 2, -masked, NEG)


def job_slots(job: dict) -> list:
    """Group-slots in job order: groups with the same ask share one
    slot, at the place of the first (the scheduler places identical
    groups as copies of one).  [(ask [6], [group names], copies)]."""
    slots, by_key = [], {}
    for g in job["groups"]:
        ask = group_ask(g)
        key = tuple(ask.tolist())
        if key not in by_key:
            by_key[key] = len(slots)
            slots.append([ask, [], 0])
        slot = slots[by_key[key]]
        slot[1].append(g["name"])
        slot[2] += int(g["count"])
    return [tuple(s) for s in slots]


def usage_at(allocs: dict, order: np.ndarray, upto: int,
             n: int) -> np.ndarray:
    """Committed usage [n, 6] of the first ``upto`` allocations in
    commit order (``order`` sorts ``allocs`` by create index)."""
    sel = order[:upto]
    used = np.zeros((n, len(DIMS)), dtype=np.float64)
    np.add.at(used, allocs["node"][sel], allocs["vec"][sel])
    return used


def slot_runs(pick_slot) -> list:
    """[(lo, hi)] of the runs of equal values in ``pick_slot``."""
    edges = [0] + [i for i in range(1, len(pick_slot))
                   if pick_slot[i] != pick_slot[i - 1]] + [len(pick_slot)]
    return list(zip(edges, edges[1:]))


def check_plan(fleet: dict, job: dict, allocs: dict, order: np.ndarray,
               sorted_index: np.ndarray, plan_rows: np.ndarray,
               before_rows: np.ndarray, register_index: int,
               scorer: Scorer, control: "Scorer | None" = None,
               max_candidates: int = 48) -> dict:
    """One committed plan of ``job`` (allocation rows ``plan_rows``, all
    with one create index) against the reference.

    Returns ``dscore`` (widest gap between the score the program
    recorded for a pick and the reference's score of that node at that
    step), ``regret`` (widest gap by which a pick scores below the
    reference's k-th best node at its step, k the copies the slot still
    wanted) and, with ``control``, the same two for that scorer put in
    the program's place on the same steps.
    """
    n = len(fleet["ids"])
    penalty = PENALTY[job.get("type", "service")]
    commit = int(allocs["create_index"][plan_rows[0]])
    job_rows_mask = np.zeros(len(allocs["node"]), dtype=bool)
    job_rows_mask[before_rows] = True

    # The plan's picks in slot order, then by the order the scheduler
    # names copies (allocation name index).
    slots = job_slots(job)
    slot_of_group = {name: s for s, (_a, names, _c) in enumerate(slots)
                     for name in names}
    pick_slot = np.asarray([slot_of_group[allocs["group"][r]]
                            for r in plan_rows])
    keep = np.argsort(pick_slot, kind="stable")
    rows, pick_slot = plan_rows[keep], pick_slot[keep]
    nodes = allocs["node"][rows]
    recorded = allocs["score"][rows]
    asks = np.stack([slots[s][0] for s in pick_slot])
    runs = slot_runs(pick_slot)
    wanted = [c for _a, _n, c in slots]
    for r in before_rows:
        wanted[slot_of_group[allocs["group"][r]]] -= 1

    # Candidate snapshots: committed prefixes from the newest before the
    # plan's commit back to the oldest the scheduler can have planned
    # on — the state at the job's registration or, for a retry, the
    # commit of the job's own previous plan.
    uniq = np.unique(sorted_index)
    uniq = uniq[uniq < commit]
    floor = register_index
    if len(before_rows):
        floor = max(floor, int(allocs["create_index"][before_rows].max()))
    cands = [int(i) for i in uniq[uniq >= floor][::-1]]
    older = uniq[uniq < floor]
    if floor == register_index:
        cands.append(int(older[-1]) if len(older) else 0)
    cands = cands[:max_candidates]

    def state(snap_index):
        upto = int(np.searchsorted(sorted_index, snap_index, side="right"))
        usage = usage_at(allocs, order, upto, n)
        jc = np.zeros(n, dtype=np.float64)
        mine = before_rows[allocs["create_index"][before_rows] <= snap_index]
        np.add.at(jc, allocs["node"][mine], 1.0)
        return usage, jc

    def pick_scores(usage, jc, sc):
        """Score of each pick at its own step: a slot's picks are all
        scored before any of them is applied; the next slot sees them."""
        usage, jc = usage.copy(), jc.copy()
        out = np.empty(len(rows), dtype=np.float64)
        for lo, hi in runs:
            out[lo:hi], _f = sc.scores(usage, jc, asks[lo], penalty,
                                       rows=nodes[lo:hi])
            np.add.at(usage, nodes[lo:hi], asks[lo:hi])
            np.add.at(jc, nodes[lo:hi], 1.0)
        return out

    best = None
    for snap in cands:
        usage, jc = state(snap)
        gap = float(np.abs(pick_scores(usage, jc, scorer) - recorded).max())
        # Newest first; on a tie the OLDER prefix wins: the picks cannot
        # tell the two apart, and the older one excludes more (below).
        if best is None or gap <= best[0]:
            best = (gap, snap, usage, jc)
    dscore, snap, usage, jc = best

    # Nodes that other jobs' plans touched between the snapshot and this
    # commit may have carried picks of this plan that the applier
    # refused; their usage as the scheduler saw it is not in the answer,
    # so they do not compete (this plan's own picks always do).
    ci = allocs["create_index"]
    between = (ci > snap) & (ci <= commit)
    between[plan_rows] = False
    between[before_rows] = False
    touched = np.zeros(n, dtype=bool)
    touched[allocs["node"][between & (allocs["node"] >= 0)]] = True

    out = {"dscore": dscore, "snapshot": snap, "commit": commit,
           "candidates": len(cands), "picks": int(len(rows)),
           "excluded_nodes": int(touched.sum())}
    regret = 0.0
    c_dscore = c_regret = 0.0
    c_unfit = 0
    usage, jc = usage.copy(), jc.copy()
    for lo, hi in runs:
        s = int(pick_slot[lo])
        ask, picks = asks[lo], nodes[lo:hi]
        masked, _fits = scorer.scores(usage, jc, ask, penalty)
        compete = ~touched
        compete[picks] = True
        field_scores = np.where(compete, masked, NEG)
        k = max(1, min(wanted[s], int((field_scores > NEG / 2).sum())))
        kth = np.partition(field_scores, n - k)[n - k]
        regret = max(regret, float(kth - masked[picks].min()))
        if control is not None:
            low, _f = control.scores(usage, jc, ask, penalty)
            low = np.where(compete, low, NEG)
            m = hi - lo
            c_picks = np.argpartition(control.rank(low), n - m)[n - m:]
            c_picks = c_picks[low[c_picks] > NEG / 2]
            # A node the control admits and the reference does not is
            # counted, not scored: the applier would refuse it.
            c_unfit += int((masked[c_picks] <= NEG / 2).sum())
            c_picks = c_picks[masked[c_picks] > NEG / 2]
            if len(c_picks):
                c_dscore = max(c_dscore, float(np.abs(
                    low[c_picks] - masked[c_picks]).max()))
                c_regret = max(c_regret,
                               float(kth - masked[c_picks].min()))
        np.add.at(usage, picks, asks[lo:hi])
        np.add.at(jc, picks, 1.0)
        wanted[s] -= hi - lo
    out["regret"] = max(regret, 0.0)
    if control is not None:
        out["control_dscore"] = c_dscore
        out["control_regret"] = max(c_regret, 0.0)
        out["control_unfit"] = c_unfit
    return out
