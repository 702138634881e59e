"""The plain reference: what a placement has to satisfy, in float64 numpy.

Imports nothing of the program and takes nothing the program made
except its ANSWERS (the committed allocations as read back).  Fleet and
jobs come from the configuration file, the traffic file and ``--seed``.

Semantics (Nomad 0.1.2, ``nomad/structs/funcs.go`` AllocsFit/ScoreFit,
``scheduler/feasible.go`` and ``scheduler/rank.go`` job anti-affinity,
as the project states them):

- a node holds a set of allocations iff, in every dimension,
  reserved + sum(asks) <= capacity, bandwidth included, and no port is
  handed out twice;
- a node is feasible for a group iff it is in one of the job's
  datacenters, its ``driver.exec`` attribute parses true, and it meets
  every hard constraint of the job and of the group (``feasible``);
  a group with ``distinct_hosts`` (or under a job or group constraint of
  that operand) also excludes every node on
  which the job holds an allocation before the step, as the program
  states it (``ops/binpack.py``: no second same-job allocation);
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

Constraints follow ``feasible.go`` (resolveConstraintTarget +
checkConstraint): targets ``$node.id``, ``$node.datacenter``,
``$node.name``, ``$attr.<key>``, ``$meta.<key>`` or a literal (any other
``$`` target fails the node); operands ``=`` ``==`` ``is``, ``!=``
``not``, the lexical ``<`` ``<=`` ``>`` ``>=`` (strings only),
``version`` and ``regexp``; an unknown operand fails.  Where this file
departs from Go:

- ``regexp`` is Python ``re.search``, not RE2: patterns that only one of
  the two accepts (look-arounds, backreferences, possessive forms) or that
  match differently are not used by any file of this directory; a
  pattern ``re`` cannot compile fails the node, as RE2's error does;
- ``version`` parses go-version's plain versions (``v1.2.3``; clauses
  ``=`` ``!=`` ``>`` ``<`` ``>=`` ``<=`` ``~>``, comma-joined) and
  raises on a pre-release or build suffix (``1.2.3-beta``,
  ``1.2.3+meta``), which no file of this directory carries;
- the driver attribute parses as Go's ``strconv.ParseBool``
  (``1 t T TRUE true True``).
"""
from __future__ import annotations

import random
import re
import uuid

import numpy as np

DIMS = ("cpu", "memory_mb", "disk_mb", "iops", "mbits", "port_slots")
PORT_SLOTS = 40000.0          # dynamic ports 20000..59999
MIN_DYNAMIC_PORT, MAX_DYNAMIC_PORT = 20000, 60000
PENALTY = {"service": 10.0, "batch": 5.0}
NEG = -1.0e30


def seeded_uuid(rng: random.Random) -> str:
    return str(uuid.UUID(int=rng.getrandbits(128), version=4))


# ``mock.go``'s node and job: what a configuration without node classes
# and a job spec without constraints get.
MOCK_ATTRIBUTES = {"kernel.name": "linux", "arch": "x86",
                   "version": "0.1.0", "driver.exec": "1"}
MOCK_CLASS = {"datacenter": "dc1", "node_class": "linux-medium"}
MOCK_JOB_CONSTRAINTS = [{"l_target": "$attr.kernel.name", "operand": "=",
                         "r_target": "linux"}]
DISTINCT_HOSTS = "distinct_hosts"
GO_TRUE = ("1", "t", "T", "TRUE", "true", "True")


def node_classes(config: dict) -> list:
    """The configuration's machine classes, each with every key filled:
    ``node_classes`` as stated (``attributes`` added to, or overriding,
    mock.go's four; ``datacenter`` dc1 and ``node_class`` the class's
    name unless stated; ``meta`` empty), or the one class of ``node``
    that a configuration without the key has always meant."""
    if "node_classes" not in config:
        return [dict(MOCK_CLASS, name="default", share=1,
                     node=config["node"], attributes=dict(MOCK_ATTRIBUTES),
                     meta={})]
    out = []
    for c in config["node_classes"]:
        attrs = dict(MOCK_ATTRIBUTES)
        attrs.update({k: str(v) for k, v in c.get("attributes", {}).items()})
        out.append({"name": c["name"], "share": int(c["share"]),
                    "node": c["node"], "attributes": attrs,
                    "meta": {k: str(v) for k, v in c.get("meta", {}).items()},
                    "datacenter": c.get("datacenter", "dc1"),
                    "node_class": c.get("node_class", c["name"])})
    if any(c["share"] < 1 for c in out):
        raise ValueError("a node class's share is a whole weight >= 1")
    return out


def class_rows(shares: list, seed: int, n_nodes: int) -> np.ndarray:
    """Class index of each fleet row: a largest-remainder interleave
    (each row goes to the class furthest below its share of the rows so
    far), so every prefix of the fleet holds each class at its share
    within one row.  The sequence repeats every ``sum(shares)`` rows; the
    seed (its own stream, ``{seed}:classes``) only orders the classes
    for ties."""
    if len(shares) == 1:
        return np.zeros(n_nodes, dtype=np.int64)
    order = list(range(len(shares)))
    random.Random(f"{seed}:classes").shuffle(order)
    tie = {k: -i for i, k in enumerate(order)}
    total = sum(shares)
    owed = [0] * len(shares)
    period = []
    for _ in range(total):
        owed = [o + w for o, w in zip(owed, shares)]
        k = max(range(len(shares)), key=lambda c: (owed[c], tie[c]))
        owed[k] -= total
        period.append(k)
    return np.resize(np.asarray(period, dtype=np.int64), n_nodes)


def make_fleet(config: dict, seed: int, n_nodes: int) -> dict:
    """ids, class of each row and capacity/reserved [n, 6] of
    ``n_nodes`` nodes of the configuration's machine classes; ids are
    drawn from the seed (first, and alone on their stream: a
    configuration of one class gets the fleet it always got)."""
    rng = random.Random(f"{seed}:fleet")
    ids = [seeded_uuid(rng) for _ in range(n_nodes)]
    classes = node_classes(config)
    cap, rsv = [], []
    for c in classes:
        shape, res = c["node"], c["node"]["reserved"]
        cap.append([shape["cpu"], shape["memory_mb"], shape["disk_mb"],
                    shape["iops"], shape["mbits"], PORT_SLOTS])
        rsv.append([res.get("cpu", 0), res.get("memory_mb", 0),
                    res.get("disk_mb", 0), res.get("iops", 0),
                    res.get("mbits", 0), float(len(res.get("ports", [])))])
    class_of = class_rows([c["share"] for c in classes], seed, n_nodes)
    return {
        "ids": ids,
        "class_of": class_of,
        "classes": classes,
        "capacity": np.asarray(cap, dtype=np.float64)[class_of],
        "reserved": np.asarray(rsv, dtype=np.float64)[class_of],
        "class_reserved_ports": [list(c["node"]["reserved"].get("ports", []))
                                 for c in classes],
        "masks": {},
    }


def group_ask(group: dict) -> np.ndarray:
    """[6] ask of one copy of a plain group spec."""
    ports = len(group.get("dynamic_ports", ()))
    return np.asarray([group["cpu"], group["memory_mb"],
                       group.get("disk_mb", 0), group.get("iops", 0),
                       group.get("mbits", 0), ports], dtype=np.float64)


# ---------------------------------------------------------------------------
# resident work: what the fleet already runs when the window opens
# ---------------------------------------------------------------------------

RESIDENT_GROUP = "main"
ASK_KEYS = ("cpu", "memory_mb", "disk_mb", "iops", "mbits", "dynamic_ports")


def resident_jobs(config: dict, fleet: dict) -> list:
    """The configuration's ``resident`` jobs as plain job specs of one
    group (``main``) each, in file order: ``id``, ``name``, ``type``,
    ``priority``, ``datacenters`` (those of its classes) and the group
    with its ask and ``count`` unset; plus ``classes`` (class indexes it
    may use), ``node_share`` and ``per_node``.  Empty without the key."""
    names = [c["name"] for c in fleet["classes"]]
    out = []
    for r in config.get("resident", ()):
        if r["type"] not in PENALTY:
            raise ValueError(f"resident {r['id']}: type {r['type']!r}")
        unknown = set(r.get("classes", ())) - set(names)
        if unknown:
            raise ValueError(f"resident {r['id']}: no class {unknown}")
        classes = [names.index(c) for c in r.get("classes", names)]
        share, per_node = float(r["node_share"]), int(r["per_node"])
        if not 0.0 < share <= 1.0 or per_node < 1:
            raise ValueError(f"resident {r['id']}: node_share in (0, 1] "
                             "and per_node >= 1")
        group = {k: r[k] for k in ASK_KEYS if k in r}
        group["name"] = RESIDENT_GROUP
        out.append({
            "id": r["id"], "name": r["id"], "type": r["type"],
            "priority": int(r["priority"]),
            "datacenters": sorted({fleet["classes"][k]["datacenter"]
                                   for k in classes}),
            "groups": [group], "classes": classes,
            "node_share": share, "per_node": per_node})
    return out


def resident_layout(config: dict, fleet: dict, seed: int) -> dict:
    """Every resident allocation of the configuration on ``fleet``, as
    columns: ``id``, ``job``, ``group``, ``name`` (``<job>.<group>[i]``),
    ``row`` [A], ``vec`` [A, 6] (its ask), ``ports`` (handed out on each
    node from 20000 upward, in file order, skipping the node's reserved
    ports); and ``jobs``, each spec with its group's ``count`` set to its
    allocations.

    A job takes ``round(node_share * rows)`` of the rows of its classes
    (all of them, or a sorted sample drawn from ``{seed}:resident``, a
    stream no other draw uses) and ``per_node`` copies on each.  A
    layout that would overfill a node in any dimension, or hand out a
    port past the dynamic range, is refused: ``ValueError``.  Without
    ``resident`` the layout is empty and nothing is drawn; a job whose
    share of a (rehearsal's) fleet rounds to no row is left out."""
    rng = random.Random(f"{seed}:resident")
    out = {"id": [], "job": [], "group": [], "name": [], "row": [],
           "vec": [], "ports": [], "jobs": []}
    n = len(fleet["ids"])
    class_of = fleet["class_of"]
    used = fleet["reserved"].copy()
    cursor = np.full(n, MIN_DYNAMIC_PORT, dtype=np.int64)
    reserved_ports = [set(p) for p in fleet["class_reserved_ports"]]
    for spec in resident_jobs(config, fleet):
        group = spec["groups"][0]
        ask = group_ask(group)
        candidates = np.flatnonzero(np.isin(class_of, spec["classes"]))
        take = int(round(spec["node_share"] * len(candidates)))
        if not take:
            continue
        if take < len(candidates):
            candidates = np.sort(np.asarray(
                rng.sample(candidates.tolist(), take), dtype=np.int64))
        rows = np.repeat(candidates, spec["per_node"])
        n_ports = len(group.get("dynamic_ports", ()))
        for i, row in enumerate(rows.tolist()):
            skip = reserved_ports[class_of[row]]
            ports = []
            p = int(cursor[row])
            while len(ports) < n_ports:
                if p not in skip:
                    ports.append(p)
                p += 1
            cursor[row] = p
            out["ports"].append(ports)
            out["id"].append(seeded_uuid(rng))
            out["name"].append(f"{spec['id']}.{group['name']}[{i}]")
        np.add.at(used, rows, ask)
        out["row"].append(rows)
        out["vec"].append(np.broadcast_to(ask, (len(rows), len(DIMS))))
        out["job"].extend([spec["id"]] * len(rows))
        out["group"].extend([group["name"]] * len(rows))
        group["count"] = len(rows)
        out["jobs"].append(spec)
        over = np.flatnonzero((used > fleet["capacity"]).any(axis=1)
                              | (cursor > MAX_DYNAMIC_PORT))
        if len(over):
            raise ValueError(
                f"resident {spec['id']} overfills node rows "
                f"{over[:5].tolist()} ({len(over)} in all): "
                f"{used[over[0]].tolist()} of "
                f"{fleet['capacity'][over[0]].tolist()}")
    out["row"] = np.concatenate(out["row"]) if out["jobs"] else \
        np.zeros(0, dtype=np.int64)
    out["vec"] = np.concatenate(out["vec"]) if out["jobs"] else \
        np.zeros((0, len(DIMS)), dtype=np.float64)
    return out


# ---------------------------------------------------------------------------
# feasibility: datacenters, the driver, hard constraints, distinct_hosts
# ---------------------------------------------------------------------------

PER_ROW_TARGETS = ("$node.id", "$node.name")
_VERSION_RE = re.compile(r"v?([0-9]+(?:\.[0-9]+)*)")
_CLAUSE_RE = re.compile(r"\s*(=|!=|>|<|>=|<=|~>)?\s*(v?[0-9][^\s,]*)\s*")


def parse_version(text: str):
    """The segments of a go-version string, as written, or None where
    go-version refuses it.  Digits followed by anything else (a
    pre-release or build suffix) raise: no file of this directory
    carries one (add semver precedence with the configuration that
    does)."""
    m = _VERSION_RE.fullmatch(text)
    if m is None:
        if _VERSION_RE.match(text):
            raise ValueError(f"version {text!r} is not plain segments: a "
                             "pre-release or build suffix is not supported")
        return None
    return [int(x) for x in m.group(1).split(".")]


def _padded(segments: list, width: int) -> list:
    return segments + [0] * (width - len(segments))


def version_meets(version: str, spec: str) -> bool:
    """go-version's ``NewConstraint(spec).Check(NewVersion(version))``
    on plain segments (the shorter padded with noughts); either string
    unparseable fails."""
    v = parse_version(version)
    if v is None:
        return False
    for clause in spec.split(","):
        m = _CLAUSE_RE.fullmatch(clause)
        c = parse_version(m.group(2)) if m else None
        if c is None:
            return False
        width = max(len(v), len(c), 3)
        pv, pc = _padded(v, width), _padded(c, width)
        op, order = m.group(1) or "=", (pv > pc) - (pv < pc)
        if op == "~>":
            # At or above c, and equal to it in every segment written
            # but the last.
            ok = order >= 0 and pv[:len(c) - 1] == pc[:len(c) - 1]
        else:
            ok = {"=": order == 0, "!=": order != 0, ">": order > 0,
                  "<": order < 0, ">=": order >= 0, "<=": order <= 0}[op]
        if not ok:
            return False
    return True


def check_values(operand: str, left: str, right: str) -> bool:
    """``feasible.go`` checkConstraint on two resolved strings."""
    if operand in ("=", "==", "is"):
        return left == right
    if operand in ("!=", "not"):
        return left != right
    if operand in ("<", "<=", ">", ">="):
        return {"<": left < right, "<=": left <= right,
                ">": left > right, ">=": left >= right}[operand]
    if operand == "version":
        return version_meets(left, right)
    if operand == "regexp":
        try:
            return re.search(right, left) is not None
        except re.error:
            return False
    return False


def resolve(target: str, cls: dict, fleet: dict, row: int):
    """``feasible.go`` resolveConstraintTarget: the string a target names
    on a node of class ``cls`` (at fleet row ``row`` for the two per-row
    targets), or None where the node has no such value."""
    if not target.startswith("$"):
        return target
    if target == "$node.id":
        return fleet["ids"][row]
    if target == "$node.name":
        return f"node-{row}"
    if target == "$node.datacenter":
        return cls["datacenter"]
    for prefix, table in (("$attr.", cls["attributes"]),
                          ("$meta.", cls["meta"])):
        if target.startswith(prefix):
            return table.get(target[len(prefix):])
    return None


def meets(fleet: dict, triple: tuple, cls: dict, row: int = -1) -> bool:
    left_t, operand, right_t = triple
    left = resolve(left_t, cls, fleet, row)
    right = resolve(right_t, cls, fleet, row)
    return left is not None and right is not None and \
        check_values(operand, left, right)


def group_constraints(job: dict, group: dict) -> tuple:
    """(sorted (l_target, operand, r_target) of the constraints of the
    job and the group, all hard, ``distinct_hosts`` left out; whether
    ``distinct_hosts`` applies to the group).  A job spec without a
    ``constraints`` key carries mock.go's one."""
    cons = job.get("constraints", MOCK_JOB_CONSTRAINTS) \
        + group.get("constraints", [])
    distinct = bool(group.get("distinct_hosts")) or \
        any(c["operand"] == DISTINCT_HOSTS for c in cons)
    triples = tuple(sorted(
        (c.get("l_target", ""), c["operand"], c.get("r_target", ""))
        for c in cons if c["operand"] != DISTINCT_HOSTS))
    return triples, distinct


def job_datacenters(job: dict) -> tuple:
    """The job's datacenters, sorted; dc1 unless the spec states them."""
    return tuple(sorted(job.get("datacenters", [MOCK_CLASS["datacenter"]])))


def feasible_mask(fleet: dict, datacenters: tuple,
                  triples: tuple) -> np.ndarray:
    """bool[n]: the nodes in one of ``datacenters`` whose ``driver.exec``
    parses true and that meet every constraint of ``triples``.  Judged
    once a class and indexed by ``class_of``, but for the per-row
    targets; kept in the fleet's ``masks`` (read-only to callers)."""
    key = (datacenters, triples)
    mask = fleet["masks"].get(key)
    if mask is not None:
        return mask
    classes = fleet["classes"]
    per_class = np.asarray([
        c["datacenter"] in datacenters
        and c["attributes"].get("driver.exec") in GO_TRUE
        and all(meets(fleet, t, c) for t in triples
                if t[0] not in PER_ROW_TARGETS
                and t[2] not in PER_ROW_TARGETS)
        for c in classes], dtype=bool)
    mask = per_class[fleet["class_of"]]
    for t in triples:
        if t[0] in PER_ROW_TARGETS or t[2] in PER_ROW_TARGETS:
            rows = np.nonzero(mask)[0]
            mask[rows] = [meets(fleet, t, classes[fleet["class_of"][r]], r)
                          for r in rows]
    fleet["masks"][key] = mask
    return mask


def feasible(fleet: dict, job: dict, group: dict) -> np.ndarray:
    """bool[n]: the nodes a copy of ``group`` of ``job`` may go to before
    ``distinct_hosts``, which depends on the job's allocations."""
    return feasible_mask(fleet, job_datacenters(job),
                         group_constraints(job, group)[0])


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
        bad = (pp < MIN_DYNAMIC_PORT) | (pp >= MAX_DYNAMIC_PORT)
        pair_class = fleet["class_of"][pn]
        for k, ports in enumerate(fleet["class_reserved_ports"]):
            if ports:
                bad |= (pair_class == k) & np.isin(pp, ports)
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

    def scores(self, usage, jc, ask, penalty, rows=None, feasible=None,
               distinct=False):
        """(masked score, fits) of placing ``ask`` on each node (or on
        ``rows`` only) given ``usage`` [n, 6] and the job's counts
        ``jc`` [n]; nodes that do not fit, that ``feasible`` [n] leaves
        out or, with ``distinct``, that hold a copy of the job score
        NEG."""
        t = self.dtype.type
        sl = slice(None) if rows is None else rows
        util = self.base[sl] + usage[sl].astype(self.dtype) \
            + ask.astype(self.dtype)
        fits = (util <= self.capacity[sl]).all(axis=-1)
        total = np.power(self.ten, self.one - util[..., 0] / self.avail_cpu[sl]) \
            + np.power(self.ten, self.one - util[..., 1] / self.avail_mem[sl])
        score = np.clip(self.twenty - total, self.lo, self.hi)
        score = score - t(penalty) * jc[sl].astype(self.dtype)
        ok = fits
        if feasible is not None:
            ok = ok & feasible[sl]
        if distinct:
            ok = ok & (jc[sl] == 0)
        return np.where(ok, score.astype(np.float64), NEG), fits

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
    """Group-slots in job order: groups with the same ask, the same hard
    constraints and the same ``distinct_hosts`` share one slot, at the
    place of the first (the scheduler places them as copies of one: the
    program's dedupe key, ``_derive_sem`` / ``group_mask_key``).
    [(ask [6], [group names], copies, constraint triples, distinct)]."""
    slots, by_key = [], {}
    for g in job["groups"]:
        ask = group_ask(g)
        triples, distinct = group_constraints(job, g)
        key = (tuple(ask.tolist()), triples, distinct)
        if key not in by_key:
            by_key[key] = len(slots)
            slots.append([ask, [], 0, triples, distinct])
        slot = slots[by_key[key]]
        slot[1].append(g["name"])
        slot[2] += int(g["count"])
    return [tuple(s) for s in slots]


def slot_masks(fleet: dict, job: dict, slots: list) -> list:
    """The feasibility mask [n] of each slot of ``job_slots(job)``."""
    dcs = job_datacenters(job)
    return [feasible_mask(fleet, dcs, slot[3]) for slot in slots]


def placement_violations(fleet: dict, specs: dict, allocs: dict,
                         by_job: dict) -> tuple:
    """(live allocations on a node their group's constraints or their
    job's datacenters exclude, live allocations that break
    ``distinct_hosts``, a few example rows) over the jobs of ``specs``
    {job id: spec}.  ``allocs``: live columns; ``by_job``: job id -> its
    rows.  A distinct group's allocation breaks it where another
    allocation of its job on that node was placed no later: in an
    earlier commit, or in the same plan at an earlier or the same slot
    (a slot's picks are placed slot by slot, in job order).  Jobs whose
    every slot admits the whole fleet and none distinct are skipped."""
    out_c = out_d = 0
    examples = []
    for jid, spec in specs.items():
        rows = by_job.get(jid)
        if not rows:
            continue
        slots = job_slots(spec)
        masks = slot_masks(fleet, spec, slots)
        dist = np.asarray([slot[4] for slot in slots], dtype=bool)
        if not dist.any() and all(m.all() for m in masks):
            continue
        slot_of = {name: s for s, slot in enumerate(slots)
                   for name in slot[1]}
        rows = np.asarray(rows, dtype=np.int64)
        node = allocs["node"][rows]
        slot = np.asarray([slot_of.get(allocs["group"][r], -1)
                           for r in rows], dtype=np.int64)
        keep = (node >= 0) & (slot >= 0)
        rows, node, slot = rows[keep], node[keep], slot[keep]
        ok = np.asarray([masks[s][i] for s, i in zip(slot, node)],
                        dtype=bool)
        out_c += int((~ok).sum())
        examples.extend(int(r) for r in rows[~ok][:3])
        if dist.any():
            order = np.lexsort((allocs["create_index"][rows] * len(slots)
                                + slot, node))
            later = np.r_[False, node[order][1:] == node[order][:-1]]
            broken = later & dist[slot[order]]
            out_d += int(broken.sum())
            examples.extend(int(r) for r in rows[order][broken][:3])
    return out_c, out_d, examples[:5]


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
    masks = slot_masks(fleet, job, slots)
    slot_of_group = {name: s for s, slot in enumerate(slots)
                     for name in slot[1]}
    pick_slot = np.asarray([slot_of_group[allocs["group"][r]]
                            for r in plan_rows])
    keep = np.argsort(pick_slot, kind="stable")
    rows, pick_slot = plan_rows[keep], pick_slot[keep]
    nodes = allocs["node"][rows]
    recorded = allocs["score"][rows]
    asks = np.stack([slots[s][0] for s in pick_slot])
    runs = slot_runs(pick_slot)
    wanted = [slot[2] for slot in slots]
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
        scored before any of them is applied; the next slot sees them.
        A distinct slot's second pick of one node scores NEG."""
        usage, jc = usage.copy(), jc.copy()
        out = np.empty(len(rows), dtype=np.float64)
        for lo, hi in runs:
            s = int(pick_slot[lo])
            out[lo:hi], _f = sc.scores(usage, jc, asks[lo], penalty,
                                       rows=nodes[lo:hi],
                                       feasible=masks[s],
                                       distinct=slots[s][4])
            if slots[s][4]:
                _u, first = np.unique(nodes[lo:hi], return_index=True)
                again = np.ones(hi - lo, dtype=bool)
                again[first] = False
                out[lo:hi][again] = NEG
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
        masked, _fits = scorer.scores(usage, jc, ask, penalty,
                                      feasible=masks[s],
                                      distinct=slots[s][4])
        compete = ~touched
        compete[picks] = True
        field_scores = np.where(compete, masked, NEG)
        k = max(1, min(wanted[s], int((field_scores > NEG / 2).sum())))
        kth = np.partition(field_scores, n - k)[n - k]
        regret = max(regret, float(kth - masked[picks].min()))
        if control is not None:
            low, _f = control.scores(usage, jc, ask, penalty,
                                     feasible=masks[s],
                                     distinct=slots[s][4])
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
