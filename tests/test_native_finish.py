"""Parity: native (C) bulk finish vs the pure-Python finish loop.

With the same uuid stream and port-LCG seed the two paths must produce
BIT-IDENTICAL plans — same nodes, ports, offers, metrics (modulo the
wall-clock allocation_time).  See native/port_alloc.cpp bulk_finish.
"""
from __future__ import annotations

import random

import numpy as np
import pytest

import nomad_tpu.mock as mock
import nomad_tpu.scheduler.jax_binpack as jb
from nomad_tpu.models.fleet import UsageMirror, fleet_cache, mirror_for
from nomad_tpu.obs import trace as trace_mod
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.batch import BatchEvalRunner
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.structs import (
    EVAL_TRIGGER_JOB_REGISTER,
    Allocation,
    Evaluation,
    NetworkResource,
    Resources,
    Task,
    TaskGroup,
    generate_uuid,
)
from nomad_tpu.structs.model import MAX_DYNAMIC_PORT, MIN_DYNAMIC_PORT

pytestmark = pytest.mark.skipif(
    jb._native_bulk() is None, reason="native extension unavailable")


def make_eval(job):
    return Evaluation(id=f"ev-{job.id}", priority=job.priority,
                      type="service",
                      triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                      job_id=job.id)


def _job(n_groups=6, count=2, with_failures=False):
    job = mock.job()
    groups = []
    for g in range(n_groups):
        cpu = 100_000 if (with_failures and g % 3 == 0) else 100
        tg = TaskGroup(
            name=f"tg-{g}", count=count,
            tasks=[
                Task(name="web", driver="exec",
                     resources=Resources(
                         cpu=cpu, memory_mb=64,
                         networks=[NetworkResource(
                             mbits=5, dynamic_ports=["http", "admin"])])),
                Task(name="sidecar", driver="exec",
                     resources=Resources(cpu=50, memory_mb=32)),
            ])
        groups.append(tg)
    job.task_groups = groups
    return job


def _deterministic(monkeypatch):
    counter = {"n": 0}

    def fake_uuids(n):
        base = counter["n"]
        counter["n"] += n
        return [f"u-{base + i:08d}" for i in range(n)]

    monkeypatch.setattr(jb, "generate_uuids", fake_uuids)
    monkeypatch.setattr(jb, "_randrange", lambda n: 987654321 % n)


def _normalize(plan):
    out = {}
    for node_id, allocs in plan.node_allocation.items():
        rows = []
        for a in allocs:
            d = a.to_dict()
            d["metrics"]["allocation_time"] = 0.0
            rows.append(d)
        out[node_id] = rows
    failed = []
    for a in plan.failed_allocs:
        d = a.to_dict()
        d["metrics"]["allocation_time"] = 0.0
        failed.append(d)
    return out, failed


def _run(monkeypatch, native: bool, nodes, jobs):
    _deterministic(monkeypatch)
    if not native:
        monkeypatch.setattr(jb, "_native_bulk", lambda: None)
    h = Harness()
    for n in nodes:
        h.state.upsert_node(h.next_index(), n)
    plans = []
    for job in jobs:
        h.state.upsert_job(h.next_index(), job)
        h.process("jax-binpack", make_eval(job))
        plans.append(_normalize(h.plans[-1]))
    return plans


def _cluster(n):
    proto = Harness()
    nodes = []
    for i in range(n):
        nodes.append(mock.node(i))
    del proto
    return nodes


def test_native_finish_parity_basic(monkeypatch):
    nodes = _cluster(16)
    jobs = [_job(n_groups=6, count=2)]
    with monkeypatch.context() as m:
        py = _run(m, False, nodes, [j.copy() for j in jobs])
    with monkeypatch.context() as m:
        nat = _run(m, True, nodes, [j.copy() for j in jobs])
    assert py == nat
    placed, failed = nat[0]
    assert sum(len(v) for v in placed.values()) == 12 and not failed


def test_native_finish_parity_with_failures_and_coalescing(monkeypatch):
    nodes = _cluster(8)
    jobs = [_job(n_groups=6, count=3, with_failures=True)]
    with monkeypatch.context() as m:
        py = _run(m, False, nodes, [j.copy() for j in jobs])
    with monkeypatch.context() as m:
        nat = _run(m, True, nodes, [j.copy() for j in jobs])
    assert py == nat
    _placed, failed = nat[0]
    assert failed  # unsatisfiable groups failed identically
    assert any(f["metrics"]["coalesced_failures"] > 0 for f in failed)


def test_native_finish_parity_busy_nodes(monkeypatch):
    """Second job's eval sees the first job's allocs on the nodes: the C
    path must walk proposed allocs for port/bandwidth state."""
    nodes = _cluster(6)
    jobs = [_job(n_groups=3, count=2), _job(n_groups=4, count=2)]
    with monkeypatch.context() as m:
        py = _run(m, False, nodes, [j.copy() for j in jobs])
    with monkeypatch.context() as m:
        nat = _run(m, True, nodes, [j.copy() for j in jobs])
    assert py == nat
    # Ports must be unique per node across BOTH jobs' offers.
    seen: dict = {}
    for placed, _f in nat:
        for node_id, allocs in placed.items():
            for a in allocs:
                for tr in a["task_resources"].values():
                    for net in tr["networks"]:
                        for port in net["reserved_ports"]:
                            key = (node_id, port)
                            assert key not in seen, key
                            seen[key] = True


def test_native_finish_bails_to_python_on_bandwidth_overflow(monkeypatch):
    """A node whose bandwidth fills mid-eval forces the divergence
    fallback; C must hand over cleanly and the combined plan still
    respects the bandwidth bound."""
    nodes = _cluster(2)
    job = mock.job()
    job.task_groups = [TaskGroup(
        name=f"tg-{g}", count=1,
        tasks=[Task(name="t", driver="exec",
                    resources=Resources(
                        cpu=10, memory_mb=8,
                        networks=[NetworkResource(
                            mbits=400, dynamic_ports=["p"])]))])
        for g in range(8)]
    with monkeypatch.context() as m:
        py = _run(m, False, nodes, [job.copy()])
    with monkeypatch.context() as m:
        nat = _run(m, True, nodes, [job.copy()])
    assert py == nat
    placed, failed = nat[0]
    per_node_bw: dict = {}
    for node_id, allocs in placed.items():
        for a in allocs:
            for tr in a["task_resources"].values():
                for net in tr["networks"]:
                    per_node_bw[node_id] = \
                        per_node_bw.get(node_id, 0) + net["mbits"]
    # mock nodes advertise 1000 mbits: never oversubscribed.
    assert all(bw <= 1000 for bw in per_node_bw.values())
    assert sum(len(v) for v in placed.values()) + len(failed) >= 5


# ---------------------------------------------------------------------------
# The finish seeds a node's ports and bandwidth from the usage mirror's
# occupancy (UsageMirror.net_occupancy) instead of walking the node's
# allocations; the walk stays as the exact fallback.  Every case runs the
# native loop and its Python twin.
# ---------------------------------------------------------------------------
TWINS = pytest.mark.parametrize("native", [True, False],
                                ids=["native", "python"])
# The port the finish's LCG draws first under _deterministic's seed.
FIRST_DRAW = MIN_DYNAMIC_PORT + \
    (((987654321 % (1 << 30)) * 1103515245 + 12345) & 0x3FFFFFFF) \
    % (MAX_DYNAMIC_PORT - MIN_DYNAMIC_PORT)


def _held(node, ports, mbits=1, offers=None):
    """An object allocation already on ``node`` holding ``ports`` and
    ``mbits`` on the node's own network (``offers`` overrides: one
    (ip, device, ports, mbits) per task).  Its ``resources`` state no
    network, so the placement kernel does not see the bandwidth: only
    the finish's exact accounting does."""
    if offers is None:
        offers = [(node.reserved.networks[0].ip, "eth0", ports, mbits)]
    return Allocation(
        id=generate_uuid(), node_id=node.id, job_id="held",
        resources=Resources(cpu=1, memory_mb=1),
        task_resources={
            f"t{i}": Resources(cpu=1, memory_mb=1, networks=[
                NetworkResource(device=dev, ip=ip, mbits=mb,
                                reserved_ports=list(pp))])
            for i, (ip, dev, pp, mb) in enumerate(offers)},
        desired_status="run", client_status="pending")


def _harness(monkeypatch, nodes, held=(), first_job=None):
    """A Harness whose store holds ``first_job``'s allocations, placed
    by the native columnar finish where there is one (slab-backed), and
    the object allocations ``held``."""
    _deterministic(monkeypatch)
    h = Harness()
    for n in nodes:
        h.state.upsert_node(h.next_index(), n)
    if first_job is not None:
        h.state.upsert_job(h.next_index(), first_job)
        with monkeypatch.context() as m:
            # Another port stream than the job under test will draw, so
            # only the held allocations sit on its first draw.
            m.setattr(jb, "_randrange", lambda n: 24680)
            h.process("jax-binpack", make_eval(first_job))
    if held:
        h.state.upsert_allocs(h.next_index(), list(held))
    return h


def _finish(monkeypatch, h, job, native=True, seeded=True, state=None):
    """One eval of ``job`` over ``h``; returns what the finish did: the
    normalized plan, the native prefix's n_done, the scheduler's
    node-init counters and the node ids ctx.proposed_allocs was asked
    for."""
    if not native:
        monkeypatch.setattr(jb, "_native_bulk", lambda: None)
    if not seeded:
        monkeypatch.setattr(UsageMirror, "net_occupancy",
                            lambda self, state, nis: {})
    seen = {"walked": [], "n_done": [], "sched": None}
    walk = EvalContext.proposed_allocs

    def counting_walk(self, node_id):
        seen["walked"].append(node_id)
        return walk(self, node_id)

    consume = jb.JaxBinPackScheduler._finish_consume_native

    def recording_consume(self, fs, result):
        seen["n_done"].append(result[0])
        return consume(self, fs, result)

    tail = jb.JaxBinPackScheduler._finish_python_tail

    def recording_tail(self, fs):
        seen["sched"] = self
        return tail(self, fs)

    monkeypatch.setattr(EvalContext, "proposed_allocs", counting_walk)
    monkeypatch.setattr(jb.JaxBinPackScheduler, "_finish_consume_native",
                        recording_consume)
    monkeypatch.setattr(jb.JaxBinPackScheduler, "_finish_python_tail",
                        recording_tail)
    h.state.upsert_job(h.next_index(), job)
    sched = jb.JaxBinPackScheduler(state or h.state.snapshot(), h,
                                   batch=False)
    # The sequential fallback a bandwidth divergence ends in shuffles
    # its nodes: same shuffle in the runs that are compared.
    rng = random.getstate()
    random.seed(27)
    try:
        sched.process(make_eval(job))
    finally:
        random.setstate(rng)
    sched = seen["sched"]
    return {"plan": _normalize(h.plans[-1]), "n_done": seen["n_done"],
            "inits": sched.net_inits, "walks": sched.net_walks,
            "walked": seen["walked"]}


def _ports_by_node(plan):
    out: dict = {}
    for node_id, allocs in plan[0].items():
        for a in allocs:
            for tr in a["task_resources"].values():
                for net in tr["networks"]:
                    out.setdefault(node_id, []).extend(
                        net["reserved_ports"])
    return out


def _busy_cluster(tight: bool, n_nodes=4):
    """Nodes that each hold slab-backed allocations (the first job's)
    and one object allocation on the LCG's first draw; with ``tight``,
    node 0 also holds one that leaves room for a single 5 Mbit offer.
    Ids are fixed so two runs' plans compare whole."""
    nodes = _cluster(n_nodes)
    for i, n in enumerate(nodes):
        n.id = f"node-{i}"
    first = _job(n_groups=2, count=n_nodes)
    first.id = "first"
    held = [_held(n, [FIRST_DRAW, 21000 + i]) for i, n in enumerate(nodes)]
    if tight:
        # 1,000 Mbit less the node's reserved 1, the held allocation's 1
        # and the first job's two offers of 5: 988 free; this takes 982.
        held.append(_held(nodes[0], [], mbits=982))
    for i, a in enumerate(held):
        a.id = f"held-{i}"
    return nodes, first, held


@TWINS
@pytest.mark.parametrize("tight", [False, True],
                         ids=["ports", "bandwidth"])
def test_finish_seeded_from_mirror_equals_walk(monkeypatch, native, tight):
    """On nodes that already hold slab-backed and object allocations
    the seeded finish and the walked finish agree: same native prefix,
    same plan, no port of an existing allocation handed out again
    although the LCG draws one first; and where a node's bandwidth
    runs out under the plan, both refuse the same placement."""
    out = {}
    for seeded in (True, False):
        with monkeypatch.context() as m:
            nodes, first, held = _busy_cluster(tight)
            h = _harness(m, nodes, held, first)
            job = _job(n_groups=3, count=2)
            job.id = "second"
            out[seeded] = _finish(m, h, job, native=native, seeded=seeded)
            out[seeded]["held"] = {
                n.id: {p for a in h.state.allocs_by_node(n.id)
                       if a.job_id != "second"
                       for tr in a.task_resources.values()
                       for net in tr.networks
                       for p in net.reserved_ports} for n in nodes}
            out[seeded]["slab_backed"] = sum(
                "_slab" in a.__dict__ for a in h.state.allocs()
                if a.job_id == "first")
    seeded, walked = out[True], out[False]
    assert seeded["slab_backed"] == 8  # the first job's, placed natively
    assert seeded["n_done"] == walked["n_done"]
    # The walk ran for every touched node on one side, for none on the
    # other; both sides touched the same nodes.
    assert seeded["inits"] == walked["inits"] > 0
    assert seeded["walks"] == 0
    assert walked["walks"] == walked["inits"]
    placed = _ports_by_node(seeded["plan"])
    if tight:
        # The placement node 0 has no bandwidth for ends in the
        # sequential fallback, whose ports are random: compare where
        # the offers went.  Node 0 had room for one, and got one.
        assert seeded["n_done"] == ([4] if native else [])
        assert {n: len(p) for n, p in placed.items()} == \
            {n: len(p) for n, p in _ports_by_node(walked["plan"]).items()}
        assert len(placed["node-0"]) == 2  # one offer = two ports
    else:
        assert seeded["plan"] == walked["plan"]
        assert not seeded["walked"]
    # Ports: nothing an existing allocation holds was handed out again,
    # though the first draw on a node IS held there.
    assert sum(len(v) for v in placed.values()) == 12
    for node_id, ports in placed.items():
        assert len(set(ports)) == len(ports)
        assert not set(ports) & seeded["held"][node_id], node_id
        assert FIRST_DRAW in seeded["held"][node_id]


def _walk_case_update(monkeypatch, native):
    """The plan evicts on the chosen nodes (a destructive job update)."""
    nodes = _cluster(2)
    job = _job(n_groups=1, count=2)
    h = _harness(monkeypatch, nodes, first_job=job)
    update = job.copy()
    update.task_groups[0].tasks[0].config = {"command": "/bin/other"}
    got = _finish(monkeypatch, h, update, native=native)
    assert h.plans[-1].node_update
    return got


def _walk_case_odd(monkeypatch, native):
    """Every node holds an allocation whose offers span two devices
    (NET_KEY_ODD in the mirror)."""
    nodes = _cluster(2)
    held = [_held(n, [], offers=[
        (n.reserved.networks[0].ip, "eth0", [21000], 1),
        (n.reserved.networks[0].ip, "eth1", [21001], 1)]) for n in nodes]
    h = _harness(monkeypatch, nodes, held)
    return _finish(monkeypatch, h, _job(n_groups=1, count=2),
                   native=native)


def _walk_case_multi_network(monkeypatch, native):
    """Multi-network nodes (net_base_for -> None): the exact
    NetworkIndex path, which walks."""
    nodes = _cluster(2)
    for n in nodes:
        n.resources.networks.append(NetworkResource(
            device="eth1", cidr="10.0.0.1/32", mbits=1000))
    held = [_held(n, [21000]) for n in nodes]
    h = _harness(monkeypatch, nodes, held)
    return _finish(monkeypatch, h, _job(n_groups=1, count=2),
                   native=native)


def _walk_case_mirror_ahead(monkeypatch, native):
    """The mirror stands one allocs index past the eval's snapshot."""
    nodes = _cluster(2)
    h = _harness(monkeypatch, nodes, [_held(n, [21000]) for n in nodes])
    job = _job(n_groups=1, count=2)
    h.state.upsert_job(h.next_index(), job)
    snap = h.state.snapshot()
    h.state.upsert_allocs(h.next_index(), [_held(nodes[0], [21001])])
    mirror = mirror_for(fleet_cache.statics_for(h.state))
    assert mirror.sync_net(h.state) and not mirror.sync_net(snap)
    return _finish(monkeypatch, h, job, native=native, state=snap)


@TWINS
@pytest.mark.parametrize("case", [
    _walk_case_update, _walk_case_odd, _walk_case_multi_network,
    _walk_case_mirror_ahead], ids=lambda f: f.__name__[11:])
def test_finish_walks_where_the_mirror_cannot_serve(monkeypatch, native,
                                                    case):
    got = case(monkeypatch, native)
    assert got["inits"] > 0
    assert got["walks"] == got["inits"]
    assert len(got["walked"]) >= got["walks"]
    assert sum(len(v) for v in got["plan"][0].values()) == 2


@TWINS
@pytest.mark.parametrize("per_node", [0, 10, 40])
def test_finish_walks_no_allocation_however_full_the_node(
        monkeypatch, native, per_node):
    """Count, don't time: with 0, 10 and 40 allocations already on
    every chosen node, ctx.proposed_allocs is never called for a node
    the plan has no deltas on."""
    nodes = _cluster(3)
    held = [_held(n, [22000 + k]) for n in nodes for k in range(per_node)]
    h = _harness(monkeypatch, nodes, held)
    got = _finish(monkeypatch, h, _job(n_groups=3, count=1), native=native)
    assert got["walked"] == [] and got["walks"] == 0
    assert got["inits"] >= 1
    assert sum(len(v) for v in got["plan"][0].values()) == 3


@TWINS
def test_finish_counters_reach_the_span_and_the_registry(monkeypatch,
                                                        native):
    """The fused runner tags its lanes' sched.finish spans with the
    window's node_inits / walked and serves both as nomad.finish.*."""
    nodes = _cluster(3)
    h = _harness(monkeypatch, nodes, [_held(n, [21000]) for n in nodes])
    if not native:
        monkeypatch.setattr(jb, "_native_bulk", lambda: None)
    jobs = [_job(n_groups=1, count=3), _job(n_groups=1, count=3)]
    for j in jobs:
        h.state.upsert_job(h.next_index(), j)
    runner = BatchEvalRunner(h.state.snapshot(), h)
    with trace_mod.tracing(seed=1) as tracer:
        runner.process([make_eval(j) for j in jobs])
        spans = [s for s in tracer.snapshot() if s["name"] == "sched.finish"]
    assert len(spans) == 2
    for s in spans:
        assert s["tags"]["node_inits"] == 6 and s["tags"]["walked"] == 0
    assert runner.finish_stats() == {"node_inits": 6, "node_walks": 0}


def test_window_copies_the_occupancy_once(monkeypatch):
    """A fused window's lanes plan on one snapshot: the mirror's
    occupancy is copied once for all of them; lanes on different
    snapshots (a pipelined drain) copy their own."""
    nodes = _cluster(3)
    h = _harness(monkeypatch, nodes, [_held(n, [21000]) for n in nodes])
    jobs = [_job(n_groups=1, count=3) for _ in range(3)]
    for j in jobs:
        h.state.upsert_job(h.next_index(), j)

    class Lane:
        def __init__(self, state, statics):
            self.state, self.statics = state, statics

    snap, statics = h.state.snapshot(), fleet_cache.statics_for(h.state)
    same = [(Lane(snap, None), [], Lane(None, statics), [0, 1], [])
            for _ in range(2)]
    assert BatchEvalRunner._window_net_seed(same).keys() == {0, 1}
    other = (Lane(h.state.snapshot(), None), [], Lane(None, statics),
             [2], [])
    assert BatchEvalRunner._window_net_seed(same + [other]) is None
    assert BatchEvalRunner._window_net_seed(same[:1]) is None

    copies = []
    occupancy = UsageMirror.net_occupancy

    def counting(self, state, node_indexes):
        copies.append({ni for ni in node_indexes if ni >= 0})
        return occupancy(self, state, node_indexes)

    monkeypatch.setattr(UsageMirror, "net_occupancy", counting)
    runner = BatchEvalRunner(h.state.snapshot(), h)
    runner.process([make_eval(j) for j in jobs])
    assert copies == [{0, 1, 2}]
    assert runner.finish_stats() == {"node_inits": 9, "node_walks": 0}

