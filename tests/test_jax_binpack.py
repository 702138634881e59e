"""Golden-parity + property tests for the TPU jax-binpack scheduler.

Parity model: the sequential schedulers (GenericStack with the LimitIterator
truncation) are the reference-faithful truth; the device path scores every
feasible node, so its *scores* must match the scalar score_fit math exactly
and its plans must obey the same invariants (fit, constraints, counts).
"""
from __future__ import annotations

import numpy as np
import pytest

import nomad_tpu.mock as mock
from nomad_tpu.models.constraints import compile_group_mask
from nomad_tpu.models.fleet import build_fleet, build_usage
from nomad_tpu.ops.binpack import place_sequence, score_all_nodes
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.feasible import check_single_constraint
from nomad_tpu.scheduler.util import task_group_constraints
from nomad_tpu.structs import (
    EVAL_TRIGGER_JOB_REGISTER,
    JOB_TYPE_SERVICE,
    Allocation,
    Constraint,
    Evaluation,
    Plan,
    Resources,
    allocs_fit,
    score_fit,
)


def make_eval(job):
    return Evaluation(
        id="eval-1", priority=job.priority, type=JOB_TYPE_SERVICE,
        triggered_by=EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
    )


# ---------------------------------------------------------------------------
# score parity: device score == scalar score_fit for every node
# ---------------------------------------------------------------------------

def test_score_parity_all_nodes():
    nodes = [mock.node(i) for i in range(13)]
    # Vary free capacity: preload usage on some nodes.
    allocs = []
    for i in (0, 3, 7):
        a = Allocation(id=f"a{i}", node_id=nodes[i].id, job_id="other",
                       resources=Resources(cpu=2000, memory_mb=4096),
                       desired_status="run")
        allocs.append(a)

    fleet = build_fleet(nodes)
    view = build_usage(fleet, allocs, job_id="j1")

    ask = Resources(cpu=500, memory_mb=256)
    ask_vec = np.asarray(ask.as_vector(), dtype=np.float32)

    feasible = np.zeros(fleet.n_pad, dtype=bool)
    feasible[:fleet.n_real] = True

    scores = np.asarray(score_all_nodes(
        fleet.capacity, fleet.reserved, view.usage, view.job_counts,
        ask_vec, feasible, False, 10.0))

    for i, node in enumerate(nodes):
        proposed = [a for a in allocs if a.node_id == node.id]
        proposed = proposed + [Allocation(resources=ask)]
        fit, _dim, util = allocs_fit(node, proposed)
        assert fit, f"mock node {i} should fit the ask"
        expected = score_fit(node, util)
        assert scores[i] == pytest.approx(expected, abs=1e-4), f"node {i}"


def test_score_marks_unfit_nodes():
    nodes = [mock.node(i) for i in range(4)]
    fleet = build_fleet(nodes)
    view = build_usage(fleet, [])
    # Ask for more cpu than any node has.
    ask = np.asarray(Resources(cpu=99999, memory_mb=10).as_vector(),
                     dtype=np.float32)
    feasible = np.ones(fleet.n_pad, dtype=bool)
    scores = np.asarray(score_all_nodes(
        fleet.capacity, fleet.reserved, view.usage, view.job_counts,
        ask, feasible, False, 10.0))
    assert (scores < -1e29).all()


def test_anti_affinity_penalty_applied():
    nodes = [mock.node(i) for i in range(4)]
    a = Allocation(id="a1", node_id=nodes[0].id, job_id="j1",
                   resources=Resources(cpu=100, memory_mb=100),
                   desired_status="run")
    fleet = build_fleet(nodes)
    view = build_usage(fleet, [a], job_id="j1")
    assert view.job_counts[0] == 1

    ask = np.asarray(Resources(cpu=100, memory_mb=64).as_vector(),
                     dtype=np.float32)
    feasible = np.ones(fleet.n_pad, dtype=bool)
    feasible[fleet.n_real:] = False
    scores = np.asarray(score_all_nodes(
        fleet.capacity, fleet.reserved, view.usage, view.job_counts,
        ask, feasible, False, 10.0))
    # Node 0 carries the same-job alloc: penalized by 10 (plus usage delta).
    assert scores[0] < scores[1] - 5.0


# ---------------------------------------------------------------------------
# constraint mask parity vs the sequential predicate walk
# ---------------------------------------------------------------------------

def test_constraint_mask_parity():
    nodes = []
    for i in range(20):
        n = mock.node(i)
        if i % 3 == 0:
            n.attributes["kernel.name"] = "windows"
        if i % 4 == 0:
            n.attributes["driver.exec"] = "0"
        nodes.append(n)

    job = mock.job()
    tg = job.task_groups[0]
    tg_constr = task_group_constraints(tg)
    fleet = build_fleet(nodes)
    mask, distinct = compile_group_mask(
        fleet, job.datacenters, job.constraints, tg_constr.constraints,
        tg_constr.drivers)
    assert not distinct

    ctx = EvalContext(None, Plan())
    for i, node in enumerate(nodes):
        expected = all(
            check_single_constraint(ctx, c, node)
            for c in job.constraints + tg_constr.constraints if c.hard)
        for d in tg_constr.drivers:
            v = node.attributes.get(f"driver.{d}")
            expected = expected and v is not None and \
                str(v).strip().lower() in ("1", "t", "true")
        assert mask[i] == expected, f"node {i}"
    assert not mask[fleet.n_real:].any()


def test_version_and_regexp_masks():
    nodes = [mock.node(i) for i in range(6)]
    for i, n in enumerate(nodes):
        n.attributes["version"] = f"0.{i}.0"
    fleet = build_fleet(nodes)
    cons = [Constraint(hard=True, l_target="$attr.version",
                       r_target=">= 0.3.0", operand="version")]
    mask, _ = compile_group_mask(fleet, ["dc1"], cons, [], set())
    assert list(mask[:6]) == [False, False, False, True, True, True]

    cons = [Constraint(hard=True, l_target="$node.name",
                       r_target=r"node-[0-2]$", operand="regexp")]
    mask, _ = compile_group_mask(fleet, ["dc1"], cons, [], set())
    assert list(mask[:6]) == [True, True, True, False, False, False]


# ---------------------------------------------------------------------------
# placement scan semantics
# ---------------------------------------------------------------------------

def test_place_sequence_spreads_via_anti_affinity():
    nodes = [mock.node(i) for i in range(8)]
    fleet = build_fleet(nodes)
    view = build_usage(fleet, [])

    ask = np.zeros((1, 6), dtype=np.float32)
    ask[0] = Resources(cpu=500, memory_mb=256).as_vector()
    feasible = np.zeros((1, fleet.n_pad), dtype=bool)
    feasible[0, :fleet.n_real] = True
    group_idx = np.zeros(8, dtype=np.int32)
    valid = np.ones(8, dtype=bool)

    chosen, scores, usage = place_sequence(
        fleet.capacity, fleet.reserved, view.usage, view.job_counts,
        feasible, ask, np.zeros(1, dtype=bool), group_idx, valid, 10.0)
    chosen = np.asarray(chosen)
    # 8 placements on 8 identical nodes with a 10-point penalty: all spread.
    assert sorted(chosen.tolist()) == list(range(8))
    # Usage accounted on device.
    assert np.asarray(usage)[:8, 0].sum() == pytest.approx(500 * 8)


def test_place_sequence_distinct_hosts_exhausts():
    nodes = [mock.node(i) for i in range(4)]
    fleet = build_fleet(nodes)
    view = build_usage(fleet, [])

    ask = np.zeros((1, 6), dtype=np.float32)
    ask[0] = Resources(cpu=10, memory_mb=10).as_vector()
    feasible = np.zeros((1, fleet.n_pad), dtype=bool)
    feasible[0, :fleet.n_real] = True
    group_idx = np.zeros(8, dtype=np.int32)
    valid = np.ones(8, dtype=bool)

    chosen, _, _ = place_sequence(
        fleet.capacity, fleet.reserved, view.usage, view.job_counts,
        feasible, ask, np.ones(1, dtype=bool), group_idx, valid, 0.0)
    chosen = np.asarray(chosen).tolist()
    # 4 distinct hosts then exhaustion (-1): placements beyond N fail.
    assert sorted(c for c in chosen if c >= 0) == list(range(4))
    assert chosen.count(-1) == 4


def test_padding_rows_never_chosen():
    nodes = [mock.node(i) for i in range(3)]  # padded to 8
    fleet = build_fleet(nodes)
    view = build_usage(fleet, [])
    ask = np.zeros((1, 6), dtype=np.float32)
    ask[0] = Resources(cpu=10, memory_mb=10).as_vector()
    feasible = np.zeros((1, fleet.n_pad), dtype=bool)
    feasible[0, :fleet.n_real] = True
    group_idx = np.zeros(8, dtype=np.int32)
    valid = np.ones(8, dtype=bool)
    chosen, _, _ = place_sequence(
        fleet.capacity, fleet.reserved, view.usage, view.job_counts,
        feasible, ask, np.zeros(1, dtype=bool), group_idx, valid, 10.0)
    assert max(np.asarray(chosen).tolist()) <= 2


# ---------------------------------------------------------------------------
# end-to-end through the Harness: jax-binpack vs sequential service scheduler
# ---------------------------------------------------------------------------

def _register_cluster(h: Harness, n_nodes: int):
    nodes = [mock.node(i) for i in range(n_nodes)]
    for n in nodes:
        h.state.upsert_node(h.next_index(), n)
    return nodes


def test_jax_scheduler_places_all():
    h = Harness()
    _register_cluster(h, 10)
    job = mock.job()
    h.state.upsert_job(h.next_index(), job)

    h.process("jax-binpack", make_eval(job))

    assert len(h.plans) == 1
    plan = h.plans[0]
    placed = [a for allocs in plan.node_allocation.values() for a in allocs]
    assert len(placed) == 10
    assert not plan.failed_allocs
    # Anti-affinity spreads 10 allocs over 10 nodes.
    assert len(plan.node_allocation) == 10
    for a in placed:
        assert a.node_id
        assert a.task_resources["web"].networks[0].mbits == 50
        assert len(a.task_resources["web"].networks[0].reserved_ports) == 1
        assert a.metrics.nodes_evaluated == 10


def test_jax_scheduler_matches_sequential_counts():
    """Same cluster, same job -> both schedulers place the full count and
    produce fitting, constraint-respecting plans."""
    for name in ("service", "jax-binpack"):
        h = Harness()
        nodes = _register_cluster(h, 16)
        # Poison half the nodes: wrong kernel.
        for n in nodes[8:]:
            n2 = n.copy()
            n2.attributes = dict(n2.attributes)
            n2.attributes["kernel.name"] = "windows"
            h.state.upsert_node(h.next_index(), n2)
        job = mock.job()
        job.task_groups[0].count = 8
        h.state.upsert_job(h.next_index(), job)

        h.process(name, make_eval(job))
        plan = h.plans[0]
        placed = [a for allocs in plan.node_allocation.values()
                  for a in allocs]
        assert len(placed) == 8, name
        good = {n.id for n in nodes[:8]}
        for a in placed:
            assert a.node_id in good, name


def test_jax_scheduler_exhaustion_fails_allocs():
    h = Harness()
    _register_cluster(h, 2)
    job = mock.job()
    job.task_groups[0].count = 4
    job.task_groups[0].tasks[0].resources.cpu = 3000  # 2 per fleet max
    h.state.upsert_job(h.next_index(), job)

    h.process("jax-binpack", make_eval(job))
    plan = h.plans[0]
    placed = [a for allocs in plan.node_allocation.values() for a in allocs]
    # 4000 MHz nodes, 100 reserved: one 3000 MHz task fits per node.
    assert len(placed) == 2
    assert len(plan.failed_allocs) >= 1  # coalesced failures

    # Evals recorded as complete.
    assert h.evals and h.evals[0].status == "complete"


def test_jax_scheduler_distinct_hosts_end_to_end():
    h = Harness()
    _register_cluster(h, 4)
    job = mock.job()
    job.task_groups[0].count = 6
    job.constraints.append(Constraint(hard=True, operand="distinct_hosts"))
    h.state.upsert_job(h.next_index(), job)

    h.process("jax-binpack", make_eval(job))
    plan = h.plans[0]
    placed = [a for allocs in plan.node_allocation.values() for a in allocs]
    assert len(placed) == 4
    assert len({a.node_id for a in placed}) == 4
    assert plan.failed_allocs


def test_jax_scheduler_plans_fit():
    """Every node's final proposed alloc set passes the exact allocs_fit."""
    h = Harness()
    nodes = _register_cluster(h, 6)
    job = mock.job()
    job.task_groups[0].count = 30
    job.task_groups[0].tasks[0].resources.cpu = 700
    h.state.upsert_job(h.next_index(), job)

    h.process("jax-binpack", make_eval(job))
    plan = h.plans[0]
    by_node = {n.id: n for n in nodes}
    for node_id, allocs in plan.node_allocation.items():
        fit, dim, _ = allocs_fit(by_node[node_id], allocs)
        assert fit, f"node {node_id} overcommitted: {dim}"


def test_jax_scheduler_updates_in_place():
    """Job modify-index bump with unchanged tasks -> in-place update path
    still works (runs through the sequential single-node stack)."""
    h = Harness()
    _register_cluster(h, 4)
    job = mock.job()
    h.state.upsert_job(h.next_index(), job)
    h.process("jax-binpack", make_eval(job))
    allocs = [a for allocs in h.plans[0].node_allocation.values()
              for a in allocs]
    h.state.upsert_allocs(h.next_index(), allocs)

    job2 = job.copy()
    job2.modify_index = job.modify_index + 1
    h.state.upsert_job(h.next_index(), job2)
    h.process("jax-binpack", make_eval(job2))

    plan = h.plans[-1]
    placed = [a for allocs in plan.node_allocation.values() for a in allocs]
    assert len(placed) == 10  # all updated in place
    assert not plan.failed_allocs


def test_fallback_divergence_never_oversubscribes(monkeypatch):
    """When the exact host network check rejects a device winner (forcing a
    sequential fallback), later device choices must be re-verified so the
    plan never oversubscribes a node (code-review regression)."""
    from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler

    h = Harness()
    nodes = _register_cluster(h, 4)
    job = mock.job()
    job.task_groups[0].count = 8
    job.task_groups[0].tasks[0].resources.cpu = 900
    h.state.upsert_job(h.next_index(), job)

    # Reject the first two device winners to force fallback + divergence.
    real = JaxBinPackScheduler._assign_networks
    calls = {"n": 0}

    def flaky(self, node, tg):
        calls["n"] += 1
        if calls["n"] <= 2:
            return None
        return real(self, node, tg)

    monkeypatch.setattr(JaxBinPackScheduler, "_assign_networks", flaky)
    h.process("jax-binpack", make_eval(job))

    plan = h.plans[0]
    by_node = {n.id: n for n in nodes}
    for node_id, allocs in plan.node_allocation.items():
        fit, dim, _ = allocs_fit(by_node[node_id], allocs)
        assert fit, f"node {node_id} oversubscribed: {dim}"
    placed = sum(len(v) for v in plan.node_allocation.values())
    assert placed + len(plan.failed_allocs) >= 8 - 7  # coalescing allowed
    assert placed >= 4


def test_fast_network_rollback_keeps_cached_index_coherent():
    """A bandwidth failure in the fast network assigner must undo the
    offers it already mirrored into the cached exact-path NetworkIndex —
    otherwise later exact-path assignments on the node see phantom
    port/bandwidth reservations (advisor regression)."""
    from nomad_tpu.models.fleet import build_fleet
    from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler
    from nomad_tpu.structs import NetworkIndex, NetworkResource, Resources

    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import Plan

    node = mock.node(0)  # eth0, 1000 mbits, 1 reserved
    sched = JaxBinPackScheduler.__new__(JaxBinPackScheduler)
    sched._statics = build_fleet([node])
    sched._node_net = {}
    sched._net_seed = {}
    sched._port_lcg = 12345
    sched.state = StateStore()
    sched.plan = Plan()

    class _Ctx:
        def proposed_allocs(self, node_id):
            return []

    sched.ctx = _Ctx()

    idx = NetworkIndex()
    idx.set_node(node)
    sched._net_cache = {node.id: idx}
    bw_before = dict(idx.used_bandwidth)
    ports_before = {ip: set(p) for ip, p in idx.used_ports.items()}

    ask_ok = NetworkResource(mbits=500, dynamic_ports=["a"])
    ask_too_big = NetworkResource(mbits=10_000, dynamic_ports=["b"])
    plan_tasks = [
        ("t1", Resources(cpu=100, memory_mb=64, networks=[ask_ok]), ask_ok),
        ("t2", Resources(cpu=100, memory_mb=64, networks=[ask_too_big]),
         ask_too_big),
    ]
    assert sched._assign_networks_fast(0, node, plan_tasks) is None

    # The cached exact-path index must be exactly as it was.
    assert idx.used_bandwidth == bw_before
    assert {ip: set(p) for ip, p in idx.used_ports.items()
            if p} == {ip: set(p) for ip, p in ports_before.items() if p}


# ---------------------------------------------------------------------------
# host (numpy) executor: kernel parity + dispatch policy
# ---------------------------------------------------------------------------

def _random_case(rng, n_nodes=23, n_groups=3, n_place=17):
    nodes = [mock.node(i) for i in range(n_nodes)]
    for i, n in enumerate(nodes):
        n.resources.cpu = int(rng.integers(800, 4000))
        n.resources.memory_mb = int(rng.integers(900, 8000))
    fleet = build_fleet(nodes)
    view = build_usage(fleet, [])
    g_pad = max(4, n_groups)
    asks = np.zeros((g_pad, 6), dtype=np.float32)
    for g in range(n_groups):
        asks[g] = Resources(
            cpu=int(rng.integers(50, 700)),
            memory_mb=int(rng.integers(40, 900))).as_vector()
    feasible = np.zeros((g_pad, fleet.n_pad), dtype=bool)
    feasible[:n_groups, :fleet.n_real] = \
        rng.random((n_groups, fleet.n_real)) > 0.2
    distinct = rng.random(g_pad) > 0.7
    group_idx = rng.integers(0, n_groups, n_place).astype(np.int32)
    valid = np.ones(n_place, dtype=bool)
    valid[-2:] = False
    return fleet, view, asks, feasible, distinct, group_idx, valid


def test_host_place_sequence_parity():
    from nomad_tpu.ops.binpack_host import place_sequence_host

    rng = np.random.default_rng(7)
    for trial in range(4):
        fleet, view, asks, feasible, distinct, group_idx, valid = \
            _random_case(rng)
        dev = place_sequence(
            fleet.capacity, fleet.reserved, view.usage, view.job_counts,
            feasible, asks, distinct, group_idx, valid, 10.0)
        host = place_sequence_host(
            fleet.capacity, fleet.reserved, view.usage, view.job_counts,
            feasible, asks, distinct, group_idx, valid, 10.0)
        dev_chosen = np.asarray(dev[0])
        assert np.array_equal(dev_chosen, host[0]), trial
        placed = dev_chosen >= 0  # scores are meaningless where -1
        np.testing.assert_allclose(np.asarray(dev[1])[placed],
                                   host[1][placed], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(dev[2]), host[2],
                                   rtol=1e-5, atol=1e-3)


def test_host_place_rounds_parity():
    from nomad_tpu.ops.binpack import place_rounds
    from nomad_tpu.ops.binpack_host import place_rounds_host

    rng = np.random.default_rng(11)
    for trial in range(4):
        fleet, view, asks, feasible, distinct, _gi, _v = \
            _random_case(rng)
        counts = np.zeros(asks.shape[0], dtype=np.int32)
        counts[:3] = rng.integers(1, 9, 3)
        dev = place_rounds(
            fleet.capacity, fleet.reserved, view.usage, view.job_counts,
            feasible, asks, distinct, counts, 10.0, k_cap=4, rounds=3)
        host = place_rounds_host(
            fleet.capacity, fleet.reserved, view.usage, view.job_counts,
            feasible, asks, distinct, counts, 10.0, k_cap=4, rounds=3)
        assert np.array_equal(np.asarray(dev[0]), host[0]), trial
        np.testing.assert_allclose(np.asarray(dev[2]), host[2],
                                   rtol=1e-5, atol=1e-3)


def test_small_eval_uses_host_executor(monkeypatch):
    """Tiny fleets must never pay a device dispatch: the executor policy
    routes them to the numpy kernels."""
    import nomad_tpu.scheduler.jax_binpack as jb

    def boom(*a, **k):
        raise AssertionError("device dispatched for a tiny workload")

    monkeypatch.setattr(jb, "place_sequence", boom)
    monkeypatch.setattr(
        "nomad_tpu.ops.binpack.place_rounds", boom)
    h = Harness()
    _register_cluster(h, 10)
    job = mock.job()
    job.task_groups[0].count = 5
    h.state.upsert_job(h.next_index(), job)
    h.process("jax-binpack", make_eval(job))
    placed = sum(len(v) for v in h.plans[0].node_allocation.values())
    assert placed == 5


def test_large_eval_uses_device_when_pipelined():
    """The policy must keep big pipelined workloads on the device."""
    from nomad_tpu.scheduler.jax_binpack import DeviceArgs, \
        JaxBinPackScheduler

    class _S:
        n_real = 20_000

    args = DeviceArgs(statics=_S(), rounds_eligible=False,
                      n_groups=64, n_place=1_000, rounds=1)
    sched = JaxBinPackScheduler.__new__(JaxBinPackScheduler)
    assert not sched.choose_host_executor(args, pipelined=True)
    # Single-shot: same workload prefers the host (one RTT >> numpy).
    assert sched.choose_host_executor(args, pipelined=False)


def test_fast_proto_matches_dataclass():
    """The template constructor (finish loop hot path) must stay
    field-for-field identical to the dataclass constructor."""
    import dataclasses

    from nomad_tpu.scheduler.jax_binpack import (_ALLOC_FACTORIES,
                                                 _ALLOC_STATIC,
                                                 _METRIC_FACTORIES,
                                                 _METRIC_STATIC)
    from nomad_tpu.structs import AllocMetric

    for cls, static, factories in (
            (Allocation, _ALLOC_STATIC, _ALLOC_FACTORIES),
            (AllocMetric, _METRIC_STATIC, _METRIC_FACTORIES)):
        names = {f.name for f in dataclasses.fields(cls)}
        assert set(static) | {n for n, _ in factories} == names
        d = dict(static)
        for n, fac in factories:
            d[n] = fac()
        assert d == cls().__dict__

    # The network fast path fills factory fields explicitly instead of
    # looping; it must fail loudly if the dataclasses grow new ones.
    from nomad_tpu.scheduler.jax_binpack import (_NET_FACTORIES,
                                                 _RES_FACTORIES)

    assert {n for n, _ in _RES_FACTORIES} == {"networks"}
    assert {n for n, _ in _NET_FACTORIES} == {"reserved_ports",
                                              "dynamic_ports"}


def test_host_place_rounds_tie_parity():
    """Homogeneous fleets tie on every score — the common case for a
    fresh cluster of identical nodes.  Host and device top-k must break
    ties the same way (lowest node index first) or the executor policy
    would change placements (code-review regression)."""
    from nomad_tpu.ops.binpack import place_rounds
    from nomad_tpu.ops.binpack_host import place_rounds_host

    nodes = [mock.node(i) for i in range(33)]  # identical resources
    fleet = build_fleet(nodes)
    view = build_usage(fleet, [])
    asks = np.zeros((4, 6), dtype=np.float32)
    asks[0] = Resources(cpu=100, memory_mb=64).as_vector()
    feasible = np.zeros((4, fleet.n_pad), dtype=bool)
    feasible[0, :fleet.n_real] = True
    distinct = np.zeros(4, dtype=bool)
    counts = np.zeros(4, dtype=np.int32)
    counts[0] = 8
    dev = place_rounds(
        fleet.capacity, fleet.reserved, view.usage, view.job_counts,
        feasible, asks, distinct, counts, 10.0, k_cap=4, rounds=3)
    host = place_rounds_host(
        fleet.capacity, fleet.reserved, view.usage, view.job_counts,
        feasible, asks, distinct, counts, 10.0, k_cap=4, rounds=3,
        n_real=fleet.n_real)
    assert np.array_equal(np.asarray(dev[0]), host[0])
    assert np.asarray(dev[2]).shape == host[2].shape


class TestTopkExact:
    """Direct coverage for the host kernel's packed-key top-k
    (ops/binpack_host._topk_exact): must match lax.top_k's contract —
    k largest, ties broken by LOWER index — exactly, byte-for-byte with
    the stable-argsort reference on the docstring's hazard cases."""

    def _ref(self, vals, k):
        return np.argsort(-vals, kind="stable")[:k]

    def test_ties_straddling_the_boundary(self):
        from nomad_tpu.ops.binpack_host import _topk_exact

        vals = np.array([5.0, 7.0, 5.0, 5.0, 7.0, 5.0, 3.0],
                        dtype=np.float32)
        for k in (1, 2, 3, 4, 5):
            assert np.array_equal(_topk_exact(vals, k),
                                  self._ref(vals, k)), k

    def test_negative_zero_and_neg_inf_rows(self):
        from nomad_tpu.ops.binpack_host import NEG_INF, _topk_exact

        vals = np.array([0.0, -0.0, NEG_INF, -0.0, 0.0, -3.5],
                        dtype=np.float32)
        for k in range(1, 7):
            assert np.array_equal(_topk_exact(vals, k),
                                  self._ref(vals, k)), k

    def test_k_bounds(self):
        from nomad_tpu.ops.binpack_host import _topk_exact

        vals = np.array([1.0, 2.0], dtype=np.float32)
        assert len(_topk_exact(vals, 0)) == 0
        assert np.array_equal(_topk_exact(vals, 5), self._ref(vals, 5))

    def test_randomized_tie_heavy_parity(self):
        from nomad_tpu.ops.binpack_host import NEG_INF, _topk_exact

        rng = np.random.default_rng(1234)
        pool = np.array([NEG_INF, -10.0, -0.0, 0.0, 1.25, 1.25, 9.5,
                         18.0], dtype=np.float32)
        for _ in range(500):
            n = int(rng.integers(2, 80))
            k = int(rng.integers(1, n + 3))
            vals = rng.choice(pool, size=n)
            assert np.array_equal(_topk_exact(vals, k),
                                  self._ref(vals, k))
        # Continuous values at fleet scale.
        vals = rng.random(16384).astype(np.float32)
        assert np.array_equal(_topk_exact(vals, 1024),
                              self._ref(vals, 1024))


def test_jax_scheduler_failures_carry_explanations():
    """Device-path failures must carry the reference's AllocMetric
    explanation — constraint filter counts when no node matches,
    dimension exhaustion counts when resources run out (monitor.go
    dumpAllocStatus is downstream of this data)."""
    # 1) Constraint nobody satisfies: constraint_filtered populated.
    h = Harness()
    _register_cluster(h, 3)
    job = mock.job()
    job.task_groups[0].constraints = [
        Constraint(hard=True, l_target="$attr.kernel.name",
                   r_target="plan9", operand="=")]
    h.state.upsert_job(h.next_index(), job)
    h.process("jax-binpack", make_eval(job))
    plan = h.plans[0]
    assert plan.failed_allocs
    m = plan.failed_allocs[0].metrics
    assert m.nodes_evaluated >= 3
    assert sum(m.constraint_filtered.values()) >= 3, m.constraint_filtered

    # 2) Resource exhaustion: dimension_exhausted populated.
    h2 = Harness()
    _register_cluster(h2, 2)
    job2 = mock.job()
    job2.task_groups[0].count = 4
    job2.task_groups[0].tasks[0].resources.cpu = 3000
    h2.state.upsert_job(h2.next_index(), job2)
    h2.process("jax-binpack", make_eval(job2))
    plan2 = h2.plans[0]
    assert plan2.failed_allocs
    m2 = plan2.failed_allocs[0].metrics
    assert m2.nodes_exhausted >= 1 or m2.dimension_exhausted, \
        (m2.nodes_exhausted, m2.dimension_exhausted)


def test_rounds_mode_places_past_fleet_fullness():
    """Regression: with N constraint-feasible nodes but only a few
    having room, the rounds estimate must grow (fit-aware _fit_rounds)
    or the finish fallback must rescue — a 100-copy task group on a
    fleet where just 5 nodes have capacity places ALL copies, not one
    per fitting node."""
    h = Harness()
    # 5 roomy nodes + 25 full-ish nodes (room for exactly one task).
    for i in range(30):
        n = mock.node(i)
        if i >= 5:
            n.resources = Resources(
                cpu=260, memory_mb=160, disk_mb=10_000, iops=150,
                networks=n.resources.networks)
        h.state.upsert_node(h.next_index(), n)
    job = mock.job()
    tg = job.task_groups[0]
    tg.count = 100
    from nomad_tpu.structs import NetworkResource

    tg.tasks[0].resources = Resources(
        cpu=100, memory_mb=64,
        networks=[NetworkResource(mbits=5, dynamic_ports=["http"])])
    h.state.upsert_job(h.next_index(), job)
    h.process("jax-binpack", make_eval(job))
    plan = h.plans[0]
    placed = sum(len(v) for v in plan.node_allocation.values())
    # 5 roomy nodes hold 38 each (cpu 4000-100-100*38...), plenty for
    # 100; the 25 tight nodes hold one each.
    assert placed == 100, (placed, len(plan.failed_allocs))
