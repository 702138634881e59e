"""Group-commit plan applier: vectorized cross-plan conflict windows,
the multi-plan raft apply, and the sequential-parity contract.

The load-bearing property (ISSUE acceptance): for a contended plan
stream, group-commit results — alloc set, per-plan partial rejections,
state indexes — are byte-identical to sequential per-plan application in
eval order.  Two parity rigs lock it down: a hand-built adversarial
stream covering every verdict family (full accept, partial rejection,
all_at_once, evict+refill, port collision, in-place update), and a
recorded stream captured from a real contended storm run.
"""
from __future__ import annotations

import pytest

import nomad_tpu.mock as mock
from nomad_tpu.ops.plan_conflict import _accepted_allocs, evaluate_window
from nomad_tpu.server.eval_broker import EvalBroker
from nomad_tpu.server.fsm import NomadFSM
from nomad_tpu.server.plan_apply import (
    OptimisticSnapshot,
    PlanApplier,
    evaluate_plan,
)
from nomad_tpu.server.plan_queue import PlanQueue
from nomad_tpu.server.raft import InmemRaft
from nomad_tpu.state.store import StateStore
from nomad_tpu.structs import (
    ALLOC_CLIENT_STATUS_PENDING,
    ALLOC_DESIRED_STATUS_RUN,
    ALLOC_DESIRED_STATUS_STOP,
    Allocation,
    Evaluation,
    NetworkResource,
    Plan,
    PlanResult,
    Resources,
    codec,
    generate_uuid,
)

FREE_CPU = 3900  # mock node capacity 4000 minus 100 reserved


def make_alloc(node, *, cpu=1000, mem=1024, job_id="j1",
               desired=ALLOC_DESIRED_STATUS_RUN) -> Allocation:
    return Allocation(
        id=generate_uuid(),
        node_id=node.id,
        job_id=job_id,
        task_group="web",
        resources=Resources(cpu=cpu, memory_mb=mem),
        desired_status=desired,
        client_status=ALLOC_CLIENT_STATUS_PENDING,
    )


def net_alloc(node, *, cpu=200, ports=(), mbits=10) -> Allocation:
    """An alloc whose offer claims ports/bandwidth on the node's one
    network — the shape the incremental port/bandwidth verifier tracks."""
    a = make_alloc(node, cpu=cpu)
    ip = node.reserved.networks[0].ip
    a.task_resources = {"web": Resources(
        cpu=cpu, memory_mb=64,
        networks=[NetworkResource(device="eth0", ip=ip, mbits=mbits,
                                  reserved_ports=list(ports))])}
    return a


def place_plan(*allocs, priority=50) -> Plan:
    plan = Plan(eval_id=generate_uuid(), priority=priority)
    for a in allocs:
        plan.append_alloc(a)
    return plan


def sequential_apply(store: StateStore, plans: list,
                     base_index: int) -> list:
    """The reference semantics: evaluate each plan against live state in
    eval order, commit its accepted portion, one index per plan."""
    results = []
    for i, plan in enumerate(plans):
        result = evaluate_plan(store, plan)
        allocs = []
        for v in result.node_update.values():
            allocs.extend(v)
        for v in result.node_allocation.values():
            allocs.extend(v)
        allocs.extend(result.failed_allocs)
        if allocs:
            store.upsert_allocs(base_index + i, allocs)
        results.append(result)
    return results


def grouped_outcomes(store: StateStore, plans: list,
                     base_index: int):
    """The group-commit path: one window verify, one batched upsert,
    same per-plan index sequence.  Returns the window's outcomes."""
    outcomes = evaluate_window(store, plans)
    items = []
    for i, outcome in enumerate(outcomes):
        result = outcome.result
        allocs = []
        for v in result.node_update.values():
            allocs.extend(v)
        for v in result.node_allocation.values():
            allocs.extend(v)
        allocs.extend(result.failed_allocs)
        if allocs:
            items.append((base_index + i, allocs))
    if items:
        store.upsert_allocs_batched(items)
    return outcomes


def grouped_apply(store: StateStore, plans: list,
                  base_index: int) -> list:
    return [o.result for o in grouped_outcomes(store, plans, base_index)]


def result_key(result: PlanResult) -> tuple:
    return (
        {n: [a.id for a in v] for n, v in result.node_update.items()},
        {n: [a.id for a in v]
         for n, v in result.node_allocation.items()},
        [a.id for a in result.failed_allocs],
        result.refresh_index > 0,
    )


def store_image(store: StateStore) -> tuple:
    return (
        {a.id: a.to_dict() for a in store.allocs()},
        {t: store.get_index(t)
         for t in ("nodes", "jobs", "evals", "allocs")},
    )


def assert_parity(nodes_setup, plans_fn) -> tuple:
    """Build two identical worlds, apply the same plan stream
    sequentially and grouped, assert byte-identical results + state."""
    s_seq, s_grp = StateStore(), StateStore()
    for store in (s_seq, s_grp):
        nodes_setup(store)
    plans = plans_fn(s_seq)  # same objects verified against both worlds
    res_seq = sequential_apply(s_seq, plans, 2000)
    res_grp = grouped_apply(s_grp, plans, 2000)
    assert [result_key(r) for r in res_seq] == \
        [result_key(r) for r in res_grp]
    assert store_image(s_seq) == store_image(s_grp)
    return res_seq, s_seq


# ---------------------------------------------------------------------------
# 1. window semantics: order sensitivity, fallbacks, evict windows
# ---------------------------------------------------------------------------

class TestWindowSemantics:
    def test_disjoint_window_full_accepts(self):
        store = StateStore()
        nodes = [mock.node(i) for i in range(4)]
        for i, n in enumerate(nodes):
            store.upsert_node(1000 + i, n)
        plans = [place_plan(make_alloc(n)) for n in nodes]
        outcomes = evaluate_window(store, plans)
        assert all(o.result.full_commit(p)[0]
                   for o, p in zip(outcomes, plans))
        assert all(not o.fallback for o in outcomes)

    def test_prefix_conflict_is_order_sensitive(self):
        """Two plans over-committing one node: the FIRST wins, the
        second is rejected with a refresh — and is reported as the
        conflict fallback."""
        store = StateStore()
        node = mock.node()
        store.upsert_node(1000, node)
        first = place_plan(make_alloc(node, cpu=FREE_CPU))
        second = place_plan(make_alloc(node, cpu=1000))
        outcomes = evaluate_window(store, [first, second])
        assert outcomes[0].result.node_allocation == \
            first.node_allocation
        assert outcomes[1].result.node_allocation == {}
        assert outcomes[1].result.refresh_index > 0
        assert not outcomes[0].fallback and outcomes[1].fallback

    def test_window_port_collision_rejects_later_plan(self):
        """A static-port claim staged by an earlier plan in the window
        must reject a later plan's identical claim (the incremental
        port mirror extended with window-local state)."""
        store = StateStore()
        node = mock.node()
        store.upsert_node(1000, node)
        first = place_plan(net_alloc(node, ports=[8080]))
        second = place_plan(net_alloc(node, ports=[8080]))
        outcomes = evaluate_window(store, [first, second])
        assert outcomes[0].result.node_allocation == \
            first.node_allocation
        assert outcomes[1].result.node_allocation == {}

    def test_window_evict_frees_capacity_for_later_plan(self):
        store = StateStore()
        node = mock.node()
        store.upsert_node(1000, node)
        existing = make_alloc(node, cpu=FREE_CPU)
        store.upsert_allocs(1001, [existing])
        evict = Plan(eval_id=generate_uuid())
        evict.append_update(existing, ALLOC_DESIRED_STATUS_STOP, "gone")
        refill = place_plan(make_alloc(node, cpu=FREE_CPU))
        outcomes = evaluate_window(store, [evict, refill])
        assert outcomes[0].result.node_update == evict.node_update
        assert outcomes[1].result.node_allocation == \
            refill.node_allocation, \
            "the window overlay must see the eviction's freed capacity"

    def test_window_respects_inflight_overlay(self):
        """The verify/apply overlap extends to windows: claims against
        a node the in-flight apply already filled must reject."""
        store = StateStore()
        a, b = mock.node(), mock.node(1)
        store.upsert_node(1000, a)
        store.upsert_node(1001, b)
        snap = OptimisticSnapshot(store.snapshot())
        snap.upsert_allocs([make_alloc(a, cpu=FREE_CPU)])  # in flight
        plans = [place_plan(make_alloc(a, cpu=1000)),
                 place_plan(make_alloc(b, cpu=1000))]
        outcomes = evaluate_window(snap, plans)
        assert outcomes[0].result.node_allocation == {}
        assert outcomes[1].result.node_allocation == \
            plans[1].node_allocation

    def test_all_at_once_window_member(self):
        store = StateStore()
        good, full = mock.node(), mock.node(1)
        store.upsert_node(1000, good)
        store.upsert_node(1001, full)
        store.upsert_allocs(1002, [make_alloc(full, cpu=FREE_CPU)])
        plan = place_plan(make_alloc(good), make_alloc(full, cpu=1000))
        plan.all_at_once = True
        outcomes = evaluate_window(
            store, [plan, place_plan(make_alloc(good, cpu=100))])
        assert outcomes[0].result.node_allocation == {}
        assert outcomes[0].result.refresh_index > 0


# ---------------------------------------------------------------------------
# 2. sequential parity (the acceptance bar)
# ---------------------------------------------------------------------------

def _stamp_adversarial_deadlines(plans) -> None:
    """Deadlines DESCENDING by window position, so the deadline-aware
    component scheduler verifies components in roughly REVERSE window
    order — results must still be byte-identical to eval order."""
    import time as _time
    now = _time.monotonic()
    n = len(plans)
    for i, plan in enumerate(plans):
        plan.deadline = now + 100.0 + (n - i) * 10.0


class TestSequentialParity:
    def test_adversarial_stream_parity(self):
        """Hand-built contended stream covering every verdict family:
        clean full accepts (with port claims), an order-sensitive accept
        on a shared node, a window port collision, cross-plan
        over-commit, all_at_once whole-rejection, evict+refill, an
        in-place update, and failed allocs riding a rejected plan —
        replayed through the window verify against the sequential
        truth, with adversarial deadlines so component scheduling
        order != eval order."""
        nodes = [mock.node(i) for i in range(6)]

        def setup(store):
            for i, n in enumerate(nodes):
                store.upsert_node(1000 + i, n)

        # Pre-existing allocs must exist in EVERY world with the same
        # ids: build once, upsert into each store.
        existing = make_alloc(nodes[3], cpu=FREE_CPU)
        existing2 = make_alloc(nodes[4], cpu=2000)

        def world():
            store = StateStore()
            setup(store)
            store.upsert_allocs(1500, [existing, existing2])
            return store

        plans = []
        plans.append(place_plan(net_alloc(nodes[0], ports=[9000])))
        plans.append(place_plan(net_alloc(nodes[0], ports=[9001])))
        plans.append(place_plan(net_alloc(nodes[0], ports=[9000])))
        plans.append(place_plan(make_alloc(nodes[1], cpu=FREE_CPU)))
        plans.append(place_plan(make_alloc(nodes[1], cpu=500)))
        p = place_plan(make_alloc(nodes[2], cpu=100),
                       make_alloc(nodes[1], cpu=500))
        p.all_at_once = True
        plans.append(p)
        evict = Plan(eval_id=generate_uuid())
        evict.append_update(existing, ALLOC_DESIRED_STATUS_STOP, "drain")
        plans.append(evict)
        plans.append(place_plan(make_alloc(nodes[3], cpu=FREE_CPU)))
        replacement = existing2.copy()
        replacement.resources = Resources(cpu=3000, memory_mb=1024)
        plans.append(place_plan(replacement))
        full_plan = place_plan(make_alloc(nodes[1], cpu=FREE_CPU))
        failed = make_alloc(nodes[1], cpu=1)
        failed.node_id = ""
        full_plan.append_failed(failed)
        plans.append(full_plan)
        _stamp_adversarial_deadlines(plans)

        s_seq = world()
        res_seq = sequential_apply(s_seq, plans, 2000)
        s_grp = world()
        res_grp = grouped_apply(s_grp, plans, 2000)
        assert [result_key(r) for r in res_seq] == \
            [result_key(r) for r in res_grp]
        assert store_image(s_seq) == store_image(s_grp)
        # Sanity on the interesting verdicts.
        assert result_key(res_seq[2])[1] == {}      # port collision
        assert result_key(res_seq[4])[1] == {}      # over-commit
        assert result_key(res_seq[5])[1] == {}      # all_at_once
        assert res_seq[7].node_allocation            # refill accepted

    def test_recorded_contended_storm_stream_parity(self):
        """Record a REAL contended plan stream (fused storm through the
        verifying planner), then replay it onto fresh worlds through
        the window verify against the sequential truth."""
        world, plans = _recorded_storm()
        s_seq = world()
        res_seq = sequential_apply(s_seq, plans, 5000)
        s_grp = world()
        res_grp = grouped_apply(s_grp, plans, 5000)
        assert [result_key(r) for r in res_seq] == \
            [result_key(r) for r in res_grp]
        assert store_image(s_seq) == store_image(s_grp)


def _recorded_storm() -> tuple:
    """(world, plans): the plan stream of a real contended storm — six
    jobs of 4 groups x 2 copies fused over 8 nodes through the
    verifying planner — with adversarial deadlines, and a factory of
    fresh worlds to replay it onto."""
    from nomad_tpu.scheduler import Harness
    from nomad_tpu.scheduler.batch import BatchEvalRunner
    from nomad_tpu.scheduler.harness import VerifyingPlanner
    from nomad_tpu.structs import (EVAL_TRIGGER_JOB_REGISTER,
                                   Task, TaskGroup)

    nodes = [mock.node(i) for i in range(8)]
    h = Harness()
    for n in nodes:
        h.state.upsert_node(h.next_index(), n.copy())
    jobs = []
    for j in range(6):
        job = mock.job()
        job.task_groups = [
            TaskGroup(name=f"tg-{g}", count=2,
                      tasks=[Task(name="web", driver="exec",
                                  resources=Resources(
                                      cpu=600, memory_mb=256,
                                      networks=[NetworkResource(
                                          mbits=5,
                                          dynamic_ports=["http"])]))])
            for g in range(4)]
        h.state.upsert_job(h.next_index(), job)
        jobs.append(job)
    h.planner = VerifyingPlanner(h)
    evals = [Evaluation(id=generate_uuid(), priority=50,
                        type=j.type,
                        triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                        job_id=j.id) for j in jobs]
    BatchEvalRunner(h.state.snapshot(), h,
                    state_refresh=h.snapshot).process(evals)
    plans = h.plans
    assert plans, "storm recorded no plans"
    _stamp_adversarial_deadlines(plans)

    def world():
        store = StateStore()
        for i, n in enumerate(nodes):
            store.upsert_node(1000 + i, n.copy())
        return store

    return world, plans


def _seeded_random_window(n_nodes: int) -> tuple:
    """(world, plans): a seeded random contended window over
    ``n_nodes`` — evict-frees-capacity, port-collision, over-commit and
    clean shapes mixed."""
    import random

    rng = random.Random(171_000 + n_nodes)
    nodes = [mock.node(i) for i in range(n_nodes)]
    # Standing allocs: every third node starts near-full so random
    # refills contend, and their evictions free real capacity.
    existing = [make_alloc(nodes[i], cpu=FREE_CPU - 500)
                for i in range(0, n_nodes, 3)]

    def world():
        store = StateStore()
        for i, n in enumerate(nodes):
            store.upsert_node(1000 + i, n)
        store.upsert_allocs(1500, existing)
        return store

    plans = []
    hot = nodes[:max(2, n_nodes // 4)]  # contention focus
    for _ in range(24):
        kind = rng.random()
        if kind < 0.25:
            # Evict-frees-capacity: stop a standing alloc, refill
            # the node to the brim in a LATER plan.
            victim = rng.choice(existing)
            evict = Plan(eval_id=generate_uuid())
            evict.append_update(victim,
                                ALLOC_DESIRED_STATUS_STOP, "churn")
            plans.append(evict)
            node = next(n for n in nodes if n.id == victim.node_id)
            plans.append(place_plan(make_alloc(node, cpu=FREE_CPU)))
        elif kind < 0.45:
            # Port collision: two claims on one hot node, one
            # shared static port — the later one must reject.
            node = rng.choice(hot)
            port = 8000 + rng.randrange(4)
            plans.append(place_plan(net_alloc(node, ports=[port])))
            plans.append(place_plan(net_alloc(node, ports=[port])))
        elif kind < 0.7:
            # Over-commit pressure on a hot node.
            node = rng.choice(hot)
            plans.append(place_plan(make_alloc(
                node, cpu=rng.choice((500, 1500, FREE_CPU)))))
        else:
            # Clean placement on a random node.
            node = rng.choice(nodes)
            plans.append(place_plan(make_alloc(
                node, cpu=rng.choice((100, 400, 900)))))
    _stamp_adversarial_deadlines(plans)
    return world, plans


# ---------------------------------------------------------------------------
# 2b. the window verify's two engines: the array pass and the all-walk path
# ---------------------------------------------------------------------------

class TestWindowEngineParity:
    """The same windows through the array pass (the size gate lifted),
    through the walk of every claim (the small windows they are) and
    through sequential ``evaluate_plan`` + commit: verdict stream,
    alloc set and store image equal."""

    @pytest.mark.parametrize("window", [
        _recorded_storm,
        lambda: _seeded_random_window(8),
        lambda: _seeded_random_window(24),
        lambda: _seeded_random_window(64),
    ], ids=["recorded-storm", "seeded-8", "seeded-24", "seeded-64"])
    def test_pass_walk_and_sequential_agree(self, window, monkeypatch):
        import nomad_tpu.ops.plan_conflict as plan_conflict

        world, plans = window()
        s_seq = world()
        res_seq = sequential_apply(s_seq, plans, 5000)

        s_walk = world()
        out_walk = grouped_outcomes(s_walk, plans, 5000)
        assert all(o.walked == o.claims for o in out_walk), \
            "a window under the gate walks every claim"

        monkeypatch.setattr(plan_conflict, "ARRAY_PASS_MIN_CLAIMS", 0)
        s_pass = world()
        out_pass = grouped_outcomes(s_pass, plans, 5000)
        assert sum(o.walked for o in out_pass) < \
            sum(o.claims for o in out_pass), \
            "the array pass decided nothing: walk against walk"

        assert [result_key(r) for r in res_seq] == \
            [result_key(o.result) for o in out_walk] == \
            [result_key(o.result) for o in out_pass]
        assert store_image(s_seq) == store_image(s_walk) \
            == store_image(s_pass)

    def test_prefix_sums_are_exact_where_a_bf16_pass_is_not(
            self, monkeypatch):
        """Free cpu per node is 3900 MHz.  Node X takes 1301 + 1301 +
        1298 = 3900: the third fits exactly.  Node Y takes 1299 + 1299
        + 1303 = 3901: the third must be rejected.  A sum carried in
        fewer bits (bf16 reads 1301 as 1304, 1299 as 1296) gets both
        wrong; the pass's float64 prefix sums and the walk's float adds
        get both right."""
        import nomad_tpu.ops.plan_conflict as plan_conflict

        nodes = [mock.node(i) for i in range(32)]
        asks = []
        for cpus in ((1301, 1299), (1301, 1299), (1298, 1303)):
            for pair in range(0, 32, 2):
                asks += [(nodes[pair], cpus[0]), (nodes[pair + 1], cpus[1])]
        plans = [place_plan(make_alloc(node, cpu=cpu, mem=517))
                 for node, cpu in asks]
        want = [True] * 64 + [True, False] * 16
        for min_claims in (0, plan_conflict.ARRAY_PASS_MIN_CLAIMS):
            monkeypatch.setattr(plan_conflict, "ARRAY_PASS_MIN_CLAIMS",
                                min_claims)
            outcomes = evaluate_window(_store(nodes), plans)
            assert [bool(o.result.node_allocation)
                    for o in outcomes] == want, min_claims
            if min_claims == 0:
                # The exact fits stayed with the pass; only the nodes
                # with a rejection walked.
                assert sum(o.walked for o in outcomes) == 16 * 3


# ---------------------------------------------------------------------------
# 3. the applier's window drain + one-raft-apply commit
# ---------------------------------------------------------------------------

def _rig(on_apply=None):
    broker = EvalBroker()
    broker.set_enabled(True)
    fsm = NomadFSM(eval_broker=broker, on_apply=on_apply)
    raft = InmemRaft(fsm)
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, broker, raft, lambda: fsm.state)
    return broker, fsm, raft, queue, applier


def _outstanding_plan(broker, fsm, raft, node, *, cpu=1000):
    """A token-fenced plan for a fresh eval the broker handed out."""
    ev = Evaluation(id=generate_uuid(), priority=50, type="service",
                    job_id=generate_uuid(), status="pending",
                    triggered_by="job-register")
    entry = codec.encode(codec.EVAL_UPDATE_REQUEST,
                         {"evals": [ev.to_dict()]})
    raft.apply(entry).wait(5.0)
    got, token = broker.dequeue(["service"], timeout=2.0)
    assert got.id == ev.id
    plan = place_plan(make_alloc(node, cpu=cpu))
    plan.eval_id = ev.id
    plan.eval_token = token
    return plan


class TestApplierWindow:
    def test_window_commits_as_one_batched_apply(self):
        applied = []
        broker, fsm, raft, queue, applier = _rig(
            on_apply=lambda i, t, p: applied.append((i, t)))
        node = mock.node()
        raft.apply(codec.encode(codec.NODE_REGISTER_REQUEST,
                                {"node": node.to_dict()})).wait(5.0)
        applied.clear()

        futures = [queue.enqueue(_outstanding_plan(broker, fsm, raft,
                                                   node, cpu=500))
                   for _ in range(4)]
        window = [queue.dequeue(0)] + queue.drain_pending(63)
        assert len(window) == 4
        applier._apply_window(window, None)

        results = [f.wait(5.0) for f in futures]
        # ONE raft apply carried the whole window...
        plan_applies = [t for _i, t in applied
                        if t in (codec.ALLOC_UPDATE_REQUEST,
                                 codec.PLAN_BATCH_APPLY_REQUEST)]
        assert plan_applies == [codec.PLAN_BATCH_APPLY_REQUEST]
        # ...every member future got the commit index, and state has
        # every plan's allocs exactly once.
        assert len({r.alloc_index for r in results}) == 1
        assert len(fsm.state.allocs_by_node(node.id)) == 4
        stats = applier.stats()
        assert stats["commits"] == 1
        assert stats["plans_committed"] == 4
        assert stats["batch_occupancy"] == 4.0
        assert stats["windows"] == [4]

    def test_window_results_match_sequential_order(self):
        """Two window plans over-commit one node: the first commits,
        the second is rejected with a refresh — eval-order semantics
        through the real applier."""
        broker, fsm, raft, queue, applier = _rig()
        node = mock.node()
        raft.apply(codec.encode(codec.NODE_REGISTER_REQUEST,
                                {"node": node.to_dict()})).wait(5.0)
        f1 = queue.enqueue(_outstanding_plan(broker, fsm, raft, node,
                                             cpu=FREE_CPU))
        f2 = queue.enqueue(_outstanding_plan(broker, fsm, raft, node,
                                             cpu=1000))
        window = [queue.dequeue(0)] + queue.drain_pending(63)
        applier._apply_window(window, None)
        r1 = f1.wait(5.0)
        r2 = f2.wait(5.0)
        assert r1.node_allocation and r1.alloc_index > 0
        assert r2.node_allocation == {} and r2.refresh_index > 0
        assert len(fsm.state.allocs_by_node(node.id)) == 1
        assert applier.stats()["conflict_fallbacks"] == 1

    def test_single_committer_keeps_legacy_wire_format(self):
        applied = []
        broker, fsm, raft, queue, applier = _rig(
            on_apply=lambda i, t, p: applied.append(t))
        node = mock.node()
        raft.apply(codec.encode(codec.NODE_REGISTER_REQUEST,
                                {"node": node.to_dict()})).wait(5.0)
        applied.clear()
        f = queue.enqueue(_outstanding_plan(broker, fsm, raft, node))
        window = [queue.dequeue(0)] + queue.drain_pending(63)
        applier._apply_window(window, None)
        assert f.wait(5.0).alloc_index > 0
        plan_applies = [t for t in applied
                        if t in (codec.ALLOC_UPDATE_REQUEST,
                                 codec.PLAN_BATCH_APPLY_REQUEST)]
        assert plan_applies == [codec.ALLOC_UPDATE_REQUEST]

    def test_bad_tokens_fenced_out_of_window(self):
        broker, fsm, raft, queue, applier = _rig()
        node = mock.node()
        raft.apply(codec.encode(codec.NODE_REGISTER_REQUEST,
                                {"node": node.to_dict()})).wait(5.0)
        good = _outstanding_plan(broker, fsm, raft, node)
        bad = place_plan(make_alloc(node))
        bad.eval_id = generate_uuid()  # never outstanding
        f_bad = queue.enqueue(bad)
        f_good = queue.enqueue(good)
        window = [queue.dequeue(0)] + queue.drain_pending(63)
        applier._apply_window(window, None)
        with pytest.raises(RuntimeError, match="not outstanding"):
            f_bad.wait(5.0)
        assert f_good.wait(5.0).alloc_index > 0

    def test_errored_batch_apply_responds_every_member_future(self):
        """The raft.apply fault site (ISSUE satellite): an errored batch
        apply must respond EVERY member future with the error, move no
        state, and a retry must not double-place."""
        from nomad_tpu import faultinject
        from nomad_tpu.faultinject import FaultPlan

        broker, fsm, raft, queue, applier = _rig()
        node = mock.node()
        raft.apply(codec.encode(codec.NODE_REGISTER_REQUEST,
                                {"node": node.to_dict()})).wait(5.0)
        plans = [_outstanding_plan(broker, fsm, raft, node)
                 for _ in range(3)]

        fplan = FaultPlan.parse("raft.apply=error(count=1)")
        with faultinject.injected(fplan):
            futures = [queue.enqueue(p) for p in plans]
            window = [queue.dequeue(0)] + queue.drain_pending(63)
            applier._apply_window(window, None)
            errs = 0
            for f in futures:
                with pytest.raises(Exception):
                    f.wait(5.0)
                errs += 1
            assert errs == 3
            assert fsm.state.allocs_by_node(node.id) == [], \
                "an errored batch apply must move no state"

            # Retry (same eval tokens are still outstanding): the full
            # window commits exactly once — no double placement.
            futures = [queue.enqueue(p) for p in plans]
            window = [queue.dequeue(0)] + queue.drain_pending(63)
            applier._apply_window(window, None)
            for f in futures:
                assert f.wait(5.0).alloc_index > 0
        assert len(fsm.state.allocs_by_node(node.id)) == 3
        assert fplan.fire_count("raft.apply") == 1

    def test_applier_thread_drains_queue_window(self):
        """End to end with the real applier thread: plans enqueued
        before the thread starts drain as one window."""
        applied = []
        broker, fsm, raft, queue, applier = _rig(
            on_apply=lambda i, t, p: applied.append(t))
        node = mock.node()
        raft.apply(codec.encode(codec.NODE_REGISTER_REQUEST,
                                {"node": node.to_dict()})).wait(5.0)
        applied.clear()
        futures = [queue.enqueue(_outstanding_plan(broker, fsm, raft,
                                                   node))
                   for _ in range(3)]
        applier.start()
        try:
            for f in futures:
                assert f.wait(5.0).alloc_index > 0
            assert codec.PLAN_BATCH_APPLY_REQUEST in applied
        finally:
            queue.set_enabled(False)
            applier.join(5.0)


# ---------------------------------------------------------------------------
# 4. plan queue window drain
# ---------------------------------------------------------------------------

class TestDrainPending:
    def test_drains_in_priority_order(self):
        q = PlanQueue()
        q.set_enabled(True)
        lo = Plan(eval_id=generate_uuid(), priority=10)
        hi = Plan(eval_id=generate_uuid(), priority=90)
        mid = Plan(eval_id=generate_uuid(), priority=50)
        q.enqueue(lo)
        q.enqueue(hi)
        q.enqueue(mid)
        first = q.dequeue(0)
        rest = q.drain_pending(8)
        assert first.plan is hi
        assert [f.plan for f in rest] == [mid, lo]
        assert q.drain_pending(8) == []
        assert q.stats()["depth"] == 0

    def test_respects_max(self):
        q = PlanQueue()
        q.set_enabled(True)
        for _ in range(5):
            q.enqueue(Plan(eval_id=generate_uuid(), priority=50))
        assert len(q.drain_pending(3)) == 3
        assert len(q.drain_pending(0)) == 0
        assert len(q.drain_pending(9)) == 2

    def test_deadline_promotion_pulls_near_deadline_plan_forward(self):
        """A LOW-priority plan whose deadline falls inside the drain
        horizon jumps the high-priority stream — without promotion it
        would sit past the window cut until the fence expires it."""
        import time as _time

        q = PlanQueue()
        q.set_enabled(True)
        urgent = Plan(eval_id=generate_uuid(), priority=1)
        urgent.deadline = _time.monotonic() + 0.05
        hi = [Plan(eval_id=generate_uuid(), priority=90)
              for _ in range(4)]
        for p in hi:
            q.enqueue(p)
        q.enqueue(urgent)
        # Window of 3 out of 5 pending: plain priority order would
        # never include the low-priority near-deadline plan.
        first = q.dequeue(0)
        window = [first.plan] + [f.plan
                                 for f in q.drain_pending(2,
                                                          horizon=1.0)]
        assert urgent in window, "near-deadline plan must be promoted"
        assert window[1] is urgent, "promoted plans lead the window"
        assert q.stats()["deadline_promotions"] == 1
        # The remaining high-priority plans are still there, in order.
        rest = q.drain_pending(8, horizon=1.0)
        assert len(rest) == 2
        assert q.stats()["depth"] == 0

    def test_far_deadlines_keep_priority_order(self):
        import time as _time

        q = PlanQueue()
        q.set_enabled(True)
        lo = Plan(eval_id=generate_uuid(), priority=10)
        lo.deadline = _time.monotonic() + 500.0  # far outside horizon
        hi = Plan(eval_id=generate_uuid(), priority=90)
        q.enqueue(lo)
        q.enqueue(hi)
        first = q.dequeue(0)
        assert first.plan is hi
        assert [f.plan for f in q.drain_pending(4, horizon=0.25)] == [lo]
        assert q.stats()["deadline_promotions"] == 0

    def test_await_depth_returns_on_fill_and_timeout(self):
        import threading
        import time as _time

        q = PlanQueue()
        q.set_enabled(True)
        t0 = _time.monotonic()
        assert q.await_depth(2, timeout=0.05) == 0  # times out empty
        assert _time.monotonic() - t0 >= 0.04

        def fill():
            q.enqueue(Plan(eval_id=generate_uuid(), priority=50))
            q.enqueue(Plan(eval_id=generate_uuid(), priority=50))

        t = threading.Thread(target=fill)
        t.start()
        assert q.await_depth(2, timeout=5.0) >= 2  # wakes on fill
        t.join(2.0)


# ---------------------------------------------------------------------------
# 5. the claim-graph partitioner (ISSUE 13 satellite: exactness)
# ---------------------------------------------------------------------------

def _brute_force_components(plans) -> set:
    """Reference partition: adjacency over shared claimed nodes,
    flood-filled."""
    from nomad_tpu.ops.plan_conflict import _touched

    n = len(plans)
    touched = [_touched(p) for p in plans]
    adj = {i: set() for i in range(n)}
    for i in range(n):
        for j in range(i + 1, n):
            if touched[i] & touched[j]:
                adj[i].add(j)
                adj[j].add(i)
    seen: set = set()
    comps = []
    for i in range(n):
        if i in seen:
            continue
        comp = set()
        stack = [i]
        while stack:
            k = stack.pop()
            if k in comp:
                continue
            comp.add(k)
            stack.extend(adj[k] - comp)
        seen |= comp
        comps.append(frozenset(comp))
    return set(comps)


class TestPartitioner:
    def test_random_claim_sets_match_brute_force(self):
        """Property test: union-find components over random windows ==
        the brute-force adjacency flood fill, and no two plans in
        different components share a node claim — across many seeds,
        with evict-frees-capacity and port-collision window shapes
        mixed in."""
        import random

        from nomad_tpu.ops.plan_conflict import (_touched,
                                                 partition_window)

        nodes = [mock.node(i) for i in range(12)]
        for seed in range(40):
            rng = random.Random(seed)
            plans = []
            for _ in range(rng.randrange(1, 24)):
                kind = rng.random()
                picked = rng.sample(nodes, rng.randrange(1, 4))
                if kind < 0.25:
                    # evict-frees-capacity shape: stop + refill
                    plan = Plan(eval_id=generate_uuid())
                    victim = make_alloc(picked[0], cpu=FREE_CPU)
                    plan.append_update(victim,
                                       ALLOC_DESIRED_STATUS_STOP,
                                       "preempted")
                    if len(picked) > 1:
                        plan.append_alloc(make_alloc(picked[1]))
                elif kind < 0.5:
                    # port-collision shape: static port claims
                    plan = place_plan(*[net_alloc(n, ports=[9000])
                                        for n in picked])
                else:
                    plan = place_plan(*[make_alloc(n) for n in picked])
                plans.append(plan)

            comps = partition_window(plans)
            # Exact partition of indices.
            flat = [i for c in comps for i in c]
            assert sorted(flat) == list(range(len(plans)))
            assert all(c == sorted(c) for c in comps)
            # Matches brute force.
            assert {frozenset(c) for c in comps} == \
                _brute_force_components(plans), seed
            # Cross-component node-claim disjointness.
            for a in range(len(comps)):
                for b in range(a + 1, len(comps)):
                    nodes_a = set().union(*[_touched(plans[i])
                                            for i in comps[a]])
                    nodes_b = set().union(*[_touched(plans[i])
                                            for i in comps[b]])
                    assert not (nodes_a & nodes_b), seed

    def test_components_ordered_by_first_member(self):
        from nomad_tpu.ops.plan_conflict import partition_window

        a, b = mock.node(), mock.node(1)
        plans = [place_plan(make_alloc(a)),     # comp 0
                 place_plan(make_alloc(b)),     # comp 1
                 place_plan(make_alloc(a))]     # joins comp 0
        comps = partition_window(plans)
        assert comps == [[0, 2], [1]]

    def test_window_info_reports_partition(self):
        store = StateStore()
        nodes = [mock.node(i) for i in range(4)]
        for i, n in enumerate(nodes):
            store.upsert_node(1000 + i, n)
        plans = [place_plan(make_alloc(n)) for n in nodes]
        outcomes = evaluate_window(store, plans)
        assert outcomes.info is not None
        assert outcomes.info["components"] == 4
        assert outcomes.info["sizes"] == [1, 1, 1, 1]
        assert {o.component for o in outcomes} == {0, 1, 2, 3}

    def test_near_deadline_component_walks_first(self):
        """A window of several components, the LAST of them holding a
        plan with a near deadline: that component walks first, and the
        verdicts are sequential application's all the same."""
        import time as _time

        shared = mock.node()
        others = [mock.node(i + 1) for i in range(4)]
        urgent = mock.node(9)

        def world():
            store = StateStore()
            for i, n in enumerate([shared, *others, urgent]):
                store.upsert_node(1000 + i, n)
            return store

        # Component 0: a conflict cluster over-committing one node;
        # components 1-4: lone plans; component 5: two plans on the
        # urgent node, the second of which must be rejected.
        plans = [place_plan(make_alloc(shared, cpu=600))
                 for _ in range(8)]
        plans += [place_plan(make_alloc(n)) for n in others]
        plans += [place_plan(make_alloc(urgent, cpu=FREE_CPU)),
                  place_plan(make_alloc(urgent, cpu=500))]
        now = _time.monotonic()
        for plan in plans:
            plan.deadline = now + 100.0
        plans[-1].deadline = now + 1.0

        s_seq = world()
        res_seq = sequential_apply(s_seq, plans, 3000)
        s_grp = world()
        outcomes = grouped_outcomes(s_grp, plans, 3000)
        assert [result_key(r) for r in res_seq] == \
            [result_key(o.result) for o in outcomes]
        assert store_image(s_seq) == store_image(s_grp)
        info = outcomes.info
        assert info["components"] == 6
        assert info["sizes"] == [8, 1, 1, 1, 1, 2]
        assert info["order"] == [5, 0, 1, 2, 3, 4]
        assert [o.component for o in outcomes] == \
            [1] * 8 + [2, 3, 4, 5] + [0, 0]
        # The walks ran in that order, one after another.
        t0s = info["comp_t0s"]
        assert t0s == sorted(t0s)
        assert outcomes[-2].result.node_allocation
        assert outcomes[-1].result.node_allocation == {}
        assert sum(1 for o in outcomes[:8]
                   if o.result.node_allocation) == 6


# ---------------------------------------------------------------------------
# 6. deadline fencing + the applier's service threads
# ---------------------------------------------------------------------------

class TestDeadlineFence:
    def test_expired_plan_dropped_before_verification(self):
        """_fence_window answers an already-expired plan with
        ErrDeadlineExceeded, commits the live plans, and counts the
        drop."""
        import time as _time

        from nomad_tpu.server.overload import ErrDeadlineExceeded

        broker, fsm, raft, queue, applier = _rig()
        node = mock.node()
        raft.apply(codec.encode(codec.NODE_REGISTER_REQUEST,
                                {"node": node.to_dict()})).wait(5.0)
        live = _outstanding_plan(broker, fsm, raft, node, cpu=100)
        live.deadline = _time.monotonic() + 30.0
        dead = _outstanding_plan(broker, fsm, raft, node, cpu=100)
        dead.deadline = _time.monotonic() - 0.1
        f_live = queue.enqueue(live)
        f_dead = queue.enqueue(dead)
        window = [queue.dequeue(0)] + queue.drain_pending(63)
        try:
            applier._apply_window(window, None)
            with pytest.raises(ErrDeadlineExceeded):
                f_dead.wait(5.0)
            assert f_live.wait(5.0).alloc_index > 0
            assert applier.stats()["expired_drops"] == 1
            assert len(fsm.state.allocs_by_node(node.id)) == 1
        finally:
            applier.shutdown(5.0)
            broker.shutdown()


class TestDispatchFailureOverlay:
    def test_dispatch_failure_drops_phantom_overlay_folds(self):
        """A window whose raft DISPATCH fails has already folded its
        allocs into the applier's optimistic overlay (the verify folds
        before the committer hand-off): the next window
        must verify against a fresh snapshot, not the phantoms — a
        later plan that fits only if the failed window never happened
        must be ACCEPTED."""
        from nomad_tpu import faultinject
        from nomad_tpu.faultinject import FaultPlan

        broker, fsm, raft, queue, applier = _rig()
        node = mock.node()
        raft.apply(codec.encode(codec.NODE_REGISTER_REQUEST,
                                {"node": node.to_dict()})).wait(5.0)
        try:
            full_a = _outstanding_plan(broker, fsm, raft, node,
                                       cpu=FREE_CPU)
            full_b = _outstanding_plan(broker, fsm, raft, node,
                                       cpu=FREE_CPU)
            fplan = FaultPlan.parse("raft.apply=error(count=1)")
            with faultinject.injected(fplan):
                f_a = queue.enqueue(full_a)
                window = [queue.dequeue(0)] + queue.drain_pending(63)
                snap = applier._apply_window(window, None)
                with pytest.raises(Exception):
                    f_a.wait(5.0)  # dispatch failed; flag raised

                # Same node, full capacity again: fits ONLY if the
                # failed window's folds are dropped.  Thread the
                # RETURNED overlay state through, like run() does.
                f_b = queue.enqueue(full_b)
                window = [queue.dequeue(0)] + queue.drain_pending(63)
                applier._apply_window(window, snap)
                assert f_b.wait(5.0).alloc_index > 0, \
                    "phantom folds from a failed dispatch must not " \
                    "reject later plans"
            assert len(fsm.state.allocs_by_node(node.id)) == 1
        finally:
            applier.shutdown(5.0)
            broker.shutdown()

    def test_window_queued_behind_failed_dispatch_is_refused(self):
        """The in-flight variant: window B verifies (and is ACCEPTED)
        against window A's overlay folds while A's dispatch has not
        yet failed, and queues behind A in the committer.  FIFO means
        B's commit job observes A's failure — it must be REFUSED with
        a retryable error (B fits only thanks to A's phantom
        eviction; committing it would durably over-commit the node) —
        and B's retry against refreshed state must see the truth."""
        import threading

        from nomad_tpu import faultinject
        from nomad_tpu.faultinject import FaultPlan

        broker, fsm, raft, queue, applier = _rig()
        applier.max_inflight_commits = 4  # let B queue behind A
        node = mock.node()
        raft.apply(codec.encode(codec.NODE_REGISTER_REQUEST,
                                {"node": node.to_dict()})).wait(5.0)
        existing = make_alloc(node, cpu=FREE_CPU)
        raft.apply(codec.encode(
            codec.ALLOC_UPDATE_REQUEST,
            {"alloc": [existing.to_dict()]})).wait(5.0)
        try:
            # A: token-fenced EVICTION of the full-node alloc.
            ev_a = _outstanding_plan(broker, fsm, raft, node, cpu=1)
            plan_a = Plan(eval_id=ev_a.eval_id,
                          eval_token=ev_a.eval_token, priority=50)
            plan_a.append_update(existing, ALLOC_DESIRED_STATUS_STOP,
                                 "preempted")
            # B: fills the capacity A's eviction would free.
            plan_b = _outstanding_plan(broker, fsm, raft, node,
                                       cpu=FREE_CPU)

            # Hold the committer so BOTH windows queue before either
            # dispatches, then fail A's dispatch.
            gate = threading.Event()
            applier._committer.submit(lambda: gate.wait(10.0))
            fplan = FaultPlan.parse("raft.apply=error(count=1)")
            with faultinject.injected(fplan):
                f_a = queue.enqueue(plan_a)
                window = [queue.dequeue(0)] + queue.drain_pending(63)
                snap = applier._apply_window(window, None)
                f_b = queue.enqueue(plan_b)
                window = [queue.dequeue(0)] + queue.drain_pending(63)
                applier._apply_window(window, snap)
                gate.set()
                with pytest.raises(Exception):
                    f_a.wait(5.0)   # A: dispatch error
                with pytest.raises(RuntimeError, match="retry"):
                    f_b.wait(5.0)   # B: refused, never committed

            # Nothing moved: the existing alloc still owns the node.
            live = [a for a in fsm.state.allocs_by_node(node.id)
                    if not a.terminal_status()]
            assert [a.id for a in live] == [existing.id], \
                "a phantom-verified window must never commit"

            # B's retry sees refreshed truth: the node is still full,
            # so the plan is rejected with a refresh (not placed).
            f_b2 = queue.enqueue(plan_b)
            window = [queue.dequeue(0)] + queue.drain_pending(63)
            applier._apply_window(window, None)
            result = f_b2.wait(5.0)
            assert result.node_allocation == {}
            assert result.refresh_index > 0
        finally:
            applier.shutdown(5.0)
            broker.shutdown()


class TestApplierServiceThreads:
    def test_window_guard_names_every_eval_of_the_window(self,
                                                         monkeypatch):
        """The applier thread arms the ``applier.window`` stall guard
        with an attribution that names the evals of THAT window, the
        fenced-out ones too — what an incident dump of a wedged window
        carries."""
        import contextlib

        import nomad_tpu.server.plan_apply as plan_apply

        armed = []

        @contextlib.contextmanager
        def recording_guard(name, timeout, extra_fn=None):
            armed.append((name, timeout, extra_fn()))
            yield

        monkeypatch.setattr(plan_apply.flight_mod, "guard",
                            recording_guard)
        broker, fsm, raft, queue, applier = _rig()
        node = mock.node()
        raft.apply(codec.encode(codec.NODE_REGISTER_REQUEST,
                                {"node": node.to_dict()})).wait(5.0)
        plans = [_outstanding_plan(broker, fsm, raft, node, cpu=100)
                 for _ in range(3)]
        stray = place_plan(make_alloc(node))  # never outstanding
        futures = [queue.enqueue(p) for p in plans + [stray]]
        applier.start()
        try:
            for f in futures[:3]:
                assert f.wait(5.0).alloc_index > 0
            with pytest.raises(RuntimeError, match="not outstanding"):
                futures[3].wait(5.0)
        finally:
            queue.set_enabled(False)
            applier.shutdown(5.0)
            broker.shutdown()
        assert {name for name, _t, _x in armed} == {"applier.window"}
        assert all(t == applier.WINDOW_STALL_S for _n, t, _x in armed)
        named = [eid for _n, _t, extra in armed
                 for eid in extra["verifying"]["eval_ids"]]
        assert sorted(named) == sorted(p.eval_id for p in plans + [stray])
        assert sum(extra["verifying"]["plans"]
                   for _n, _t, extra in armed) == 4

    def test_shutdown_reaps_the_applier_and_the_committer(self):
        """The applier's two service threads — its own and the
        committer's — are gone after the queue is disabled and
        ``shutdown`` returns."""
        broker, fsm, raft, queue, applier = _rig()
        node = mock.node()
        raft.apply(codec.encode(codec.NODE_REGISTER_REQUEST,
                                {"node": node.to_dict()})).wait(5.0)
        applier.start()
        f = queue.enqueue(_outstanding_plan(broker, fsm, raft, node))
        assert f.wait(5.0).alloc_index > 0
        threads = [applier._thread, applier._committer._thread]
        assert all(t is not None and t.is_alive() for t in threads)
        queue.set_enabled(False)
        applier.shutdown(5.0)
        broker.shutdown()
        assert not any(t.is_alive() for t in threads)

    def test_committer_survives_and_keeps_order(self):
        """FIFO commit order: jobs resolve in submission order even
        when earlier jobs are slower."""
        import threading
        import time as _time

        from nomad_tpu.server.plan_apply import _Committer

        committer = _Committer(name="test-committer")
        order = []
        done = threading.Event()

        def job(k, delay):
            def run():
                _time.sleep(delay)  # sleep-ok: ordering probe
                order.append(k)
                if k == 2:
                    done.set()
            return run

        committer.submit(job(0, 0.05))
        committer.submit(job(1, 0.0))
        committer.submit(job(2, 0.0))
        assert done.wait(5.0)
        assert order == [0, 1, 2]
        committer.stop()
        assert not any(t.name == "test-committer" and t.is_alive()
                       for t in threading.enumerate())


# ---------------------------------------------------------------------------
# 8. the window's claims as columns: the array pass against sequential truth
# ---------------------------------------------------------------------------

def _node_net(node) -> tuple:
    """(ip, device) of a mock node's one network."""
    return node.reserved.networks[0].ip, node.resources.networks[0].device


def slab_allocs(rows, *, cpu=19, mem=39, mbits=1) -> list:
    """Slab-backed allocations as the native finish emits them: one
    AllocSlab of one task group whose task asks ``cpu`` / ``mem`` /
    ``mbits`` and one dynamic port; ``rows`` is [(node, port)]."""
    import numpy as np

    import nomad_tpu.scheduler.jax_binpack as jb
    from nomad_tpu.structs import AllocSlab, Task, TaskGroup

    job = mock.job()
    tg = TaskGroup(name="web", count=len(rows), tasks=[Task(
        name="web", driver="exec", resources=Resources(
            cpu=cpu, memory_mb=mem, networks=[NetworkResource(
                mbits=mbits, dynamic_ports=["http"])]))])
    job.task_groups = [tg]
    n = len(rows)
    slab = AllocSlab(
        eval_id=generate_uuid(), job=job,
        slots=jb.build_slots_c([(Resources(cpu=cpu, memory_mb=mem),
                                 jb._net_plan_for(tg)[1])]),
        metric_proto=dict(jb._METRIC_STATIC, nodes_evaluated=n,
                          allocation_time=0.0),
        groups=[0] * n, ids=[generate_uuid() for _ in range(n)],
        names=[f"{job.id}.web[{r}]" for r in range(n)],
        tgs=["web"] * n, scores=[1.0] * n,
        port_off=np.arange(n + 1, dtype=np.int64), n_rows=n,
        ports=np.asarray([port for _node, port in rows], dtype=np.int32))
    for r, (node, _port) in enumerate(rows):
        slab.node_ids[r] = node.id
        slab.ips[r], slab.devs[r] = _node_net(node)
    slab.seal(n)
    return [slab.alloc(r) for r in range(n)]


def _ordered_key(result: PlanResult) -> tuple:
    """result_key with the dicts' order in it: the accepted portion
    reaches the overlay, the log and the store in that order."""
    return ([(n, [a.id for a in v])
             for n, v in result.node_update.items()],
            [(n, [a.id for a in v])
             for n, v in result.node_allocation.items()],
            [a.id for a in result.failed_allocs],
            result.refresh_index > 0)


def assert_columnar_parity(store: StateStore, plans: list,
                           inflight=()) -> list:
    """The window pass against sequential ``evaluate_plan`` + fold on
    the same snapshot: the same PlanResults, in the same order, and the
    same end overlay.  Returns the outcomes."""
    snap = store.snapshot()
    seq = OptimisticSnapshot(snap)
    seq.upsert_allocs(list(inflight))
    res_seq = []
    for plan in plans:
        result = evaluate_plan(seq, plan)
        res_seq.append(result)
        seq.upsert_allocs(_accepted_allocs(result))
    col = OptimisticSnapshot(snap)
    col.upsert_allocs(list(inflight))
    outcomes = evaluate_window(col, plans)
    assert [_ordered_key(o.result) for o in outcomes] == \
        [_ordered_key(r) for r in res_seq]
    assert list(col._overlay) == list(seq._overlay)
    assert all(col._overlay[k] is seq._overlay[k] for k in col._overlay)
    assert col._by_node == seq._by_node
    return outcomes


def _claims_walked(outcomes) -> list:
    return [(o.claims, o.walked) for o in outcomes]


def _store(nodes) -> StateStore:
    store = StateStore()
    for i, n in enumerate(nodes):
        store.upsert_node(1000 + i, n)
    return store


def _case_bandwidth():
    """The third claim on a node takes its bandwidth past the NIC: the
    node's whole sequence walks, its neighbour stays with the pass."""
    a, b = mock.node(0), mock.node(1)
    store = _store([a, b])
    store.upsert_allocs(1500, slab_allocs([(a, 19999)], mbits=300))
    plans = [place_plan(*slab_allocs([(a, 20000 + k), (b, 20000 + k)],
                                     mbits=300)) for k in range(3)]
    return store, plans, (), [(2, 1)] * 3, [True, True, False]


def _case_window_port():
    """The same dynamic port twice on one node inside the window."""
    a, b = mock.node(0), mock.node(1)
    plans = [place_plan(*slab_allocs([(a, 20000), (b, 20001)])),
             place_plan(*slab_allocs([(a, 20000), (b, 20002)]))]
    return _store([a, b]), plans, (), [(2, 1)] * 2, [True, False]


def _case_live_port():
    """A port a committed allocation already holds on the node."""
    a, b = mock.node(0), mock.node(1)
    store = _store([a, b])
    store.upsert_allocs(1500, slab_allocs([(a, 20000)]))
    plans = [place_plan(*slab_allocs([(a, 20000), (b, 20000)])),
             place_plan(*slab_allocs([(a, 20001), (b, 20001)]))]
    return store, plans, (), [(2, 1)] * 2, [False, True]


def _case_reserved_port():
    """The node's own reserved port (22 on a mock node)."""
    a, b = mock.node(0), mock.node(1)
    plans = [place_plan(*slab_allocs([(a, 22), (b, 20000)]))]
    return _store([a, b]), plans, (), [(2, 1)], [False]


def _case_evict_frees():
    """An eviction frees the capacity a later plan's claim needs."""
    a, b = mock.node(0), mock.node(1)
    store = _store([a, b])
    existing = make_alloc(a, cpu=FREE_CPU)
    store.upsert_allocs(1500, [existing])
    evict = Plan(eval_id=generate_uuid())
    evict.append_update(existing, ALLOC_DESIRED_STATUS_STOP, "gone")
    plans = [evict,
             place_plan(*slab_allocs([(a, 20000), (b, 20000)],
                                     cpu=FREE_CPU))]
    return store, plans, (), [(1, 1), (2, 1)], [True, True]


def _case_all_at_once():
    """An all_at_once plan with a rejection gives its other claims
    back: its whole component walks, the other component does not."""
    a, b, c = mock.node(0), mock.node(1), mock.node(2)
    store = _store([a, b, c])
    store.upsert_allocs(1500, [make_alloc(b, cpu=FREE_CPU)])
    gang = place_plan(*slab_allocs([(a, 20000), (b, 20000)], cpu=2000))
    gang.all_at_once = True
    plans = [gang,
             place_plan(*slab_allocs([(a, 20001)], cpu=2000)),
             place_plan(*slab_allocs([(c, 20000)]))]
    return store, plans, (), [(2, 2), (1, 1), (1, 0)], \
        [False, True, True]


def _case_inflight():
    """An in-flight apply already filled one of the nodes."""
    a, b = mock.node(0), mock.node(1)
    plans = [place_plan(*slab_allocs([(a, 20000), (b, 20000)],
                                     cpu=1000))]
    return _store([a, b]), plans, [make_alloc(a, cpu=FREE_CPU)], \
        [(2, 1)], [False]


def _case_id_twice():
    """One allocation id placed by two plans of the window."""
    a, b = mock.node(0), mock.node(1)
    twice = slab_allocs([(a, 20000)], cpu=1000)
    plans = [place_plan(twice[0], *slab_allocs([(b, 20000)])),
             place_plan(twice[0], *slab_allocs([(b, 20001)]))]
    return _store([a, b]), plans, (), [(2, 1)] * 2, [True, True]


def _case_net_key_odd():
    """An allocation whose tasks' offers span two devices."""
    a, b = mock.node(0), mock.node(1)
    odd = make_alloc(a, cpu=200)
    ip = _node_net(a)[0]
    odd.task_resources = {
        t: Resources(cpu=100, memory_mb=32, networks=[NetworkResource(
            device=dev, ip=ip, mbits=5, reserved_ports=[port])])
        for t, dev, port in (("web", "eth0", 9000), ("db", "eth1", 9001))}
    plans = [place_plan(odd, *slab_allocs([(b, 20000)])),
             place_plan(*slab_allocs([(a, 20000), (b, 20001)]))]
    return _store([a, b]), plans, (), [(2, 1)] * 2, None


def _case_multi_network():
    """A node with two network devices keeps the scalar walk."""
    a, b = mock.node(0), mock.node(1)
    a.resources.networks.append(NetworkResource(
        device="eth1", cidr="10.0.0.1/32", mbits=1000))
    plans = [place_plan(*slab_allocs([(a, 20000), (b, 20000)]))]
    return _store([a, b]), plans, (), [(2, 1)], None


def _case_mixed_backing():
    """Object-backed and slab-backed allocations, in one window and in
    one plan: nothing in it needs the walk."""
    a, b, c = mock.node(0), mock.node(1), mock.node(2)
    plans = [place_plan(net_alloc(a, ports=[9000]),
                        *slab_allocs([(b, 20000)])),
             place_plan(*slab_allocs([(a, 20000), (c, 20000)])),
             place_plan(net_alloc(b, ports=[9001]), make_alloc(c))]
    return _store([a, b, c]), plans, (), [(2, 0)] * 3, \
        [True, True, True]


class TestColumnarWindowParity:
    """The window pass reads the claims as columns and decides what it
    can prove; the rest walks.  Whatever the split, the PlanResults and
    the end overlay are sequential application's."""

    @pytest.mark.parametrize("case", [
        _case_bandwidth, _case_window_port, _case_live_port,
        _case_reserved_port, _case_evict_frees, _case_all_at_once,
        _case_inflight, _case_id_twice, _case_net_key_odd,
        _case_multi_network, _case_mixed_backing,
    ], ids=lambda f: f.__name__[6:])
    @pytest.mark.parametrize("min_claims", [0, None],
                             ids=["pass", "small-window"])
    def test_case_leaves_the_pass_where_it_must(self, case, min_claims,
                                                monkeypatch):
        """Each case through the array pass (the size gate lifted) and,
        as the small window it is, through the walk of every claim."""
        import nomad_tpu.ops.plan_conflict as plan_conflict

        store, plans, inflight, counts, full = case()
        if min_claims is not None:
            monkeypatch.setattr(plan_conflict, "ARRAY_PASS_MIN_CLAIMS",
                                min_claims)
        else:
            assert sum(c for c, _w in counts) < \
                plan_conflict.ARRAY_PASS_MIN_CLAIMS
            counts = [(c, c) for c, _w in counts]
        outcomes = assert_columnar_parity(store, plans, inflight)
        assert _claims_walked(outcomes) == counts
        if full is not None:
            assert [o.result.full_commit(p)[0]
                    for o, p in zip(outcomes, plans)] == full

    @pytest.mark.parametrize("seed, n_plans", [(1, 6), (2, 8), (3, 7)])
    def test_c1m_shaped_window(self, seed, n_plans):
        """C1M's window: slab-backed plans of 1,000 one-placement claims
        on the same 1,000 nodes, which hold up to 199 allocations and
        199 ports each (203 of the ask fit a node), so the last lanes
        are rejected on the nodes that fill.  Sequential truth here
        commits each accepted portion to the store, as the applier's
        raft apply does."""
        import random

        rng = random.Random(29_000 + seed)
        nodes = [mock.node(i) for i in range(1000)]
        store = _store(nodes)
        held = [rng.choice((150, 180, 197, 198, 199)) for _ in nodes]
        for lo in range(0, 1000, 100):
            store.upsert_allocs(1500 + lo, slab_allocs(
                [(node, 20000 + k)
                 for node, n in zip(nodes[lo:lo + 100], held[lo:lo + 100])
                 for k in range(n)]))
        plans = []
        for lane in range(n_plans):
            order = list(range(1000))
            rng.shuffle(order)
            plans.append(place_plan(*slab_allocs(
                [(nodes[k], 30000 + lane) for k in order])))

        col = OptimisticSnapshot(store.snapshot())
        outcomes = evaluate_window(col, plans)
        res_seq = sequential_apply(store, plans, 5000)
        assert [_ordered_key(o.result) for o in outcomes] == \
            [_ordered_key(r) for r in res_seq]
        assert list(col._overlay) == \
            [a.id for r in res_seq for a in _accepted_allocs(r)]
        # A node walks when its sequence holds a rejection: 203 fit.
        walked = sum(1 for n in held if n + n_plans > 203)
        assert walked > 0
        assert _claims_walked(outcomes) == [(1000, walked)] * n_plans
        rejected = [1000 - sum(len(v) for v in
                               o.result.node_allocation.values())
                    for o in outcomes]
        assert rejected == [sum(1 for n in held if n + lane >= 203)
                            for lane in range(n_plans)]

    def test_verify_is_host_code(self):
        """On the suite's multi-device host a window big enough for
        the array pass (>= 512 claims), with claims that walk in it,
        moves nothing across the host/device seam and compiles
        nothing."""
        import jax
        import jax.monitoring as monitoring

        import nomad_tpu.ops.plan_conflict as plan_conflict
        from nomad_tpu.parallel.devices import transfer_counts

        assert len(jax.devices()) > 1
        compiles = []  # listeners cannot be taken off again: keep it cheap
        monitoring.register_event_duration_secs_listener(
            lambda event, _secs, **_kw: compiles.append(event)
            if event.endswith("backend_compile_duration") else None)

        nodes = [mock.node(i) for i in range(300)]
        store = _store(nodes)
        store.upsert_allocs(1500, slab_allocs(
            [(nodes[0], 20000 + k) for k in range(202)]))
        plans = [place_plan(*slab_allocs(
            [(n, 30000 + lane) for n in nodes])) for lane in range(3)]
        assert sum(len(p.node_allocation) for p in plans) >= \
            plan_conflict.ARRAY_PASS_MIN_CLAIMS
        before = transfer_counts()
        n_compiles = len(compiles)
        outcomes = assert_columnar_parity(store, plans)
        assert transfer_counts() == before
        assert len(compiles) == n_compiles
        # 203 of the ask fit a node: node 0 rejects its second and
        # third claim, so its three claims walk; the pass took the rest.
        assert _claims_walked(outcomes) == [(300, 1)] * 3
