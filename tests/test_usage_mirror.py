"""UsageMirror: the incremental state->HBM usage bridge.

Verifies the mirror stays exactly equal to a from-scratch build_usage
through every kind of store delta (upserts, client updates, reaps,
changelog compaction, snapshot restore), that it does O(changed) work
(no full rebuilds once primed), that plan-delta views match the
_proposed_allocs_all path, and that the device-resident copy tracks the
host arrays through scatter maintenance.

Reference analogue: the alloc feed of nomad/state/state_store.go:115-156;
SURVEY.md section 7 "Incremental device state".
"""
from __future__ import annotations

import numpy as np
import pytest

import nomad_tpu.mock as mock
from nomad_tpu.models.fleet import (
    UsageMirror,
    build_fleet,
    build_usage,
    fleet_cache,
    mirror_for,
)
from nomad_tpu.state import StateStore
from nomad_tpu.structs import (
    ALLOC_CLIENT_STATUS_FAILED,
    Allocation,
    NetworkResource,
    Plan,
    Resources,
    generate_uuid,
)


def _mk_store(n_nodes: int = 8):
    store = StateStore()
    nodes = []
    for i in range(n_nodes):
        n = mock.node(i)
        nodes.append(n)
        store.upsert_node(i + 1, n)
    return store, nodes


def _alloc(node_id: str, job_id: str = "j1", cpu: int = 500,
           mem: int = 256) -> Allocation:
    return Allocation(
        id=generate_uuid(), node_id=node_id, job_id=job_id,
        resources=Resources(cpu=cpu, memory_mb=mem),
    )


def _assert_mirror_matches(mirror: UsageMirror, store, job_id: str = "j1"):
    """Mirror state must equal a from-scratch aggregation of the store."""
    live = [a for a in store.allocs() if not a.terminal_status()]
    scratch = build_usage(mirror.statics, live, job_id=job_id)
    np.testing.assert_allclose(mirror.usage, scratch.usage)
    dense = np.zeros(mirror.statics.n_pad, dtype=np.int32)
    for ni, c in mirror.job_counts.get(job_id, {}).items():
        dense[ni] = c
    np.testing.assert_array_equal(dense, scratch.job_counts)
    # alloc_rows tracks exactly the live allocs on known nodes.
    expect_rows = {a.id for a in live
                   if a.node_id in mirror.statics.index_of}
    assert set(mirror.alloc_rows) == expect_rows


def test_sync_through_upsert_update_delete():
    store, nodes = _mk_store()
    statics = build_fleet(list(store.nodes()))
    mirror = UsageMirror(statics)
    assert mirror.sync(store)
    _assert_mirror_matches(mirror, store)
    assert mirror.rebuilds == 1

    # Upserts land incrementally.
    a1 = _alloc(nodes[0].id)
    a2 = _alloc(nodes[1].id, job_id="j2")
    store.upsert_allocs(10, [a1, a2])
    assert mirror.sync(store)
    _assert_mirror_matches(mirror, store)

    # Client update to terminal removes the contribution.
    done = a1.copy()
    done.client_status = ALLOC_CLIENT_STATUS_FAILED
    store.update_alloc_from_client(11, done)
    assert mirror.sync(store)
    _assert_mirror_matches(mirror, store)

    # Replacing an alloc's node moves its usage row.
    moved = a2.copy()
    moved.node_id = nodes[2].id
    store.upsert_allocs(12, [moved])
    assert mirror.sync(store)
    _assert_mirror_matches(mirror, store)
    _assert_mirror_matches(mirror, store, job_id="j2")

    # Reap (delete_eval with alloc ids) drops rows.
    store.delete_eval(13, [], [a2.id])
    assert mirror.sync(store)
    _assert_mirror_matches(mirror, store, job_id="j2")
    # Everything above was incremental: exactly the one initial rebuild.
    assert mirror.rebuilds == 1


def test_sync_survives_changelog_compaction():
    store, nodes = _mk_store()
    statics = build_fleet(list(store.nodes()))
    mirror = UsageMirror(statics)
    mirror.sync(store)

    # Force the changelog past its compaction bound while the mirror
    # isn't watching; the gap exceeds the retained log so sync must
    # detect it and rebuild, not silently under-apply.
    n_writes = StateStore._ALLOC_LOG_MAX + 10
    for i in range(n_writes):
        store.upsert_allocs(100 + i, [_alloc(nodes[i % len(nodes)].id)])
    assert mirror.sync(store)
    _assert_mirror_matches(mirror, store)
    assert mirror.rebuilds == 2  # initial + post-compaction


def test_sync_incremental_when_log_covers_gap():
    store, nodes = _mk_store()
    statics = build_fleet(list(store.nodes()))
    mirror = UsageMirror(statics)
    mirror.sync(store)
    for i in range(200):
        store.upsert_allocs(10 + i, [_alloc(nodes[i % len(nodes)].id)])
        assert mirror.sync(store)
    _assert_mirror_matches(mirror, store)
    assert mirror.rebuilds == 1


def test_mirror_is_monotonic_old_snapshot_refused():
    store, nodes = _mk_store()
    statics = build_fleet(list(store.nodes()))
    mirror = UsageMirror(statics)
    old_snap = store.snapshot()
    store.upsert_allocs(10, [_alloc(nodes[0].id)])
    assert mirror.sync(store)
    # A snapshot from before the mirror's fence cannot be served.
    assert not mirror.sync(old_snap)
    _assert_mirror_matches(mirror, store)


def test_view_applies_plan_deltas():
    store, nodes = _mk_store()
    statics = build_fleet(list(store.nodes()))
    mirror = UsageMirror(statics)
    a1 = _alloc(nodes[0].id)
    a2 = _alloc(nodes[1].id)
    store.upsert_allocs(10, [a1, a2])
    mirror.sync(store)

    plan = Plan()
    plan.append_update(a1, "stop", "")
    placed = _alloc(nodes[3].id)
    plan.append_alloc(placed)

    view = mirror.view(plan, "j1")
    # Equivalent from-scratch: existing minus evictions plus placements.
    proposed = [a2, placed]
    scratch = build_usage(statics, proposed, job_id="j1")
    np.testing.assert_allclose(view.usage, scratch.usage)
    np.testing.assert_array_equal(view.job_counts, scratch.job_counts)
    # Plan-delta views are private copies with no resident device copy.
    assert view.usage_device is None
    # The mirror's own arrays were not touched (copy-on-write).
    _assert_mirror_matches(mirror, store)


def test_view_without_deltas_shares_device_copy():
    store, nodes = _mk_store()
    statics = build_fleet(list(store.nodes()))
    mirror = UsageMirror(statics)
    store.upsert_allocs(10, [_alloc(nodes[0].id)])
    mirror.sync(store)
    view = mirror.view(Plan(), "j1")
    assert view.usage_device is not None
    np.testing.assert_allclose(np.asarray(view.usage_device), view.usage)
    assert view.dispatch_usage() is view.usage_device


def test_device_copy_tracks_scatter_maintenance():
    store, nodes = _mk_store()
    statics = build_fleet(list(store.nodes()))
    mirror = UsageMirror(statics)
    mirror.sync(store)
    d0 = mirror.device_usage()
    for i in range(20):
        store.upsert_allocs(10 + i, [_alloc(nodes[i % len(nodes)].id)])
        mirror.sync(store)
        np.testing.assert_allclose(np.asarray(mirror.device_usage()),
                                   mirror.usage)
    # No donation: the first handed-out buffer is still readable.
    np.testing.assert_allclose(np.asarray(d0),
                               np.zeros_like(mirror.usage))


def test_views_frozen_under_later_syncs():
    store, nodes = _mk_store()
    statics = build_fleet(list(store.nodes()))
    mirror = UsageMirror(statics)
    store.upsert_allocs(10, [_alloc(nodes[0].id)])
    mirror.sync(store)
    view = mirror.view(None, "j1")
    before = view.usage.copy()
    for i in range(5):
        store.upsert_allocs(11 + i, [_alloc(nodes[1].id)])
        mirror.sync(store)
    np.testing.assert_allclose(view.usage, before)


def test_restore_forces_rebuild():
    store, nodes = _mk_store()
    statics = build_fleet(list(store.nodes()))
    mirror = UsageMirror(statics)
    store.upsert_allocs(10, [_alloc(nodes[0].id)])
    mirror.sync(store)
    assert mirror.rebuilds == 1

    # Snapshot-restore rebuilds the store wholesale; the changelog base
    # moves past the mirror so it must rebuild.
    restore = store.restore()
    for n in store.nodes():
        restore.node_restore(n)
    restore.alloc_restore(_alloc(nodes[2].id))
    restore.index_restore("allocs", 50)
    restore.commit()
    assert mirror.sync(store)
    assert mirror.rebuilds == 2
    _assert_mirror_matches(mirror, store)
    # ... and exactly once: repeated syncs of the restored (quiet) state
    # must be no-ops, not rebuild thrash (code-review regression).
    for _ in range(5):
        assert mirror.sync(store)
    assert mirror.rebuilds == 2

    # A restore that lands on the SAME allocs index still forces one
    # rebuild (the world changed wholesale even though the index didn't).
    restore2 = store.restore()
    for n in store.nodes():
        restore2.node_restore(n)
    restore2.alloc_restore(_alloc(nodes[3].id))
    restore2.index_restore("allocs", 50)
    restore2.commit()
    assert mirror.sync(store)
    assert mirror.rebuilds == 3
    _assert_mirror_matches(mirror, store)


def test_scheduler_path_uses_mirror_o_changed(monkeypatch):
    """1k sequential evals against a growing store do O(changed) host
    work: the mirror rebuilds once and the O(allocs) fallback
    (_proposed_allocs_all) is never taken."""
    from nomad_tpu.scheduler import Harness
    from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler

    calls = {"fallback": 0}
    orig = JaxBinPackScheduler._proposed_allocs_all

    def counting(self):
        calls["fallback"] += 1
        return orig(self)

    monkeypatch.setattr(JaxBinPackScheduler, "_proposed_allocs_all",
                        counting)

    h = Harness()
    for i in range(16):
        h.state.upsert_node(h.next_index(), mock.node(i))
    n_evals = 50
    jobs = []
    for _ in range(n_evals):
        j = mock.job()
        j.task_groups[0].count = 1
        h.state.upsert_job(h.next_index(), j)
        jobs.append(j)

    from nomad_tpu.structs import (
        EVAL_TRIGGER_JOB_REGISTER,
        Evaluation,
    )
    for j in jobs:
        ev = Evaluation(
            id=generate_uuid(), priority=50, type="service",
            triggered_by=EVAL_TRIGGER_JOB_REGISTER, job_id=j.id)
        h.process("jax-binpack", ev)

    assert calls["fallback"] == 0
    statics = fleet_cache.statics_for(h.state)
    assert statics.mirror is not None
    assert statics.mirror.rebuilds <= 1
    # And the plans actually placed (the path was live, not short-circuited).
    assert len(h.plans) == n_evals


def test_mirror_for_is_singleton():
    store, _nodes = _mk_store()
    statics = build_fleet(list(store.nodes()))
    assert mirror_for(statics) is mirror_for(statics)


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-v"]))


def test_scatter_rows_pads_to_pow2_and_stays_exact():
    """_scatter_rows pads every batch to a power-of-two row count (the
    jit would otherwise recompile per distinct delta size) with no-op
    rewrites — results must equal a plain numpy row assignment for odd,
    even, single and empty batches."""
    import jax
    import numpy as np
    from nomad_tpu.models.fleet import _scatter_rows

    base = np.arange(40, dtype=np.float32).reshape(10, 4)
    usage_d = jax.device_put(base)
    rng = np.random.default_rng(7)
    for n in (0, 1, 2, 3, 5, 7, 10):
        idx = rng.choice(10, size=n, replace=False).astype(np.int64) \
            if n else np.zeros(0, dtype=np.int64)
        rows = rng.normal(size=(n, 4)).astype(np.float32)
        want = np.asarray(usage_d).copy()
        want[idx] = rows
        usage_d = _scatter_rows(usage_d, idx, rows)
        np.testing.assert_array_equal(np.asarray(usage_d), want)


# ---------------------------------------------------------------------------
# net_occupancy: what the scheduler's finish seeds a node's ports and
# bandwidth from (scheduler/jax_binpack.FastPlacementMixin._node_net_init)
# ---------------------------------------------------------------------------

def _net_alloc(node, offers, terminal=False) -> Allocation:
    """One alloc on ``node``; ``offers`` is one (ip | None = the node's
    own, device, ports, mbits) per task."""
    own_ip = node.reserved.networks[0].ip
    a = Allocation(
        id=generate_uuid(), node_id=node.id, job_id="net",
        resources=Resources(cpu=1, memory_mb=1),
        task_resources={
            f"t{i}": Resources(cpu=1, memory_mb=1, networks=[
                NetworkResource(device=dev, ip=ip or own_ip, mbits=mb,
                                reserved_ports=list(ports))])
            for i, (ip, dev, ports, mb) in enumerate(offers)})
    if terminal:
        a.client_status = ALLOC_CLIENT_STATUS_FAILED
    return a


def _walked(store, node) -> tuple:
    """What the finish's walk of the node's live allocs collects."""
    ports: set = set()
    mbits = 0
    for a in store.allocs_by_node(node.id):
        if a.terminal_status():
            continue
        for tr in a.task_resources.values():
            for offer in tr.networks:
                ports.update(offer.reserved_ports)
                mbits += offer.mbits
    return ports, mbits


def test_net_occupancy_equals_the_walk_on_plain_nodes():
    store, nodes = _mk_store(4)
    statics = build_fleet(list(store.nodes()))
    mirror = UsageMirror(statics)
    store.upsert_allocs(20, [
        _net_alloc(nodes[0], [(None, "eth0", [21000, 21001], 5)]),
        _net_alloc(nodes[0], [(None, "eth0", [21002], 7),
                              (None, "eth0", [], 3)]),
        _net_alloc(nodes[1], [(None, "eth0", [30000], 1)]),
        _net_alloc(nodes[1], [(None, "eth0", [30001], 9)], terminal=True),
        _alloc(nodes[2].id),  # no network at all
    ])
    # First call switches net tracking on (one rebuild of the net
    # dicts), brings the mirror to the state, and serves every node
    # asked for — the empty ones with an empty occupancy.
    occ = mirror.net_occupancy(store, [0, 1, 2, 3, 3, -1])
    assert set(occ) == {0, 1, 2, 3}
    for ni in range(4):
        ports, mbits = _walked(store, nodes[ni])
        assert (set(occ[ni][0]), occ[ni][1]) == (ports, mbits)
        assert len(occ[ni][0]) == len(ports)
    assert occ[2] == occ[3] == (frozenset(), 0)
    # Deltas keep it exact: one alloc goes terminal, one arrives.
    gone = store.allocs_by_node(nodes[0].id)[0].copy()
    gone.client_status = ALLOC_CLIENT_STATUS_FAILED
    store.update_alloc_from_client(21, gone)
    store.upsert_allocs(22, [_net_alloc(nodes[3],
                                        [(None, "eth0", [40000], 2)])])
    occ = mirror.net_occupancy(store, range(4))
    for ni in range(4):
        ports, mbits = _walked(store, nodes[ni])
        assert (set(occ[ni][0]), occ[ni][1]) == (ports, mbits)
    assert mirror.rebuilds == 1
    # A copy, not a view: later syncs do not reach into it.
    store.upsert_allocs(23, [_net_alloc(nodes[3],
                                        [(None, "eth0", [40001], 2)])])
    mirror.sync(store)
    assert occ[3][0] == {40000}


def test_net_occupancy_leaves_out_what_needs_the_walk():
    store, nodes = _mk_store(6)
    multi = nodes[4]
    multi.resources.networks.append(NetworkResource(
        device="eth1", cidr="10.0.0.1/32", mbits=1000))
    store.upsert_node(50, multi)
    statics = build_fleet(list(store.nodes()))
    mirror = UsageMirror(statics)
    index_of = statics.index_of
    store.upsert_allocs(60, [
        # offers spanning two devices: NET_KEY_ODD
        _net_alloc(nodes[0], [(None, "eth0", [21000], 1),
                              (None, "eth1", [21001], 1)]),
        # an offer off the node's own network
        _net_alloc(nodes[1], [("10.9.9.9", "eth0", [21000], 1)]),
        # one port held twice
        _net_alloc(nodes[2], [(None, "eth0", [21000], 1)]),
        _net_alloc(nodes[2], [(None, "eth0", [21000], 1)]),
        # a task with two networks: the walk counts both, the mirror one
        Allocation(
            id=generate_uuid(), node_id=nodes[3].id, job_id="net",
            resources=Resources(cpu=1, memory_mb=1),
            task_resources={"t": Resources(cpu=1, memory_mb=1, networks=[
                NetworkResource(device="eth0", mbits=1,
                                ip=nodes[3].reserved.networks[0].ip,
                                reserved_ports=[21000]),
                NetworkResource(device="eth0", mbits=1,
                                ip=nodes[3].reserved.networks[0].ip,
                                reserved_ports=[21001])])}),
        # a multi-network node (net_base_for -> None)
        _net_alloc(multi, [(None, "eth0", [21000], 1)]),
        # and a plain one
        _net_alloc(nodes[5], [(None, "eth0", [21000], 1)]),
    ])
    occ = mirror.net_occupancy(store, range(6))
    assert set(occ) == {index_of[nodes[5].id]}
    assert occ[index_of[nodes[5].id]] == ({21000}, 1)


def test_net_occupancy_serves_only_the_exact_snapshot():
    store, nodes = _mk_store(2)
    statics = build_fleet(list(store.nodes()))
    mirror = UsageMirror(statics)
    store.upsert_allocs(20, [_net_alloc(nodes[0],
                                        [(None, "eth0", [21000], 1)])])
    old = store.snapshot()
    store.upsert_allocs(21, [_net_alloc(nodes[0],
                                        [(None, "eth0", [21001], 1)])])
    # A lagging mirror is brought up to the state asked for ...
    assert mirror.net_occupancy(old, [0]) == {0: ({21000}, 1)}
    assert mirror.index == 20
    new = mirror.net_occupancy(store, [0])
    assert set(new[0][0]) == {21000, 21001} and new[0][1] == 2
    # ... and one that has moved past it serves nothing: the walk does.
    assert mirror.net_occupancy(old, [0]) == {}
    assert mirror.index == 21
