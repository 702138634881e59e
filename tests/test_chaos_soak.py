"""Chaos soak on the networked server path.

A multi-worker optimistic eval storm rides REAL RPC (ConnPool -> the
server's mux plane) while nodes heartbeat-expire mid-storm through the
actual TTL-expiry path (HeartbeatManager._invalidate -> node down ->
node-update evals).  After the dust settles, the invariants the
reference guarantees must hold (analogue: nomad/plan_apply_test.go +
worker_test.go):

  1. no node is oversubscribed (exact allocs_fit per node);
  2. the incremental usage mirror equals a from-scratch rebuild;
  3. every evaluation is terminal (none stuck in the broker).

Deterministic job/topology seeds; worker/raft/heartbeat interleaving is
whatever the scheduler actually does under concurrency — the point is
that the invariants hold for EVERY interleaving.
"""
from __future__ import annotations

import time

import numpy as np
import pytest

import nomad_tpu.mock as mock
from nomad_tpu.models.fleet import build_usage, fleet_cache, mirror_for
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.rpc import ConnPool
from nomad_tpu.structs import (
    NODE_STATUS_DOWN,
    NODE_STATUS_READY,
    NetworkResource,
    Resources,
    Task,
    TaskGroup,
    allocs_fit,
)

TERMINAL = ("complete", "failed", "canceled")


def _storm_job(rng, n_groups: int):
    job = mock.job()
    job.task_groups = [
        TaskGroup(name=f"tg-{g}", count=int(rng.integers(1, 3)),
                  tasks=[Task(
                      name="web", driver="exec",
                      resources=Resources(
                          cpu=int(rng.integers(100, 700)),
                          memory_mb=int(rng.integers(32, 256)),
                          networks=[NetworkResource(
                              mbits=int(rng.integers(1, 10)),
                              dynamic_ports=["http"])]),
                  )])
        for g in range(n_groups)]
    return job


@pytest.mark.parametrize("seed", [7, 23])
def test_chaos_storm_with_heartbeat_expiry(seed):
    rng = np.random.default_rng(seed)
    srv = Server(ServerConfig(num_schedulers=4, enable_rpc=True))
    srv.establish_leadership()
    pool = ConnPool()
    try:
        addr = srv.rpc_address()

        # Fleet registered over real RPC (heartbeat TTLs armed).
        n_nodes = 40
        node_ids = []
        for i in range(n_nodes):
            node = mock.node(i)
            out = pool.call(addr, "Node.Register",
                            {"node": node.to_dict()})
            assert out["heartbeat_ttl"] > 0
            node_ids.append(node.id)

        # Optimistic storm: 18 jobs x 12 TGs submitted over RPC; the
        # 4-worker pool processes them concurrently against snapshots.
        eval_ids = []
        job_ids = []
        for _ in range(18):
            job = _storm_job(rng, 12)
            resp = pool.call(addr, "Job.Register",
                            {"job": job.to_dict()})
            eval_ids.append(resp["eval_id"])
            job_ids.append(job.id)

        # Mid-storm chaos: a deterministic subset of nodes misses its
        # heartbeats — the REAL expiry path marks them down and spawns
        # node-update evals that race the in-flight storm.
        time.sleep(0.15)  # sleep-ok: mid-storm pacing before injected expiry
        expire = [node_ids[int(i)] for i in
                  rng.choice(n_nodes, size=10, replace=False)]
        for node_id in expire:
            srv.heartbeats._invalidate(node_id)

        # Drain to quiescence: every eval (the storm's AND the
        # node-update ones the expiries spawn) terminal.  Surviving
        # nodes keep heartbeating while we wait so the real ~20s TTL
        # (min_ttl + grace) can't expire them under a slow run and
        # muddy the deterministic down-set.
        survivors = [nid for nid in node_ids if nid not in set(expire)]
        deadline = time.monotonic() + 55
        last_beat = 0.0
        while time.monotonic() < deadline:
            if time.monotonic() - last_beat > 4.0:
                for nid in survivors:
                    pool.call(addr, "Node.Heartbeat", {"node_id": nid})
                last_beat = time.monotonic()
            evals = srv.fsm.state.evals()
            if evals and all(e.status in TERMINAL for e in evals) and \
                    len(evals) >= len(eval_ids):
                break
            time.sleep(0.2)  # sleep-ok: poll cadence between liveness heartbeats

        state = srv.fsm.state

        # (3) every eval terminal — nothing stuck in the broker.
        stuck = [(e.id, e.status) for e in state.evals()
                 if e.status not in TERMINAL]
        assert not stuck, f"non-terminal evals after soak: {stuck[:5]}"

        # Expired nodes are down; the rest stayed ready.
        downed = {nid for nid in expire}
        for nid in node_ids:
            node = state.node_by_id(nid)
            want = NODE_STATUS_DOWN if nid in downed else NODE_STATUS_READY
            assert node.status == want, (nid, node.status)

        # (1) no oversubscription anywhere, exact accounting.
        total_live = 0
        for nid in node_ids:
            live = [a for a in state.allocs_by_node(nid)
                    if not a.terminal_status() and a.node_id]
            total_live += len(live)
            node = state.node_by_id(nid)
            fit, dim, _util = allocs_fit(node, live)
            assert fit, f"node {nid} oversubscribed on {dim}"
            # Port uniqueness per node (the native finish's contract).
            ports = [p for a in live
                     for tr in a.task_resources.values()
                     for net in tr.networks for p in net.reserved_ports]
            assert len(ports) == len(set(ports)), f"port collision {nid}"
        assert total_live > 0, "storm placed nothing"

        # (2) incremental mirror == from-scratch rebuild.
        snap = state.snapshot()
        statics = fleet_cache.statics_for(snap)
        mirror = mirror_for(statics)
        mirror.sync(snap)  # prime/converge (side effect is the point)
        live = [a for a in snap.allocs() if not a.terminal_status()]
        scratch = build_usage(statics, live, job_id=job_ids[0])
        np.testing.assert_allclose(mirror.usage, scratch.usage,
                                   rtol=0, atol=0)
    finally:
        pool.shutdown()
        srv.shutdown()


def test_chaos_storm_with_drain():
    """Drain-mid-storm soak: nodes drain over real RPC while the worker
    pool is placing; at quiescence drained nodes hold no live allocs,
    nothing is oversubscribed, and the round-5 NET tracking
    (sync_net's incremental port/bandwidth state, which the vectorized
    plan verifier consumed throughout the storm) equals a from-scratch
    rebuild."""
    rng = np.random.default_rng(11)
    srv = Server(ServerConfig(num_schedulers=4, enable_rpc=True))
    srv.establish_leadership()
    pool = ConnPool()
    try:
        addr = srv.rpc_address()
        n_nodes = 30
        node_ids = []
        for i in range(n_nodes):
            node = mock.node(i)
            pool.call(addr, "Node.Register", {"node": node.to_dict()})
            node_ids.append(node.id)

        eval_ids = []
        for _ in range(14):
            job = _storm_job(rng, 10)
            resp = pool.call(addr, "Job.Register",
                             {"job": job.to_dict()})
            eval_ids.append(resp["eval_id"])

        time.sleep(0.1)  # sleep-ok: mid-storm pacing before injected drain
        drained = [node_ids[int(i)] for i in
                   rng.choice(n_nodes, size=8, replace=False)]
        for nid in drained:
            pool.call(addr, "Node.UpdateDrain",
                      {"node_id": nid, "drain": True})

        survivors = [nid for nid in node_ids if nid not in set(drained)]
        deadline = time.monotonic() + 55
        last_beat = 0.0
        while time.monotonic() < deadline:
            if time.monotonic() - last_beat > 4.0:
                for nid in node_ids:
                    pool.call(addr, "Node.Heartbeat", {"node_id": nid})
                last_beat = time.monotonic()
            evals = srv.fsm.state.evals()
            if evals and all(e.status in TERMINAL for e in evals) and \
                    len(evals) >= len(eval_ids):
                break
            time.sleep(0.2)  # sleep-ok: poll cadence between liveness heartbeats

        state = srv.fsm.state
        stuck = [(e.id, e.status) for e in state.evals()
                 if e.status not in TERMINAL]
        assert not stuck, f"non-terminal evals after soak: {stuck[:5]}"

        # A placement can slip onto a draining node inside the
        # applier's optimistic verify window (plan verified against the
        # snapshot taken just before the drain committed — the same
        # window the reference's overlapped verify/apply has,
        # plan_apply.go:68-85).  Drain is ENFORCED by node evals, so a
        # follow-up node evaluation must clear any straggler.
        n_evals = len(srv.fsm.state.evals())
        for nid in drained:
            pool.call(addr, "Node.Evaluate", {"node_id": nid})
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if time.monotonic() - last_beat > 4.0:
                for nid in node_ids:
                    pool.call(addr, "Node.Heartbeat", {"node_id": nid})
                last_beat = time.monotonic()
            evals = srv.fsm.state.evals()
            if len(evals) > n_evals and \
                    all(e.status in TERMINAL for e in evals):
                break
            time.sleep(0.2)  # sleep-ok: poll cadence between liveness heartbeats
        state = srv.fsm.state

        # Drained nodes end empty; survivors are never oversubscribed.
        total_live = 0
        for nid in node_ids:
            live = [a for a in state.allocs_by_node(nid)
                    if not a.terminal_status() and a.node_id]
            if nid in set(drained):
                assert not live, f"drained node {nid} still has allocs"
                continue
            total_live += len(live)
            node = state.node_by_id(nid)
            fit, dim, _util = allocs_fit(node, live)
            assert fit, f"node {nid} oversubscribed on {dim}"
        assert total_live > 0, "storm placed nothing on survivors"

        # Round-5 net tracking: incremental == rebuild after the storm.
        snap = state.snapshot()
        statics = fleet_cache.statics_for(snap)
        mirror = mirror_for(statics)
        assert mirror.sync_net(snap)
        from nomad_tpu.models.fleet import UsageMirror
        fresh = UsageMirror(statics)
        fresh.sync_net(snap)
        assert mirror.net_rows == fresh.net_rows
        assert mirror.node_ports == fresh.node_ports
        assert mirror.node_bw == fresh.node_bw
        assert mirror.node_dup == fresh.node_dup
        np.testing.assert_allclose(mirror.usage, fresh.usage,
                                   rtol=0, atol=0)
    finally:
        pool.shutdown()
        srv.shutdown()


def test_leader_failover_mid_storm():
    """Raft-failover chaos: the leader dies while a storm is in flight;
    the new leader restores the eval broker from replicated state,
    finishes every evaluation, and the committed allocations still
    satisfy exact fit (plans commit atomically through raft, so a
    half-processed storm can never leave torn placements)."""
    from tests.test_raft_net import (
        make_cluster,
        wait_for_stable_leader,
        wait_until,
    )

    servers = make_cluster(3)
    try:
        leader = wait_for_stable_leader(servers)
        nodes = [mock.node(i) for i in range(10)]
        for node in nodes:
            leader.node_register(node)

        rng = np.random.default_rng(11)
        eval_ids = []
        for _ in range(8):
            job = _storm_job(rng, 6)
            _, eid = leader.job_register(job)
            eval_ids.append(eid)

        # Kill the leader immediately: the storm is mid-flight.
        # (Server.shutdown tears down raft + RPC too.)
        survivors = [s for s in servers if s is not leader]
        leader.shutdown()
        for s in survivors:
            s.raft.remove_peer(leader.rpc_address())

        # Load-tolerant: the two survivors may flap leadership for a
        # while when the host is starving their tickers — wait for a
        # leader that HOLDS, with a generous bar (this soak proves
        # convergence invariants, not election latency).
        wait_for_stable_leader(survivors, timeout=60)

        # Every raft-committed eval must reach a terminal status on a
        # survivor's replica (the broker restores from replicated
        # state on WHICHEVER survivor currently leads — a mid-wait
        # re-flap must not fail the check, so read both replicas).
        def all_terminal():
            for s in survivors:
                state = s.fsm.state
                evs = [state.eval_by_id(eid) for eid in eval_ids]
                if all(e is not None and e.status in TERMINAL
                       for e in evs):
                    return True
            return False
        wait_until(all_terminal, timeout=90,
                   msg="storm evals terminal on a survivor")

        # Committed placements satisfy exact fit on every node, on every
        # survivor's replica.
        for s in survivors:
            state = s.fsm.state
            for node in nodes:
                live = [a for a in state.allocs_by_node(node.id)
                        if not a.terminal_status() and a.node_id]
                fit, dim, _ = allocs_fit(state.node_by_id(node.id), live)
                assert fit, f"node {node.id} oversubscribed on {dim}"
        # Replicas agree on the alloc set (load-tolerant bar: replication
        # to the trailing survivor rides the same starved tickers).
        def alloc_ids(s):
            return frozenset(a.id for a in s.fsm.state.allocs())
        wait_until(lambda: alloc_ids(survivors[0]) == alloc_ids(
            survivors[1]), timeout=60, msg="replicas agree on allocs")
    finally:
        for s in servers:
            try:
                s.shutdown()
            except Exception:
                pass
