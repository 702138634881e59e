"""Multi-server raft tests: election, replication, forwarding, failover.

Parity with the reference's in-process multi-server integration rig
(nomad/server_test.go testServer + testJoin): full servers on loopback
ports with aggressively tightened raft timings.
"""
from __future__ import annotations

import time

import pytest

import nomad_tpu.mock as mock
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.rpc import ConnPool

from tests.conftest import wait_until

FAST = dict(
    raft_mode="net",
    raft_election_timeout=(0.05, 0.10),
    raft_heartbeat_interval=0.02,
    num_schedulers=1,
)


def make_cluster(n: int):
    servers = [Server(ServerConfig(**FAST)) for _ in range(n)]
    addrs = [s.rpc_address() for s in servers]
    for s in servers:
        for a in addrs:
            s.raft.add_peer(a)
    return servers


def wait_for_leader(servers, timeout=5.0) -> Server:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        leaders = [s for s in servers if s.raft.is_leader()]
        if len(leaders) == 1 and leaders[0].is_leader():
            return leaders[0]
        time.sleep(0.02)  # sleep-ok: poll interval of the bounded wait
    raise AssertionError("no single leader elected")


def wait_for_stable_leader(servers, timeout=30.0,
                           stable_polls=5) -> Server:
    """A leader that HOLDS leadership across ``stable_polls``
    consecutive observations.  Under host load, election RPCs and
    ticker threads get starved and leadership can flap between
    wait_for_leader's single-instant polls — the documented chaos-soak
    leader-flap flake.  The soak tests need a leader that survived a
    whole observation window, with a load-tolerant deadline, not a
    lucky single sample."""
    deadline = time.monotonic() + timeout
    candidate, streak = None, 0
    while time.monotonic() < deadline:
        leaders = [s for s in servers if s.raft.is_leader()]
        if len(leaders) == 1 and leaders[0].is_leader():
            if leaders[0] is candidate:
                streak += 1
                if streak >= stable_polls:
                    return candidate
            else:
                candidate, streak = leaders[0], 1
        else:
            candidate, streak = None, 0
        time.sleep(0.05)  # sleep-ok: poll interval of the bounded wait
    raise AssertionError("no stable single leader within "
                         f"{timeout}s (last candidate {candidate})")


@pytest.fixture
def pool():
    p = ConnPool()
    yield p
    p.shutdown()


def test_single_node_self_elects():
    s = Server(ServerConfig(**FAST))
    try:
        wait_until(lambda: s.raft.is_leader() and s.is_leader(),
                   msg="self-election")
    finally:
        s.shutdown()
        s.raft.shutdown()


def test_three_node_election_and_replication(pool):
    servers = make_cluster(3)
    try:
        leader = wait_for_leader(servers)
        node = mock.node()
        leader.node_register(node)
        wait_until(
            lambda: all(s.fsm.state.node_by_id(node.id) is not None
                        for s in servers),
            msg="replication to all followers")
    finally:
        for s in servers:
            s.shutdown()
            s.raft.shutdown()


def _call_retry(pool, addr, method, args, timeout=10.0):
    """RPC with retry across leadership churn: the tight test timings
    (50-100ms elections) can drop leadership mid-call under host load;
    real clients retry exactly like this."""
    from nomad_tpu.server.rpc import RPCError

    deadline = time.monotonic() + timeout
    while True:
        try:
            return pool.call(addr, method, args)
        except RPCError:
            if time.monotonic() >= deadline:
                raise
            time.sleep(0.1)  # sleep-ok: poll interval of the bounded retry


def test_follower_forwards_writes(pool):
    servers = make_cluster(3)
    try:
        wait_for_leader(servers)
        follower = next(s for s in servers if not s.raft.is_leader())
        for i in range(3):
            _call_retry(pool, follower.rpc_address(), "Node.Register",
                        {"node": mock.node(i).to_dict()})
        job = mock.job()
        job.task_groups[0].count = 3
        out = _call_retry(pool, follower.rpc_address(), "Job.Register",
                          {"job": job.to_dict()})
        assert out["eval_id"]
        # Eval completion may migrate across a mid-test re-election;
        # watch replicated state rather than one server's broker.
        wait_until(
            lambda: all(len(s.fsm.state.allocs_by_job(job.id)) == 3
                        for s in servers),
            timeout=20, msg="alloc replication")
    finally:
        for s in servers:
            s.shutdown()
            s.raft.shutdown()


def test_stale_reads_serve_locally_on_follower(pool):
    """A read with ``stale`` set is answered from the follower's own
    snapshot — never forwarded (reference nomad/rpc.go forward +
    structs.QueryOptions.AllowStale).  Non-stale follower reads forward
    to the leader."""
    servers = make_cluster(3)
    try:
        leader = wait_for_leader(servers)
        node = mock.node()
        leader.node_register(node)
        follower = next(s for s in servers if not s.raft.is_leader())
        wait_until(lambda: follower.fsm.state.node_by_id(node.id)
                   is not None, msg="replication to follower")

        # Any forward attempt from the follower must blow up loudly.
        def boom(*a, **kw):
            raise AssertionError("stale read was forwarded")
        orig_call = follower.conn_pool.call
        follower.conn_pool.call = boom
        try:
            out = pool.call(follower.rpc_address(), "Node.GetNode",
                            {"node_id": node.id, "stale": True})
            assert out["node"]["id"] == node.id
            assert out["known_leader"] is True
            # Without stale, the same read needs the leader: the
            # sabotaged pool surfaces as an RPC error.
            from nomad_tpu.server.rpc import RPCError
            with pytest.raises(RPCError):
                pool.call(follower.rpc_address(), "Node.GetNode",
                          {"node_id": node.id})
        finally:
            follower.conn_pool.call = orig_call
    finally:
        for s in servers:
            s.shutdown()
            s.raft.shutdown()


def test_leader_failover():
    from nomad_tpu.structs import Evaluation, generate_uuid

    servers = make_cluster(3)
    try:
        leader = wait_for_leader(servers)
        node = mock.node()
        leader.node_register(node)
        # A committed pending eval of a scheduler type no worker
        # consumes: it must survive the failover INSIDE the new
        # leader's broker (leadership-restore re-enqueue), not just in
        # state.
        parked_eval = Evaluation(
            id=generate_uuid(), priority=50, type="exotic",
            triggered_by="test", job_id="parked-job", status="pending")
        leader.apply_eval_update([parked_eval])

        # Kill the leader: remaining two must elect a new one.
        survivors = [s for s in servers if s is not leader]
        leader.shutdown()
        leader.raft.shutdown()
        leader.rpc_server.shutdown()
        for s in survivors:
            s.raft.remove_peer(leader.rpc_address())

        new_leader = wait_for_leader(survivors, timeout=10)
        assert new_leader is not leader
        # Replicated state survives the failover; prior-term entries apply
        # once the new leader commits its own-term no-op.
        wait_until(
            lambda: new_leader.fsm.state.node_by_id(node.id) is not None,
            msg="committed entry visible on new leader")
        # ISSUE 8 satellite: post-failover leader bring-up actually
        # repopulates the leader-only machinery on the NEW leader —
        # HeartbeatManager.initialize re-arms every live node at the
        # failover TTL, and the broker restore re-enqueues the
        # committed pending eval.
        wait_until(lambda: new_leader.heartbeats.active() >= 1,
                   msg="heartbeat timers re-armed on new leader")
        wait_until(
            lambda: any(e.id == parked_eval.id
                        for q in new_leader.eval_broker._ready.values()
                        for *_prio, e in q._heap),
            msg="pending eval restored into new leader's broker")
        # And the new leader can make progress.
        node2 = mock.node(2)
        new_leader.node_register(node2)
        wait_until(
            lambda: all(s.fsm.state.node_by_id(node2.id) is not None
                        for s in survivors),
            msg="post-failover replication")
    finally:
        for s in servers:
            try:
                s.shutdown()
                s.raft.shutdown()
            except Exception:
                pass


def test_net_raft_durability(tmp_path):
    """Term/vote metadata and log entries survive a restart (raft safety)."""
    cfg = dict(FAST)
    cfg["data_dir"] = str(tmp_path)
    s = Server(ServerConfig(**cfg))
    try:
        wait_until(lambda: s.raft.is_leader(), msg="election")
        node = mock.node()
        s.node_register(node)
        term_before = s.raft._term
    finally:
        s.shutdown()

    s2 = Server(ServerConfig(**cfg))
    try:
        # Persisted term is restored (never moves backwards).
        assert s2.raft._term >= term_before
        wait_until(lambda: s2.raft.is_leader(), msg="re-election")
        # Replayed log is reapplied once the new term commits.
        wait_until(lambda: s2.fsm.state.node_by_id(node.id) is not None,
                   msg="log replay apply")
    finally:
        s2.shutdown()


def test_net_raft_compaction_survives_restart(tmp_path):
    """Log compaction persists the snapshot to disk: a full restart after
    the durable log was truncated must restore the FSM from the snapshot
    file, not silently come up empty (reference FileSnapshotStore role)."""
    cfg = dict(FAST)
    cfg["data_dir"] = str(tmp_path)
    cfg["raft_snapshot_threshold"] = 8
    s = Server(ServerConfig(**cfg))
    nodes = [mock.node(i) for i in range(12)]
    try:
        wait_until(lambda: s.raft.is_leader(), msg="election")
        for n in nodes:
            s.node_register(n)
        # Enough applies to cross the threshold and truncate the log.
        wait_until(lambda: s.raft._log_base_index > 0, msg="compaction")
    finally:
        s.shutdown()

    s2 = Server(ServerConfig(**cfg))
    try:
        # State is restored from the persisted snapshot immediately (the
        # truncated log alone can no longer rebuild it).
        assert s2.raft._last_applied >= 8
        wait_until(lambda: s2.raft.is_leader(), msg="re-election")
        wait_until(
            lambda: all(s2.fsm.state.node_by_id(n.id) is not None
                        for n in nodes),
            msg="full state after snapshot restore + log tail replay")
    finally:
        s2.shutdown()


class _StubRPC:
    address = ("127.0.0.1", 0)

    def register(self, name, fn):
        pass


class _RecordingFSM:
    def __init__(self):
        self.applied = []

    def apply(self, index, data):
        self.applied.append((index, bytes(data)))

    def snapshot(self):
        return b"snap"

    def restore(self, blob):
        pass


def test_net_raft_replay_is_last_writer_wins(tmp_path):
    """A record re-appended at an existing index marks a follower conflict
    truncation; boot replay must take the LAST record per index or stale
    (possibly uncommitted) entries resurrect under committed ones."""
    from nomad_tpu.server.raft import FileLogStore
    from nomad_tpu.server.raft_net import NetRaft

    store = FileLogStore(str(tmp_path / "raft" / "log.bin"))
    store.append(1, {"t": 1, "d": b"a"})
    store.append(2, {"t": 1, "d": b"stale"})
    store.append(3, {"t": 1, "d": b"stale2"})
    # Conflict truncation at index 2: leader of term 2 rewrites the suffix.
    store.append(2, {"t": 2, "d": b"B"})
    store.append(3, {"t": 2, "d": b"C"})
    store.append(4, {"t": 2, "d": b"D"})
    store.close()

    raft = NetRaft(_RecordingFSM(), _StubRPC(), None,
                   election_timeout=(30.0, 60.0),
                   data_dir=str(tmp_path))
    try:
        log = [(e["index"], e["term"], bytes(e["data"])) for e in raft._log]
        assert log == [(1, 1, b"a"), (2, 2, b"B"), (3, 2, b"C"),
                       (4, 2, b"D")]
    finally:
        raft.shutdown()


def test_inmem_raft_append_before_apply(tmp_path):
    """Entries are persisted BEFORE the FSM applies them (raft
    discipline, reference raft-boltdb ordering): a failing apply consumes
    its index and leaves a poisoned entry that boot replay skips; the
    in-memory FSM can never run ahead of the durable log."""
    from nomad_tpu.server.raft import FileLogStore, InmemRaft

    class FSM(_RecordingFSM):
        def apply(self, index, data):
            if data == b"boom":
                raise RuntimeError("bad entry")
            super().apply(index, data)

    path = str(tmp_path / "log.bin")
    raft = InmemRaft(FSM(), FileLogStore(path))
    raft.apply(b"one").wait(1)
    bad = raft.apply(b"boom")
    assert bad.error is not None
    raft.apply(b"two").wait(1)
    assert raft.applied_index() == 3
    raft.log_store.close()

    fsm2 = FSM()
    raft2 = InmemRaft(fsm2, FileLogStore(path))
    assert [d for _, d in fsm2.applied] == [b"one", b"two"]
    assert raft2.applied_index() == 3
    raft2.log_store.close()


def test_inmem_raft_disk_failure_rejects_before_apply(tmp_path):
    """A failing durable append rejects the entry with NO state moved:
    the FSM is untouched and the index is not consumed."""
    from nomad_tpu.server.raft import FileLogStore, InmemRaft

    class FlakyLog(FileLogStore):
        fail = False

        def append_many(self, records):
            if self.fail:
                raise OSError("disk full")
            super().append_many(records)

    fsm = _RecordingFSM()
    log = FlakyLog(str(tmp_path / "log.bin"))
    raft = InmemRaft(fsm, log)
    raft.apply(b"one").wait(1)
    log.fail = True
    fut = raft.apply(b"lost")
    assert isinstance(fut.error, OSError)
    assert raft.applied_index() == 1
    assert [d for _, d in fsm.applied] == [b"one"]
    log.fail = False
    raft.apply(b"two").wait(1)
    assert [d for _, d in fsm.applied] == [b"one", b"two"]
    log.close()


def test_log_rewrite_is_atomic_replacement(tmp_path):
    """FileLogStore.rewrite replaces the log via tmp+rename and appends
    keep working afterwards."""
    import os

    from nomad_tpu.server.raft import FileLogStore

    path = str(tmp_path / "log.bin")
    log = FileLogStore(path)
    for i in range(1, 6):
        log.append(i, f"e{i}".encode())
    log.rewrite((i, f"e{i}".encode()) for i in (4, 5))
    log.append(6, b"e6")
    log.close()
    assert not os.path.exists(path + ".tmp")
    replayed = list(FileLogStore(path).replay())
    assert [(i, bytes(d)) for i, d in replayed] == \
        [(4, b"e4"), (5, b"e5"), (6, b"e6")]


def test_snapshot_legacy_format_and_location(tmp_path):
    """Pre-layout data_dirs restore: bare (unwrapped) snapshot blobs in
    the legacy <data_dir>/snapshots location are found and decoded."""
    from nomad_tpu.server.raft import (
        InmemRaft,
        SnapshotStore,
        resolve_snapshot_dir,
        unwrap_snapshot,
    )

    data_dir = str(tmp_path)
    legacy = SnapshotStore(f"{data_dir}/snapshots")
    legacy.save(7, b"raw-fsm-blob")  # old format: bare blob, no wrapper

    resolved = resolve_snapshot_dir(data_dir)
    assert resolved == f"{data_dir}/snapshots"

    term, blob = unwrap_snapshot(b"raw-fsm-blob")
    assert (term, blob) == (0, b"raw-fsm-blob")

    class FSM(_RecordingFSM):
        restored = None

        def restore(self, blob):
            self.restored = blob

    fsm = FSM()
    raft = InmemRaft(fsm, None, SnapshotStore(resolved))
    assert fsm.restored == b"raw-fsm-blob"
    assert raft.applied_index() == 7

    # Once the current layout has snapshots, it wins.
    import msgpack
    cur = SnapshotStore(f"{data_dir}/raft/snapshots")
    cur.save(9, msgpack.packb((3, b"new-blob"), use_bin_type=True))
    assert resolve_snapshot_dir(data_dir) == f"{data_dir}/raft/snapshots"
    assert unwrap_snapshot(
        msgpack.packb((3, b"new-blob"), use_bin_type=True)) == \
        (3, b"new-blob")


def test_inmem_replay_last_writer_wins_and_torn_tail(tmp_path):
    """Duplicate indexes in the durable log (re-append after a reported
    disk failure whose record nonetheless landed) replay last-writer-wins;
    a torn tail record ends replay cleanly (code-review regression)."""
    from nomad_tpu.server.raft import FileLogStore, InmemRaft

    path = str(tmp_path / "log.bin")
    log = FileLogStore(path)
    log.append(1, b"one")
    log.append(2, b"lost-but-landed")
    log.append(2, b"two-retry")
    log.close()
    # Torn tail: a length prefix promising more bytes than exist.
    with open(path, "ab") as fh:
        fh.write((999).to_bytes(4, "big"))
        fh.write(b"partial")

    fsm = _RecordingFSM()
    raft = InmemRaft(fsm, FileLogStore(path))
    assert [(i, bytes(d)) for i, d in fsm.applied] == \
        [(1, b"one"), (2, b"two-retry")]
    assert raft.applied_index() == 2
    raft.log_store.close()
