"""Durability & crash-recovery proofs.

Three layers, matching the crash plane's design:

1. **Storage crash points** (fast): every torn-write shape the
   ``crash`` fault can leave — torn log tails, bit-rotted records,
   torn snapshot tmp files, snapshots persisted but never pruned, torn
   meta tmp files — must recover to a clean committed prefix on the
   next open, byte-exactly, never an exception.
2. **Crash-point soak** (slow): a live submission storm against a
   durable server, a seeded crash at each storage site, a
   CrashHarness hard-drop (no graceful teardown), and a
   reboot-from-data_dir whose state store must byte-compare (store
   fingerprint incl. the alloc changelog) against a replay of the
   recorded applied history prefix — with client retries then
   converging to exactly-once placement, zero duplicate allocs.
3. **Leader-kill soak** (slow): a 3-server durable NetRaft cluster
   under a storm; the leader is hard-killed repeatedly, survivors
   elect, the killed node reboots from its own data_dir and catches up
   (log replay or InstallSnapshot), and the cluster converges to
   exactly-once placement with identical stores.
"""
from __future__ import annotations

import os
import random
import shutil
import threading
import time

import msgpack
import pytest

import nomad_tpu.mock as mock
from nomad_tpu import faultinject
from nomad_tpu.faultinject import FaultCrash, FaultPlan
from nomad_tpu.faultinject.crash import CrashHarness, freeze_storage
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.fsm import NomadFSM
from nomad_tpu.server.raft import (
    LOG_MAGIC,
    FileLogStore,
    InmemRaft,
    MetaStore,
    SnapshotStore,
    StorageDead,
    resolve_snapshot_dir,
)
from nomad_tpu.server.rpc import ConnPool
from nomad_tpu.structs import Resources, Task, TaskGroup

from tests.conftest import wait_until

TERMINAL = ("complete", "failed", "canceled")


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _small_job(n_groups: int = 2, count: int = 1):
    job = mock.job()
    job.constraints = []
    job.task_groups = [
        TaskGroup(name=f"tg-{g}", count=count,
                  tasks=[Task(name="web", driver="exec",
                              resources=Resources(cpu=100,
                                                  memory_mb=32))])
        for g in range(n_groups)]
    return job


def _assert_exactly_once(state, jobs) -> None:
    """Every job fully placed, no duplicate live alloc names (the
    double-placement signature)."""
    for job in jobs:
        expected = sum(tg.count for tg in job.task_groups)
        live = [a for a in state.allocs_by_job(job.id)
                if not a.terminal_status()]
        names = [a.name for a in live]
        assert len(names) == len(set(names)), \
            f"duplicate allocs for {job.id}: {sorted(names)}"
        assert len(live) == expected, \
            f"job {job.id}: {len(live)} live allocs, want {expected}"


def _evals_terminal(state, jobs) -> bool:
    for job in jobs:
        evals = state.evals_by_job(job.id)
        if not evals:
            return False
        if any(e.status not in TERMINAL for e in evals):
            return False
    return True


def _replay_twin(history: list, upto: int) -> NomadFSM:
    """A fresh FSM fed the recorded applied history up to index
    ``upto`` — the reference state a recovered store must byte-match
    (boot-replay tolerance for poisoned entries mirrored)."""
    twin = NomadFSM()
    for index, entry in history:
        if index > upto:
            break
        try:
            twin.apply(index, entry)
        except Exception:
            pass
    return twin


def _submit_retry(pool, addr_fn, method, args, acked=None, key=None,
                  deadline=30.0):
    """Client-style submission: retry across crashes/reboots until the
    server acks.  Records the acked raft index."""
    end = time.monotonic() + deadline
    while True:
        try:
            resp = pool.call(addr_fn(), method, args, timeout=2.0)
        except Exception:
            if time.monotonic() >= end:
                raise
            time.sleep(0.05)  # sleep-ok: bounded retry poll across a crash
            continue
        if acked is not None and key is not None:
            acked[key] = resp.get("index", 0)
        return resp


# ---------------------------------------------------------------------------
# 1. storage crash points (fast)
# ---------------------------------------------------------------------------

class TestLogStoreCrashPoints:
    def _records(self, store):
        return [(i, bytes(d)) for i, d in store.replay()]

    def test_log_append_crash_leaves_recoverable_prefix(self, tmp_path):
        path = str(tmp_path / "log.bin")
        store = FileLogStore(path)
        store.append(1, b"one")
        store.append(2, b"two")
        plan = FaultPlan(seed=5).add("log.append", "crash", count=1)
        with faultinject.injected(plan):
            with pytest.raises(FaultCrash):
                store.append(3, b"three")
            # The store is dead: not one more byte may land.
            with pytest.raises(StorageDead):
                store.append(4, b"four")
            assert plan.is_crashed()
        store.close()

        # Reboot: tail-scan recovers a committed prefix — the two acked
        # records always, the torn third only if it landed whole.
        reopened = FileLogStore(path)
        records = self._records(reopened)
        full = [(1, b"one"), (2, b"two"), (3, b"three")]
        assert records == full[:len(records)] and len(records) >= 2
        # And the recovered store accepts appends cleanly again.
        reopened.append(len(records) + 1, b"next")
        reopened.close()

    def test_fsync_crash_full_record_lands_and_replays(self, tmp_path):
        """fraction=1.0: the whole record survived the cut (a failed
        fsync that actually hit disk).  Replay keeps it — the caller
        saw an error and will re-append the index; last-writer-wins
        replay resolves the duplicate."""
        path = str(tmp_path / "log.bin")
        store = FileLogStore(path)
        store.append(1, b"one")
        record = msgpack.packb((2, b"lost-but-landed"), use_bin_type=True)
        framed = store._frame(record)
        with store._lock:
            store._power_loss(framed, store._good_offset,
                              FaultCrash("log.fsync", 1.0, "torn"))
        store.close()
        reopened = FileLogStore(path)
        assert self._records(reopened) == [(1, b"one"),
                                           (2, b"lost-but-landed")]
        reopened.close()

    def test_corrupt_crash_detected_by_crc(self, tmp_path):
        """mode=corrupt: every byte landed but one rotted.  The CRC
        catches it; the tail-scan truncates to the prior record."""
        path = str(tmp_path / "log.bin")
        store = FileLogStore(path)
        store.append(1, b"one")
        record = msgpack.packb((2, b"rotted"), use_bin_type=True)
        framed = store._frame(record)
        pos = store._good_offset
        with store._lock:
            store._power_loss(framed, pos,
                              FaultCrash("log.fsync", 1.0, "corrupt"))
        store.close()
        assert os.path.getsize(path) == pos + len(framed)
        reopened = FileLogStore(path)
        assert self._records(reopened) == [(1, b"one")]
        assert os.path.getsize(path) == pos  # rotted tail truncated
        reopened.close()

    def test_append_error_truncates_back_to_known_good(self, tmp_path):
        """ISSUE satellite (the raft.py:79 hazard): a mid-record write
        failure leaves partial bytes; the store re-stats and truncates
        back to the last known-good offset before allowing appends."""
        path = str(tmp_path / "log.bin")
        store = FileLogStore(path)
        store.append(1, b"one")
        good = store._good_offset

        real_fh = store._fh

        class TornWriter:
            """Writes ``budget`` bytes then fails — a dying disk."""

            def __init__(self, fh, budget):
                self.fh = fh
                self.budget = budget

            def write(self, data):
                if len(data) > self.budget:
                    self.fh.write(data[:self.budget])
                    self.fh.flush()
                    self.budget = 0
                    raise OSError("disk error mid-record")
                self.budget -= len(data)
                return self.fh.write(data)

            def __getattr__(self, name):
                return getattr(self.fh, name)

        store._fh = TornWriter(real_fh, budget=7)
        with pytest.raises(OSError):
            store.append(2, b"torn-away")
        store._fh = real_fh
        # Recovery already ran: the partial bytes are gone.
        assert os.path.getsize(path) == good
        store.append(2, b"two-retry")
        store.close()
        reopened = FileLogStore(path)
        assert self._records(reopened) == [(1, b"one"), (2, b"two-retry")]
        reopened.close()

    def test_legacy_log_upgraded_in_place(self, tmp_path):
        """Pre-CRC data_dirs keep restoring: the old [length][record]
        framing is parsed (tail rule included) and rewritten
        checksummed on open."""
        path = str(tmp_path / "log.bin")
        legacy = b""
        for i, data in ((1, b"a"), (2, b"b")):
            record = msgpack.packb((i, data), use_bin_type=True)
            legacy += len(record).to_bytes(4, "big") + record
        legacy += (99).to_bytes(4, "big") + b"torn"  # torn legacy tail
        with open(path, "wb") as fh:
            fh.write(legacy)
        store = FileLogStore(path)
        assert self._records(store) == [(1, b"a"), (2, b"b")]
        with open(path, "rb") as fh:
            assert fh.read(len(LOG_MAGIC)) == LOG_MAGIC
        store.append(3, b"c")
        assert self._records(store) == [(1, b"a"), (2, b"b"), (3, b"c")]
        store.close()

    def test_rotted_magic_header_rescues_intact_records(self, tmp_path):
        """A bit-rotted MAGIC header must not route an otherwise-intact
        CRC-framed log through the legacy parser — that "upgrade" would
        misread the framing and erase every record.  The CRC records
        are individually recoverable; rescue them and rewrite the
        header."""
        path = str(tmp_path / "log.bin")
        store = FileLogStore(path)
        for i, data in ((1, b"a"), (2, b"bb"), (3, b"ccc")):
            store.append(i, data)
        store.close()
        with open(path, "r+b") as fh:
            fh.seek(2)
            byte = fh.read(1)
            fh.seek(2)
            fh.write(bytes([byte[0] ^ 0xFF]))
        rescued = FileLogStore(path)
        assert self._records(rescued) == [(1, b"a"), (2, b"bb"),
                                          (3, b"ccc")]
        with open(path, "rb") as fh:
            assert fh.read(len(LOG_MAGIC)) == LOG_MAGIC
        rescued.append(4, b"dddd")
        assert self._records(rescued)[-1] == (4, b"dddd")
        rescued.close()

    def test_random_crash_offsets_always_yield_committed_prefix(
            self, tmp_path):
        """Property: ANY truncation or single-byte corruption of a
        recorded log replays as a committed prefix — never an
        exception, never a reordering, never a resurrection."""
        path = str(tmp_path / "log.bin")
        store = FileLogStore(path)
        original = []
        for i in range(1, 21):
            data = f"entry-{i}".encode() * (i % 5 + 1)
            store.append(i, data)
            original.append((i, data))
        store.close()
        size = os.path.getsize(path)

        for trial in range(40):
            rng = random.Random(trial)
            victim = str(tmp_path / f"victim-{trial}.bin")
            shutil.copyfile(path, victim)
            offset = rng.randrange(len(LOG_MAGIC), size)
            if rng.random() < 0.5:
                with open(victim, "r+b") as fh:
                    fh.truncate(offset)
            else:
                with open(victim, "r+b") as fh:
                    fh.seek(offset)
                    byte = fh.read(1)
                    fh.seek(offset)
                    fh.write(bytes([byte[0] ^ 0xFF]))
            recovered = FileLogStore(victim)
            records = [(i, bytes(d)) for i, d in recovered.replay()]
            assert records == original[:len(records)], \
                f"trial {trial} @ {offset}: not a committed prefix"
            recovered.close()


class TestSnapshotStoreCrashPoints:
    def _blob(self, tag: bytes) -> bytes:
        return tag * 64

    def test_checksum_fallback_to_older_snapshot(self, tmp_path):
        store = SnapshotStore(str(tmp_path), retain=2)
        store.save(5, self._blob(b"five"))
        path9 = store.save(9, self._blob(b"nine"))
        with open(path9, "r+b") as fh:
            fh.seek(30)
            byte = fh.read(1)
            fh.seek(30)
            fh.write(bytes([byte[0] ^ 0xFF]))
        index, blob = store.latest()
        assert (index, blob) == (5, self._blob(b"five"))

    def test_save_prunes_only_after_durable_rename(self, tmp_path):
        store = SnapshotStore(str(tmp_path), retain=1)
        store.save(1, self._blob(b"one"))
        store.save(2, self._blob(b"two"))
        names = sorted(os.listdir(str(tmp_path)))
        assert names == ["snapshot-%020d.bin" % 2]

    def test_crash_mid_tmp_write_leaves_old_set_untouched(self, tmp_path):
        store = SnapshotStore(str(tmp_path), retain=2)
        store.save(1, self._blob(b"one"))
        framed = b"\0" * 64
        with store._lock:
            store._power_loss(
                os.path.join(str(tmp_path), "snapshot-%020d.bin" % 2),
                os.path.join(str(tmp_path),
                             "snapshot-%020d.bin.tmp" % 2),
                framed, FaultCrash("snapshot.persist", 0.3, "torn"))
        with pytest.raises(StorageDead):
            store.save(3, self._blob(b"three"))
        # The torn tmp was never renamed; the real set still restores.
        fresh = SnapshotStore(str(tmp_path), retain=2)
        index, blob = fresh.latest()
        assert (index, blob) == (1, self._blob(b"one"))

    def test_crash_between_rename_and_prune_keeps_both(self, tmp_path):
        """The fencing case: the new snapshot IS durable; the old one
        (and the caller's log truncate, which only runs after save
        returns) never got deleted.  Both recovery points remain."""
        store = SnapshotStore(str(tmp_path), retain=1)
        store.save(1, self._blob(b"one"))
        blob2 = self._blob(b"two")
        import zlib
        framed = (b"NTPSNP2\n" + zlib.crc32(blob2).to_bytes(4, "big")
                  + blob2)
        with store._lock:
            store._power_loss(
                os.path.join(str(tmp_path), "snapshot-%020d.bin" % 2),
                os.path.join(str(tmp_path),
                             "snapshot-%020d.bin.tmp" % 2),
                framed, FaultCrash("snapshot.persist", 0.9, "torn"))
        names = sorted(n for n in os.listdir(str(tmp_path))
                       if n.endswith(".bin"))
        assert len(names) == 2
        fresh = SnapshotStore(str(tmp_path), retain=1)
        assert fresh.latest() == (2, blob2)

    def test_random_snapshot_truncations_never_raise(self, tmp_path):
        store = SnapshotStore(str(tmp_path / "snaps"), retain=3)
        store.save(3, self._blob(b"three"))
        path7 = store.save(7, self._blob(b"seven"))
        size = os.path.getsize(path7)
        for trial in range(20):
            rng = random.Random(1000 + trial)
            victim_dir = str(tmp_path / f"v{trial}")
            shutil.copytree(str(tmp_path / "snaps"), victim_dir)
            victim = os.path.join(victim_dir, os.path.basename(path7))
            with open(victim, "r+b") as fh:
                fh.truncate(rng.randrange(0, size))
            got = SnapshotStore(victim_dir, retain=3).latest()
            # Either the older snapshot, or — when the truncation kept
            # the whole payload — nothing was actually lost.
            assert got is not None
            assert got[0] in (3, 7)
            if got[0] == 3:
                assert got[1] == self._blob(b"three")


class TestMetaStoreCrashPoints:
    def test_torn_tmp_keeps_previous_meta(self, tmp_path):
        path = str(tmp_path / "meta.json")
        store = MetaStore(path)
        store.save({"term": 3, "voted_for": ["127.0.0.1", 4000]})
        plan = FaultPlan(seed=9).add("meta.persist", "crash", count=1)
        with faultinject.injected(plan):
            with pytest.raises(FaultCrash):
                store.save({"term": 4, "voted_for": None})
            with pytest.raises(StorageDead):
                store.save({"term": 5, "voted_for": None})
        fresh = MetaStore(path)
        assert fresh.load() == {"term": 3,
                                "voted_for": ["127.0.0.1", 4000]}

    def test_crash_latch_freezes_every_storage_site(self, tmp_path):
        """One crash = the whole process is dead: after log.append
        crashes, the snapshot and meta stores refuse writes too."""
        plan = FaultPlan(seed=1).add("log.append", "crash", count=1)
        log = FileLogStore(str(tmp_path / "log.bin"))
        snaps = SnapshotStore(str(tmp_path / "snaps"))
        meta = MetaStore(str(tmp_path / "meta.json"))
        with faultinject.injected(plan):
            with pytest.raises(FaultCrash):
                log.append(1, b"x")
            with pytest.raises(StorageDead):
                snaps.save(1, b"blob")
            with pytest.raises(StorageDead):
                meta.save({"term": 1})
            plan.reset_crashed()
            # The latch cleared (reboot): OTHER stores work again...
            snaps.save(1, b"blob")
            meta.save({"term": 1})
            # ...but the store that took the hit stays dead.
            with pytest.raises(StorageDead):
                log.append(2, b"y")

    def test_scoped_crash_latch_spares_other_data_dirs(self, tmp_path):
        """A crash rule aimed at ONE server's data_dir (``method``
        path-prefix predicate) freezes only that server's stores: its
        in-process peers keep committing — the multi-server power-cut
        model a cluster soak needs."""
        s1, s2 = str(tmp_path / "s1"), str(tmp_path / "s2")
        plan = FaultPlan(seed=2).add("log.append", "crash", count=1,
                                     method=f"{s1}*")
        log1 = FileLogStore(f"{s1}/raft/log.bin")
        snaps1 = SnapshotStore(f"{s1}/raft/snapshots")
        log2 = FileLogStore(f"{s2}/raft/log.bin")
        snaps2 = SnapshotStore(f"{s2}/raft/snapshots")
        meta2 = MetaStore(f"{s2}/raft/meta.json")
        with faultinject.injected(plan):
            log2.append(1, b"unmatched path: no fire")
            with pytest.raises(FaultCrash):
                log1.append(1, b"x")
            # s1 is dead end to end...
            with pytest.raises(StorageDead):
                snaps1.save(1, b"blob")
            # ...while its peers write on, every store kind.
            log2.append(2, b"after the cut")
            snaps2.save(1, b"blob")
            meta2.save({"term": 1})
        log1.close()
        log2.close()


class TestBootRefusesSilentGap:
    """A checksum-failed newest snapshot falls back to an older one;
    if the log was already compacted past the fallback, the durable
    history has a HOLE.  Booting anyway would silently drop the
    committed entries in the gap — both backends must refuse loudly
    instead (CommittedDataLoss), never skip-and-continue."""

    def _lay_down_gap(self, tmp_path, record):
        """data_dir with snapshots at 2 (good) and 5 (rotted CRC) and
        a log compacted to entries 6..7: fallback to 2 leaves entries
        3..5 unrecoverable."""
        snap_dir = resolve_snapshot_dir(str(tmp_path))
        snaps = SnapshotStore(snap_dir)
        snaps.save(2, b"old-state")
        path5 = snaps.save(5, b"new-state")
        with open(path5, "r+b") as fh:
            fh.seek(20)
            byte = fh.read(1)
            fh.seek(20)
            fh.write(bytes([byte[0] ^ 0xFF]))
        log = FileLogStore(str(tmp_path / "raft" / "log.bin"))
        for i in (6, 7):
            log.append(i, record(i))
        log.close()

    def test_inmem_boot_refuses_gap(self, tmp_path):
        from nomad_tpu.server.raft import CommittedDataLoss

        from tests.test_raft_net import _RecordingFSM

        self._lay_down_gap(tmp_path, lambda i: b"entry-%d" % i)
        log = FileLogStore(str(tmp_path / "raft" / "log.bin"))
        snaps = SnapshotStore(resolve_snapshot_dir(str(tmp_path)))
        with pytest.raises(CommittedDataLoss):
            InmemRaft(_RecordingFSM(), log, snaps)
        log.close()

    def test_net_raft_boot_refuses_gap(self, tmp_path):
        from nomad_tpu.server.raft import CommittedDataLoss
        from nomad_tpu.server.raft_net import NetRaft

        from tests.test_raft_net import _RecordingFSM, _StubRPC

        self._lay_down_gap(tmp_path,
                           lambda i: {"t": 1, "d": b"entry-%d" % i})
        with pytest.raises(CommittedDataLoss):
            NetRaft(_RecordingFSM(), _StubRPC(), None,
                    election_timeout=(30.0, 60.0),
                    data_dir=str(tmp_path))

    def test_install_snapshot_persist_failure_refuses_install(
            self, tmp_path):
        """Persist-before-memory on the InstallSnapshot path: a
        follower whose snapshot store cannot make the installed blob
        durable must refuse the install with NO state moved — fsm,
        log, and commit indexes untouched (the leader retries)."""
        from nomad_tpu.server.raft_net import NetRaft

        from tests.test_raft_net import _RecordingFSM, _StubRPC

        class RecordingRestoreFSM(_RecordingFSM):
            def __init__(self):
                super().__init__()
                self.restored = []

            def restore(self, blob):
                self.restored.append(bytes(blob))

        fsm = RecordingRestoreFSM()
        raft = NetRaft(fsm, _StubRPC(), None,
                       election_timeout=(30.0, 60.0),
                       data_dir=str(tmp_path))
        try:
            raft._snap_store.die()
            reply = raft._handle_install_snapshot({
                "term": 1, "leader": ["127.0.0.1", 4000],
                "last_included_index": 5, "last_included_term": 1,
                "data": b"snap-blob"})
            assert reply == {"term": 1}
            assert fsm.restored == []
            assert raft._last_applied == 0
            assert raft._commit_index == 0
            assert raft._log_base_index == 0
            assert raft._snap_blob is None
            # No snapshot file landed either: a reboot replays the
            # old history, matching the refused in-memory state.
            assert raft._snap_store.latest() is None
        finally:
            raft.shutdown()


# ---------------------------------------------------------------------------
# 2. crash-point soak: committed prefix + exactly-once (slow)
# ---------------------------------------------------------------------------

def _soak_config(data_dir: str, snapshot_threshold: int) -> ServerConfig:
    return ServerConfig(
        data_dir=data_dir, enable_rpc=True, num_schedulers=2,
        raft_snapshot_threshold=snapshot_threshold)


@pytest.mark.slow
@pytest.mark.parametrize("site,seed", [
    ("log.append", 11),
    ("log.append", 12),
    ("log.fsync", 21),
    ("snapshot.persist", 31),
    ("snapshot.persist", 32),
])
def test_crash_point_soak_recovers_committed_prefix(tmp_path, site, seed):
    """A live submission storm, a seeded crash at ``site``, a hard
    kill, a reboot from the same data_dir.  The rebooted store must be
    a byte-exact committed prefix of the recorded applied history, no
    acked write may be lost, and retries must converge to exactly-once
    placement."""
    data_dir = str(tmp_path / "server")
    threshold = 8 if site == "snapshot.persist" else 100_000
    server = Server(_soak_config(data_dir, threshold))
    server.establish_leadership()

    history: list = []
    server.fsm.on_entry = lambda i, e: history.append((i, e))
    # Entries handed to the log: a power cut may leave whole records of
    # the batch in flight durable (group commit writes concurrent
    # appliers' entries as one batch) though none of them was applied
    # or acked — a committed prefix all the same.
    offered: dict = {}
    sound_append = server.raft.log_store.append_many

    def recording_append(records):
        try:
            sound_append(records)
        except StorageDead:
            raise   # refused by a dead store: nothing of it landed
        except BaseException:
            offered.update({i: bytes(e) for i, e in records})
            raise
        offered.update({i: bytes(e) for i, e in records})
    server.raft.log_store.append_many = recording_append

    current = {"server": server}
    harness = CrashHarness()
    pool = ConnPool()
    jobs = [_small_job() for _ in range(12)]
    acked: dict = {}
    stop = threading.Event()

    def addr_fn():
        return current["server"].rpc_address()

    def lane(lane_jobs):
        for job in lane_jobs:
            if stop.is_set():
                return
            _submit_retry(pool, addr_fn, "Job.Register",
                          {"job": job.to_dict()}, acked=acked,
                          key=job.id, deadline=60.0)

    plan = FaultPlan(seed=seed).add(site, "crash", count=1, after=4)
    try:
        # Capacity lands before the faults arm: the crash must hit
        # mid-storm, with submissions in flight.
        for i in range(6):
            _submit_retry(pool, addr_fn, "Node.Register",
                          {"node": mock.node(i).to_dict()})
        with faultinject.injected(plan):
            lanes = [threading.Thread(target=lane, args=(jobs[i::2],),
                                      daemon=True) for i in range(2)]
            for t in lanes:
                t.start()

            wait_until(lambda: plan.fire_count(site) > 0, timeout=30,
                       msg=f"crash fired at {site}")
            harness.kill(server)
            pre_crash_history = list(history)
            acked_max = max(acked.values(), default=0)

            # -- recovery proof on a cold, workerless boot ------------
            snap_store = SnapshotStore(resolve_snapshot_dir(data_dir))
            latest = snap_store.latest()
            since = latest[0] if latest else 0
            probe_fsm = NomadFSM()
            probe_raft = InmemRaft(
                probe_fsm, FileLogStore(f"{data_dir}/raft/log.bin"),
                snap_store)
            k = probe_raft.applied_index()
            probe_raft.log_store.close()
            assert k >= acked_max, \
                f"committed write lost: recovered to {k}, " \
                f"acked up to {acked_max}"
            applied_max = max((i for i, _e in pre_crash_history),
                              default=0)
            in_flight = [(i, offered[i])
                         for i in range(applied_max + 1, k + 1)]
            twin = _replay_twin(pre_crash_history + in_flight, k)
            assert probe_fsm.state.fingerprint(changelog_since=since) == \
                twin.state.fingerprint(changelog_since=since), \
                "recovered store is not a byte-exact committed prefix"

            # -- reboot for real, converge, exactly-once --------------
            server2 = harness.reboot(_soak_config(data_dir, threshold))
            current["server"] = server2
            for t in lanes:
                t.join(90.0)
            assert all(not t.is_alive() for t in lanes)
            assert set(acked) == {j.id for j in jobs}
            wait_until(lambda: _evals_terminal(server2.fsm.state, jobs),
                       timeout=60, msg="all evals terminal after reboot")
            _assert_exactly_once(server2.fsm.state, jobs)
    finally:
        stop.set()
        pool.shutdown()
        harness.reap(also=[current["server"]])


@pytest.mark.slow
def test_meta_persist_crash_recovers_and_elects(tmp_path):
    """The meta.persist walk: a single-node NetRaft server crashes
    persisting its first election's term bump.  The torn tmp never
    replaced meta.json; the reboot elects cleanly and a storm then
    places exactly once."""
    data_dir = str(tmp_path / "server")
    cfg_kw = dict(
        data_dir=data_dir, raft_mode="net", num_schedulers=2,
        raft_election_timeout=(0.05, 0.10),
        raft_heartbeat_interval=0.02)
    harness = CrashHarness()
    pool = ConnPool()
    plan = FaultPlan(seed=77).add("meta.persist", "crash", count=1)
    server2 = None
    try:
        with faultinject.injected(plan):
            server = Server(ServerConfig(**cfg_kw))
            # The first election attempt hits the crash; the node can
            # never become leader (it cannot persist its term).
            wait_until(lambda: plan.fire_count("meta.persist") > 0,
                       timeout=10, msg="crash fired at meta.persist")
            assert not server.raft.is_leader()
            harness.kill(server)

            server2 = harness.reboot(ServerConfig(**cfg_kw))
            wait_until(lambda: server2.raft.is_leader() and
                       server2.is_leader(), msg="post-reboot election")
            # Meta persistence works again and is valid JSON.
            meta = MetaStore(f"{data_dir}/raft/meta.json").load()
            assert meta is not None and meta["term"] >= 1

            jobs = [_small_job() for _ in range(6)]
            for i in range(4):
                _submit_retry(pool, server2.rpc_address, "Node.Register",
                              {"node": mock.node(i).to_dict()})
            for job in jobs:
                _submit_retry(pool, server2.rpc_address, "Job.Register",
                              {"job": job.to_dict()})
            wait_until(lambda: _evals_terminal(server2.fsm.state, jobs),
                       timeout=30, msg="storm terminal after recovery")
            _assert_exactly_once(server2.fsm.state, jobs)
    finally:
        pool.shutdown()
        harness.reap(also=[server2] if server2 is not None else None)


# ---------------------------------------------------------------------------
# 3. leader-kill soak: rolling failover on a durable cluster (slow)
# ---------------------------------------------------------------------------

def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_leader_kill_soak_converges_exactly_once(tmp_path):
    """≥3 rolling leader kills (hard drops, storage frozen mid-flight)
    on a durable 3-server cluster under a live storm: survivors elect,
    the killed node reboots from its own data_dir and catches up (log
    replay or InstallSnapshot — threshold kept low so compaction
    happens mid-soak), and the cluster converges to exactly-once
    placement with identical stores."""
    ports = [_free_port() for _ in range(3)]
    peers = [("127.0.0.1", p) for p in ports]

    def cfg(i: int) -> ServerConfig:
        return ServerConfig(
            data_dir=str(tmp_path / f"s{i}"), raft_mode="net",
            rpc_port=ports[i], raft_peers=list(peers),
            num_schedulers=1,
            raft_election_timeout=(0.10, 0.20),
            raft_heartbeat_interval=0.03,
            raft_snapshot_threshold=48)

    servers = {i: Server(cfg(i)) for i in range(3)}
    alive = dict(servers)
    harness = CrashHarness()
    pool = ConnPool()
    stop = threading.Event()
    jobs = [_small_job() for _ in range(24)]
    acked: dict = {}
    rr = [0]

    def addr_fn():
        targets = list(alive.values())
        rr[0] += 1
        return targets[rr[0] % len(targets)].rpc_address()

    def leader_of(pool_servers, timeout=15.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            leaders = [s for s in pool_servers.values()
                       if s.raft.is_leader()]
            if len(leaders) == 1:
                return leaders[0]
            time.sleep(0.02)  # sleep-ok: poll interval of the bounded wait
        raise AssertionError("no single leader")

    def lane(lane_jobs):
        for job in lane_jobs:
            if stop.is_set():
                return
            _submit_retry(pool, addr_fn, "Job.Register",
                          {"job": job.to_dict()}, acked=acked,
                          key=job.id, deadline=120.0)

    try:
        leader_of(alive)
        for i in range(8):
            _submit_retry(pool, addr_fn, "Node.Register",
                          {"node": mock.node(i).to_dict()})
        lanes = [threading.Thread(target=lane, args=(jobs[i::2],),
                                  daemon=True) for i in range(2)]
        for t in lanes:
            t.start()

        for kill in range(3):
            leader = leader_of(alive)
            victim = next(i for i, s in alive.items() if s is leader)
            harness.kill(leader)
            del alive[victim]

            # Survivors elect among themselves.
            new_leader = leader_of(alive)
            assert new_leader is not leader

            # The killed node reboots from its own disk and catches up
            # via log replay or InstallSnapshot.
            reborn = harness.reboot(cfg(victim))
            alive[victim] = reborn
            canary = mock.node(100 + kill)
            _submit_retry(pool, addr_fn, "Node.Register",
                          {"node": canary.to_dict()})
            wait_until(
                lambda: reborn.fsm.state.node_by_id(canary.id)
                is not None,
                timeout=30, msg=f"reborn s{victim} caught up "
                f"(kill {kill})")

        for t in lanes:
            t.join(150.0)
        assert all(not t.is_alive() for t in lanes)
        assert set(acked) == {j.id for j in jobs}, "lost submissions"

        leader = leader_of(alive)
        wait_until(lambda: _evals_terminal(leader.fsm.state, jobs),
                   timeout=90, msg="storm terminal after 3 kills")
        _assert_exactly_once(leader.fsm.state, jobs)

        # Replicas converge to the same tables (changelogs differ
        # legitimately across InstallSnapshot boundaries).
        def converged():
            prints = {s.fsm.state.fingerprint(changelog_since=10**9)
                      for s in alive.values()}
            return len(prints) == 1
        wait_until(converged, timeout=30, msg="replica convergence")
    finally:
        stop.set()
        pool.shutdown()
        harness.reap(also=list(alive.values()))


# ---------------------------------------------------------------------------
# 4. client crash-reattach (ISSUE satellite)
# ---------------------------------------------------------------------------

def test_client_reboot_with_corrupt_alloc_state_reattaches(tmp_path):
    """A client hard-rebooted mid-task with a TORN alloc state file
    must not silently discard the allocation: the alloc is re-fetched
    from the server and the still-running task re-attached via its
    (separately persisted) handle — same pid, never a double."""
    from nomad_tpu.client import Client
    from nomad_tpu.client.config import ClientConfig

    srv = Server(ServerConfig(num_schedulers=2, enable_rpc=True))
    srv.establish_leadership()
    cfg = ClientConfig(
        state_dir=str(tmp_path / "client-state"),
        alloc_dir=str(tmp_path / "allocs"),
        servers=[srv.rpc_address()],
        options={"driver.raw_exec.enable": "1",
                 "fingerprint.skip_accel": "1"},
    )
    client = Client(cfg)
    client2 = None
    try:
        client.start()
        wait_until(lambda: srv.fsm.state.node_by_id(client.node.id)
                   is not None, msg="node registration")
        job = mock.job()
        job.constraints = []
        job.task_groups[0].count = 1
        job.task_groups[0].tasks = [Task(
            name="sleeper", driver="raw_exec",
            config={"command": "/bin/sleep", "args": "300"},
            resources=Resources(cpu=100, memory_mb=32))]
        _, eval_id = srv.job_register(job)
        srv.wait_for_evals([eval_id], timeout=15)

        def task_running():
            for runner in client.alloc_runners.values():
                tr = runner.task_runners.get("sleeper")
                if tr is not None and tr.state == "running":
                    return True
            return False
        wait_until(task_running, timeout=20, msg="task running")
        alloc_id = next(iter(client.alloc_runners))
        pid = client.alloc_runners[alloc_id] \
            .task_runners["sleeper"].handle.pid

        # Hard reboot: stop the agent's loops (no graceful destroy —
        # the task process survives, as it would a real agent crash)
        # and tear the alloc state file mid-record.
        client.shutdown()
        state_path = os.path.join(str(tmp_path / "client-state"),
                                  "allocs", alloc_id, "state.json")
        size = os.path.getsize(state_path)
        with open(state_path, "r+b") as fh:
            fh.truncate(size // 2)

        client2 = Client(cfg)
        # The torn state did NOT restore a runner — and did NOT get
        # silently discarded either: it is queued for server re-fetch.
        assert alloc_id not in client2.alloc_runners
        assert alloc_id in client2._recover_alloc_ids
        assert os.path.isdir(os.path.dirname(state_path))
        client2.start()

        def reattached():
            runner = client2.alloc_runners.get(alloc_id)
            if runner is None:
                return False
            tr = runner.task_runners.get("sleeper")
            return tr is not None and tr.state == "running" and \
                tr.handle is not None
        wait_until(reattached, timeout=20, msg="re-attach after reboot")
        tr2 = client2.alloc_runners[alloc_id].task_runners["sleeper"]
        # Same pid: the live process was re-attached, not doubled.
        assert tr2.handle.pid == pid
        assert alloc_id not in client2._recover_alloc_ids
    finally:
        if client2 is not None:
            client2.shutdown()
            client2.destroy_all()
        client.destroy_all()
        srv.shutdown()


def test_client_reboot_with_corrupt_state_and_stopped_alloc_reclaims(
        tmp_path):
    """The other half of the reattach satellite: a torn-state alloc
    the SERVER is done with (job stopped while the client was down)
    must not be forgotten — the still-running orphan is re-attached by
    its persisted task handle, killed, and both directories reclaimed,
    with the recover queue drained."""
    from nomad_tpu.client import Client
    from nomad_tpu.client.config import ClientConfig
    from nomad_tpu.client.driver.base import _pid_alive

    srv = Server(ServerConfig(num_schedulers=2, enable_rpc=True))
    srv.establish_leadership()
    cfg = ClientConfig(
        state_dir=str(tmp_path / "client-state"),
        alloc_dir=str(tmp_path / "allocs"),
        servers=[srv.rpc_address()],
        options={"driver.raw_exec.enable": "1",
                 "fingerprint.skip_accel": "1"},
    )
    client = Client(cfg)
    client2 = None
    try:
        client.start()
        wait_until(lambda: srv.fsm.state.node_by_id(client.node.id)
                   is not None, msg="node registration")
        job = mock.job()
        job.constraints = []
        job.task_groups[0].count = 1
        job.task_groups[0].tasks = [Task(
            name="sleeper", driver="raw_exec",
            config={"command": "/bin/sleep", "args": "300"},
            resources=Resources(cpu=100, memory_mb=32))]
        _, eval_id = srv.job_register(job)
        srv.wait_for_evals([eval_id], timeout=15)

        def task_running():
            for runner in client.alloc_runners.values():
                tr = runner.task_runners.get("sleeper")
                if tr is not None and tr.state == "running":
                    return True
            return False
        wait_until(task_running, timeout=20, msg="task running")
        alloc_id = next(iter(client.alloc_runners))
        pid = client.alloc_runners[alloc_id] \
            .task_runners["sleeper"].handle.pid

        # Agent crash with a torn state file...
        client.shutdown()
        state_dir = os.path.join(str(tmp_path / "client-state"),
                                 "allocs", alloc_id)
        state_path = os.path.join(state_dir, "state.json")
        with open(state_path, "r+b") as fh:
            fh.truncate(os.path.getsize(state_path) // 2)
        # ...and the job stopped while the agent was down.
        _, stop_eval = srv.job_deregister(job.id)
        srv.wait_for_evals([stop_eval], timeout=15)

        client2 = Client(cfg)
        assert alloc_id in client2._recover_alloc_ids
        client2.start()

        alloc_root = client2._alloc_root(alloc_id)

        def reclaimed():
            return (alloc_id not in client2.alloc_runners
                    and not os.path.isdir(state_dir)
                    and not os.path.isdir(alloc_root)
                    and not _pid_alive(pid))
        # Load-tolerant bar (documented pre-existing flake, PR 12/13
        # notes): the reclaim rides a background thread + an RPC watch
        # cycle, both starved under full-suite host load — the proof is
        # THAT it converges, not how fast.
        wait_until(reclaimed, timeout=60,
                   msg="orphan killed and directories reclaimed")
        assert alloc_id not in client2._recover_alloc_ids
    finally:
        if client2 is not None:
            client2.shutdown()
            client2.destroy_all()
        client.destroy_all()
        srv.shutdown()
