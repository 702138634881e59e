"""Group commit in the single-node raft (server/raft.py InmemRaft):
appliers that arrive while another is in the commit section ride the
next one's batch — one log write and one fsync for all of them — and
nobody is answered before the fsync that covers its own entry."""
from __future__ import annotations

import threading
import time

import pytest

from nomad_tpu.server import raft as raft_mod
from nomad_tpu.server.raft import FileLogStore, InmemRaft


class _FSM:
    def __init__(self, durable: set) -> None:
        self.applied: list = []
        self.durable = durable

    def apply(self, index: int, entry: bytes):
        # Persist BEFORE apply: the entry is on disk by now.
        assert index in self.durable, index
        if entry == b"poison":
            raise ValueError("poisoned entry")
        self.applied.append((index, bytes(entry)))
        return index


def _raft(tmp_path, monkeypatch, fsync_s: float = 0.0):
    """(raft, fsm, fsync count holder, durable index set): a log on
    disk whose fsync takes ``fsync_s`` and whose appends mark their
    indexes durable only once the fsync has returned."""
    store = FileLogStore(str(tmp_path / "raft" / "log.bin"))
    fsyncs = [0]
    real_fsync = raft_mod.os.fsync

    def slow_fsync(fd):
        fsyncs[0] += 1
        time.sleep(fsync_s)
        real_fsync(fd)
    monkeypatch.setattr(raft_mod.os, "fsync", slow_fsync)
    durable: set = set()
    sound = store.append_many

    def append_many(records):
        sound(records)
        durable.update(i for i, _e in records)
    store.append_many = append_many
    fsm = _FSM(durable)
    return InmemRaft(fsm, store), fsm, fsyncs, durable


def test_concurrent_appliers_share_fsyncs_and_none_is_answered_early(
        tmp_path, monkeypatch):
    raft, fsm, fsyncs, durable = _raft(tmp_path, monkeypatch, fsync_s=0.005)
    threads, per_thread = 8, 25
    answers: list = []
    lock = threading.Lock()

    def caller(t: int) -> None:
        for k in range(per_thread):
            future = raft.apply(f"{t}:{k}".encode())
            assert future.done()        # resolved on return
            index, response = future.wait(0)
            assert index in durable and response == index
            with lock:
                answers.append((index, f"{t}:{k}".encode()))

    pool = [threading.Thread(target=caller, args=(t,))
            for t in range(threads)]
    for t in pool:
        t.start()
    for t in pool:
        t.join(60.0)
    total = threads * per_thread
    assert sorted(i for i, _e in answers) == list(range(1, total + 1))
    assert raft.applied_index() == total
    # The FSM saw every entry once, in log order, and each caller was
    # told the index its own entry got.
    assert [i for i, _e in fsm.applied] == list(range(1, total + 1))
    assert dict(fsm.applied) == dict(answers)
    # One fsync per BATCH: eight callers behind a 5 ms flush share it.
    assert fsyncs[0] < total / 2, fsyncs[0]
    # What is on disk is what was applied, in order.
    raft.log_store.close()
    replayed = list(FileLogStore(raft.log_store.path).replay())
    assert [(i, bytes(e)) for i, e in replayed] == fsm.applied


def test_a_lone_applier_pays_its_own_fsync(tmp_path, monkeypatch):
    raft, fsm, fsyncs, _durable = _raft(tmp_path, monkeypatch)
    for k in range(5):
        index, _ = raft.apply(b"x%d" % k).wait(0)
        assert index == k + 1
    assert fsyncs[0] == 5 and len(fsm.applied) == 5


def test_a_failed_append_fails_its_batch_and_moves_nothing(tmp_path,
                                                            monkeypatch):
    raft, fsm, _fsyncs, _durable = _raft(tmp_path, monkeypatch)
    raft.apply(b"first").wait(0)
    sound = raft.log_store.append_many

    def failing(records):
        raise OSError("disk full")
    raft.log_store.append_many = failing
    future = raft.apply(b"lost")
    with pytest.raises(OSError):
        future.wait(0)
    assert raft.applied_index() == 1 and len(fsm.applied) == 1
    raft.log_store.append_many = sound
    index, _ = raft.apply(b"second").wait(0)
    assert index == 2 and fsm.applied[-1] == (2, b"second")


def test_an_apply_error_is_its_own_entrys_alone(tmp_path, monkeypatch):
    raft, fsm, _fsyncs, _durable = _raft(tmp_path, monkeypatch,
                                         fsync_s=0.02)
    results: dict = {}

    def caller(entry: bytes) -> None:
        future = raft.apply(entry)
        try:
            results[entry] = future.wait(0)[0]
        except ValueError as e:
            results[entry] = e

    pool = [threading.Thread(target=caller, args=(e,))
            for e in (b"a", b"poison", b"b", b"c")]
    for t in pool:
        t.start()
    for t in pool:
        t.join(30.0)
    assert isinstance(results[b"poison"], ValueError)
    good = sorted(results[e] for e in (b"a", b"b", b"c"))
    assert len(set(good)) == 3 and raft.applied_index() == 4
    assert sorted(e for _i, e in fsm.applied) == [b"a", b"b", b"c"]
