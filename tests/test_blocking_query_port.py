"""Blocking-query semantics port, run against BOTH serving paths.

The reference's blocking-query contract (nomad/rpc.go:269-338 block,
nomad/http_test.go TestParseWait/blocking tables, node_endpoint_test.go
Node.GetAllocs blocking cases):

- ``min_query_index`` 0 (or absent) answers immediately with the
  current table index;
- ``min_query_index`` below the current index answers immediately;
- ``min_query_index`` at/above the current index blocks until a write
  moves the table past it, then answers with the NEW index;
- a wait that expires answers with the CURRENT data and index — a
  timeout is a normal response, never an error;
- waits are table-keyed: a write to another table must not wake the
  query;
- a query for an object that doesn't exist still honors the table
  semantics (blocks, then answers ``None``);
- a read of ONE evaluation watches that evaluation (reference
  Eval.GetEval from Nomad 0.2 on: ``watch.Item{Eval: id}``,
  ``reply.Index = out.ModifyIndex``): it compares, parks under and
  answers with the row's own ``modify_index`` — the table's index only
  when there is no such evaluation — so writes to other evaluations
  neither wake it nor move its index.

Every case runs twice — through the in-proc RPC path (the colocated
agent, synchronous fan-out waiter) and through the event-driven mux
wire path (parked fan-out callback) — on identically-driven fresh
servers, and the responses must be byte-identical: the serving-plane
refactor may change WHERE a query waits, never WHAT it answers.
"""
from __future__ import annotations

import json
import sys
import threading
import time

import pytest

from nomad_tpu.agent.agent import InprocRPC
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.rpc import ConnPool
from nomad_tpu.structs import Allocation, Evaluation, Node
from tests.conftest import wait_until


def _node(i: int) -> Node:
    return Node(id=f"bq-n{i:03d}", name=f"bq-{i}", datacenter="dc1",
                status="ready")


def _alloc(i: int, node_id: str) -> Allocation:
    return Allocation(id=f"bq-a{i:03d}", node_id=node_id,
                      job_id="bq-job", eval_id="bq-eval",
                      name=f"bq[{i}]", desired_status="run",
                      client_status="pending")


def _eval(name: str, status: str = "pending") -> Evaluation:
    return Evaluation(id=f"bq-eval-{name}", priority=50, type="service",
                      triggered_by="job-register", job_id=f"bq-job-{name}",
                      status=status)


class _InprocPath:
    name = "inproc"

    def __enter__(self):
        self.srv = Server(ServerConfig(num_schedulers=0, tune_gc=False,
                                       use_device_scheduler=False))
        self.srv.establish_leadership()
        self.rpc = InprocRPC(self.srv)
        return self

    def call(self, method, args):
        return self.rpc.call(method, args)

    def __exit__(self, *exc):
        self.srv.shutdown()


class _MuxPath:
    name = "mux"

    def __enter__(self):
        self.srv = Server(ServerConfig(num_schedulers=0, tune_gc=False,
                                       use_device_scheduler=False,
                                       enable_rpc=True))
        self.srv.establish_leadership()
        self.pool = ConnPool()
        return self

    def call(self, method, args):
        return self.pool.call(self.srv.rpc_address(), method,
                              dict(args), timeout=30.0)

    def __exit__(self, *exc):
        self.pool.shutdown()
        self.srv.shutdown()


def _canon(resp) -> str:
    return json.dumps(resp, sort_keys=True)


# Each case: (name, run(path) -> response dict).  Writes are
# deterministic (fixed ids, raft-sequenced indexes) so both fresh
# servers produce byte-identical state and responses.

def _case_min_index_zero_immediate(p):
    p.srv.node_register(_node(0))
    return p.call("Node.List", {})


def _case_min_index_below_current_immediate(p):
    first = p.srv.node_register(_node(0))
    p.srv.node_register(_node(1))
    return p.call("Node.List", {"min_query_index": first,
                                "max_query_time": 5.0})


def _case_blocks_until_change(p):
    p.srv.node_register(_node(0))
    cur = p.srv.fsm.state.get_index("nodes")

    def write():
        time.sleep(0.3)  # sleep-ok: park the query before the wake write
        p.srv.node_register(_node(1))

    t = threading.Thread(target=write)
    t.start()
    resp = p.call("Node.List", {"min_query_index": cur,
                                "max_query_time": 10.0})
    t.join(5)
    assert resp["index"] > cur, "must answer with the post-write index"
    return resp


def _case_timeout_returns_current(p):
    p.srv.node_register(_node(0))
    cur = p.srv.fsm.state.get_index("nodes")
    t0 = time.monotonic()
    resp = p.call("Node.List", {"min_query_index": cur,
                                "max_query_time": 0.3})
    assert 0.2 <= time.monotonic() - t0 < 5.0
    assert resp["index"] == cur, "timeout answers with the CURRENT index"
    return resp


def _case_unknown_object_blocks_then_none(p):
    p.srv.node_register(_node(0))  # nonzero world
    cur = p.srv.fsm.state.get_index("evals")
    resp = p.call("Eval.GetEval", {"eval_id": "no-such-eval",
                                   "min_query_index": cur or 0,
                                   "max_query_time": 0.3})
    assert resp["eval"] is None
    return resp


def _case_get_allocs_wakes_on_alloc_write(p):
    p.srv.node_register(_node(0))
    # Seed the table: a pre-first-write index of 0 takes the immediate
    # path by contract (min_query_index 0 never blocks).
    p.srv.fsm.state.upsert_allocs(999, [])
    cur = p.srv.fsm.state.get_index("allocs")

    def write():
        time.sleep(0.3)  # sleep-ok: park the long-poll before the alloc lands
        p.srv.fsm.state.upsert_allocs(1000, [_alloc(0, "bq-n000")])

    t = threading.Thread(target=write)
    t.start()
    resp = p.call("Node.GetAllocs", {"node_id": "bq-n000",
                                     "min_query_index": cur,
                                     "max_query_time": 10.0})
    t.join(5)
    assert len(resp["allocs"]) == 1 and resp["index"] == 1000
    return resp


def _case_waits_are_table_keyed(p):
    p.srv.node_register(_node(0))
    jobs_cur = p.srv.fsm.state.get_index("jobs")

    def write_other_table():
        time.sleep(0.15)  # sleep-ok: the cross-table write lands mid-wait
        p.srv.node_register(_node(1))

    t = threading.Thread(target=write_other_table)
    t.start()
    t0 = time.monotonic()
    resp = p.call("Job.List", {"min_query_index": jobs_cur or 0,
                               "max_query_time": 0.6})
    t.join(5)
    took = time.monotonic() - t0
    if jobs_cur > 0:
        assert took >= 0.5, "a nodes write must not wake a jobs query"
    assert resp["jobs"] == []
    return resp


def _case_eval_read_wakes_on_its_own_write_only(p):
    p.srv.apply_eval_update([_eval("a"), _eval("b")])
    cur = p.srv.fsm.state.eval_by_id("bq-eval-a").modify_index

    def write():
        time.sleep(0.2)  # sleep-ok: park the read before the writes
        p.srv.apply_eval_update([_eval("b", "complete")])
        time.sleep(0.2)  # sleep-ok: b's write must not have answered it
        p.srv.apply_eval_update([_eval("a", "complete")])

    t = threading.Thread(target=write)
    t.start()
    resp = p.call("Eval.GetEval", {"eval_id": "bq-eval-a",
                                   "min_query_index": cur,
                                   "max_query_time": 10.0})
    t.join(5)
    assert resp["eval"]["status"] == "complete"
    assert resp["index"] == resp["eval"]["modify_index"] == cur + 2
    return resp


def _case_eval_read_answers_the_rows_index_not_the_tables(p):
    p.srv.apply_eval_update([_eval("a")])
    p.srv.apply_eval_update([_eval("b")])
    resp = p.call("Eval.GetEval", {"eval_id": "bq-eval-a"})
    assert resp["index"] == resp["eval"]["modify_index"] \
        == p.srv.fsm.state.get_index("evals") - 1
    return resp


CASES = [
    _case_eval_read_wakes_on_its_own_write_only,
    _case_eval_read_answers_the_rows_index_not_the_tables,
    _case_min_index_zero_immediate,
    _case_min_index_below_current_immediate,
    _case_blocks_until_change,
    _case_timeout_returns_current,
    _case_unknown_object_blocks_then_none,
    _case_get_allocs_wakes_on_alloc_write,
    _case_waits_are_table_keyed,
]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[6:])
def test_blocking_query_semantics_byte_identical_on_both_paths(case):
    responses = {}
    for path_cls in (_InprocPath, _MuxPath):
        with path_cls() as p:
            resp = case(p)
            assert resp.get("known_leader") is True
            responses[path_cls.name] = _canon(resp)
    assert responses["inproc"] == responses["mux"], \
        "the two serving paths answered differently:\n" \
        f"inproc: {responses['inproc']}\nmux:    {responses['mux']}"


def test_parked_path_actually_parks_while_inproc_blocks_a_thread():
    """Structural sanity for the comparison above: over the wire the
    waiting query is a fan-out waiter with NO dispatch worker pinned;
    in-proc it is the caller's own thread."""
    with _MuxPath() as p:
        p.srv.node_register(_node(0))
        cur = p.srv.fsm.state.get_index("nodes")
        got = []
        t = threading.Thread(target=lambda: got.append(
            p.call("Node.List", {"min_query_index": cur,
                                 "max_query_time": 10.0})))
        t.start()
        wait_until(lambda: p.srv.fsm.state.watch.live_waiters() == 1,
                   msg="wire query parked in the fan-out")
        assert p.srv.rpc_server._pool.stats()["busy"] == 0, \
            "a parked blocking query must not pin a dispatch worker"
        p.srv.node_register(_node(1))
        t.join(10)
        assert got and got[0]["index"] > cur


# -- a read of ONE evaluation watches that evaluation ----------------------
# Each case runs on both wait paths: the in-process event wait and
# ``mux.Parked`` on the wire.

def _get(p, name, **args):
    return p.call("Eval.GetEval", dict(args, eval_id=f"bq-eval-{name}"))


def _row_index(p, name) -> int:
    return p.srv.fsm.state.eval_by_id(f"bq-eval-{name}").modify_index


def _blocked_read(p, name, index, wait=20.0):
    """Start a read of one eval at ``index`` and return once it is
    parked: ``(thread, answers)``."""
    watch = p.srv.fsm.state.watch
    before = watch.live_waiters()
    got = []
    t = threading.Thread(target=lambda: got.append(
        _get(p, name, min_query_index=index, max_query_time=wait)))
    t.start()
    wait_until(lambda: watch.live_waiters() == before + 1,
               msg="the read parked in the fan-out")
    return t, got


def _eval_case_other_evals_writes_leave_it_parked(p):
    watch = p.srv.fsm.state.watch
    p.srv.apply_eval_update([_eval("a"), _eval("b"), _eval("c")])
    t, got = _blocked_read(p, "a", _row_index(p, "a"))
    p.srv.apply_eval_update([_eval("b", "complete")])
    p.srv.apply_eval_update([_eval("c", "complete"), _eval("d")])
    t.join(0.3)
    assert t.is_alive() and not got and watch.live_waiters() == 1, \
        "a write to evaluations b, c woke the read of a"
    p.srv.apply_eval_update([_eval("a", "complete")])
    t.join(10)
    assert got and got[0]["eval"]["status"] == "complete"
    assert got[0]["index"] == got[0]["eval"]["modify_index"] \
        == p.srv.fsm.state.get_index("evals")
    assert watch.live_waiters() == 0 and watch.stats()["timeouts"] == 0


def _eval_case_its_reap_wakes_it_and_answers_null(p):
    state = p.srv.fsm.state
    p.srv.apply_eval_update([_eval("a"), _eval("b")])
    t, got = _blocked_read(p, "a", _row_index(p, "a"))
    reap_index = state.latest_index() + 1
    state.delete_eval(reap_index, ["bq-eval-a"], [])
    t.join(10)
    assert got and got[0]["eval"] is None, "woken by the reap, not left " \
        "to its timeout"
    assert got[0]["index"] == reap_index == state.get_index("evals")
    assert state.watch.live_waiters() == 0
    assert state.watch.stats()["timeouts"] == 0


def _eval_case_answers_at_once_past_the_rows_index(p):
    p.srv.apply_eval_update([_eval("a")])
    first = _row_index(p, "a")
    p.srv.apply_eval_update([_eval("a", "complete")])
    p.srv.apply_eval_update([_eval("b")])   # the table moves on
    t0 = time.monotonic()
    resp = _get(p, "a", min_query_index=first, max_query_time=10.0)
    assert time.monotonic() - t0 < 5.0
    assert resp["eval"]["status"] == "complete"
    assert resp["index"] == resp["eval"]["modify_index"] == first + 1 \
        < p.srv.fsm.state.get_index("evals")
    assert p.srv.fsm.state.watch.stats()["delivered"] == 0, \
        "answered without parking"


def _eval_case_the_tables_later_writes_do_not_answer_it(p):
    """The table's index has passed the caller's; the row's has not."""
    p.srv.apply_eval_update([_eval("a")])
    p.srv.apply_eval_update([_eval("b")])
    t0 = time.monotonic()
    resp = _get(p, "a", min_query_index=_row_index(p, "a"),
                max_query_time=0.3)
    assert time.monotonic() - t0 >= 0.2, "b's write answered a's read"
    assert resp["eval"]["status"] == "pending"
    assert resp["index"] == _row_index(p, "a")


def _eval_case_unknown_id_falls_back_to_the_tables_index(p):
    state = p.srv.fsm.state
    p.srv.apply_eval_update([_eval("a")])
    p.srv.apply_eval_update([_eval("b")])
    table = state.get_index("evals")
    for args in ({}, {"min_query_index": table - 1,
                      "max_query_time": 10.0}):
        t0 = time.monotonic()
        resp = _get(p, "nope", **args)
        assert time.monotonic() - t0 < 5.0
        assert resp["eval"] is None and resp["index"] == table
    # At the table's index it parks, and wakes when that id is written.
    t, got = _blocked_read(p, "nope", table)
    p.srv.apply_eval_update([_eval("nope")])
    t.join(10)
    assert got and got[0]["eval"]["id"] == "bq-eval-nope"
    assert got[0]["index"] == got[0]["eval"]["modify_index"] == table + 1
    assert state.watch.stats()["timeouts"] == 0


def _eval_case_write_between_check_and_subscribe_is_delivered(p):
    """The lost-wakeup recheck reads the ROW: a write to a landing
    after the index check and before the subscribe is delivered at
    once; a write to b landing there is not."""
    watch = p.srv.fsm.state.watch
    p.srv.apply_eval_update([_eval("a"), _eval("b")])
    subscribe = watch.subscribe
    gap_writes = [_eval("b", "complete"), _eval("a", "complete")]

    def subscribe_after_a_write(key, *args, **kw):
        p.srv.apply_eval_update([gap_writes.pop(0)])
        return subscribe(key, *args, **kw)
    watch.subscribe = subscribe_after_a_write
    try:
        got = []
        t = threading.Thread(target=lambda: got.append(_get(
            p, "a", min_query_index=_row_index(p, "a"),
            max_query_time=20.0)))
        t.start()
        wait_until(lambda: len(gap_writes) == 1 and
                   watch.live_waiters() == 1,
                   msg="parked through b's write in the gap")
        t.join(0.3)
        assert t.is_alive() and not got, \
            "the recheck read the table's index, not the row's"
        watch.subscribe = subscribe
        p.srv.apply_eval_update([_eval("a", "failed")])
        t.join(10)
        assert got and got[0]["eval"]["status"] == "failed"
        watch.subscribe = subscribe_after_a_write
        t0 = time.monotonic()
        resp = _get(p, "a", min_query_index=got[0]["index"],
                    max_query_time=20.0)
        assert time.monotonic() - t0 < 5.0 and not gap_writes
        assert resp["eval"]["status"] == "complete"
        assert resp["index"] == resp["eval"]["modify_index"] \
            == got[0]["index"] + 1
        assert watch.live_waiters() == 0
        assert watch.stats()["timeouts"] == 0
    finally:
        watch.subscribe = subscribe


def _eval_case_write_inside_the_answer_cannot_move_its_index(p):
    """A write to a landing while its answer is being built (after the
    row was fetched) must not be covered by the answer's index: the
    index is the fetched row's, so the re-ask finds the write."""
    p.srv.apply_eval_update([_eval("a")])
    first = _row_index(p, "a")
    to_dict = Evaluation.to_dict
    pending_writes = [_eval("a", "complete")]

    def to_dict_after_a_write(self):
        if self.id == "bq-eval-a" and pending_writes:
            p.srv.apply_eval_update([pending_writes.pop()])
        return to_dict(self)
    Evaluation.to_dict = to_dict_after_a_write
    try:
        resp = _get(p, "a")
    finally:
        Evaluation.to_dict = to_dict
    assert not pending_writes and _row_index(p, "a") == first + 1
    assert resp["eval"]["status"] == "pending"
    assert resp["index"] == resp["eval"]["modify_index"] == first
    again = _get(p, "a", min_query_index=resp["index"],
                 max_query_time=0.5)
    assert again["eval"]["status"] == "complete"
    assert p.srv.fsm.state.watch.stats()["timeouts"] == 0


def _eval_case_index_is_the_answered_rows_under_a_writer(p):
    """A writer updates a in a loop while readers re-ask with the index
    they were given (a shortened switch interval widens every window
    between an answer and its index): every answer's index is that
    answer's ``modify_index``, so no reader ever holds ``pending`` with
    an index at or past the ``complete`` write, and every wait ends by
    a wake."""
    state = p.srv.fsm.state
    p.srv.apply_eval_update([_eval("a")])

    def writer():
        for i in range(40):
            p.srv.apply_eval_update([_eval("a"), _eval(f"x{i}")])
        p.srv.apply_eval_update([_eval("a", "complete")])

    def reader(answers):
        index = 0
        while True:
            resp = _get(p, "a", min_query_index=index,
                        max_query_time=30.0)
            answers.append((resp["eval"]["status"], resp["index"],
                            resp["eval"]["modify_index"], index))
            index = resp["index"]
            if resp["eval"]["status"] == "complete":
                return

    logs = [[] for _ in range(4)]
    threads = [threading.Thread(target=reader, args=(log,))
               for log in logs] + [threading.Thread(target=writer)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(25)
            assert not t.is_alive(), "a reader parked past its complete"
    finally:
        sys.setswitchinterval(interval)
    complete_index = _row_index(p, "a")
    for answers in logs:
        assert all(i == m for _s, i, m, _asked in answers), answers
        assert all(i > asked for _s, i, _m, asked in answers), \
            ("a wake moved nothing", answers)
        assert all(i < complete_index for s, i, _m, _asked in answers
                   if s == "pending"), answers
        assert answers[-1][:3] == ("complete", complete_index,
                                   complete_index)
    assert state.watch.stats()["timeouts"] == 0
    assert state.watch.live_waiters() == 0


EVAL_CASES = [
    _eval_case_other_evals_writes_leave_it_parked,
    _eval_case_its_reap_wakes_it_and_answers_null,
    _eval_case_answers_at_once_past_the_rows_index,
    _eval_case_the_tables_later_writes_do_not_answer_it,
    _eval_case_unknown_id_falls_back_to_the_tables_index,
    _eval_case_write_between_check_and_subscribe_is_delivered,
    _eval_case_write_inside_the_answer_cannot_move_its_index,
    _eval_case_index_is_the_answered_rows_under_a_writer,
]


@pytest.mark.parametrize("path_cls", [_InprocPath, _MuxPath],
                         ids=lambda c: c.name)
@pytest.mark.parametrize("case", EVAL_CASES, ids=lambda c: c.__name__[11:])
def test_eval_read_watches_its_own_evaluation(case, path_cls):
    with path_cls() as p:
        case(p)
