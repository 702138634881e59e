"""Runtime sanitizers: cross-check the static analysis with real runs.

Static analysis sees possible orders; these see ACTUAL ones.

**LockOrderWitness** — `threading.Lock/RLock/Condition` constructors are
wrapped so every lock created from package code carries its creation
*site* (file:line).  Each thread keeps a held-site stack; acquiring B
while holding A records the edge A→B.  A cycle in the observed graph
means two real code paths took the same pair of lock classes in
opposite orders — the textbook deadlock precondition, caught even when
the test run never actually deadlocks (exactly what `-race`-style
sanitizers are for).  Edges are keyed by site, not instance; same-site
nesting of distinct instances is collected separately (``self_edges``,
advisory — hierarchical same-class locking is often legitimate).

**RecompileSentinel** — snapshots the jit caches of the package's
registered kernels and fails any test session that retraces a kernel
past its budget.  Unbounded retracing is the silent performance failure
mode of the device path: every new (shape, static-arg) combination
costs a full XLA compile, and a kernel whose shapes aren't properly
bucketed erodes the bench headline without failing a single behavioral
test.

**TransferGuardSanitizer** — wraps the scheduler's device-dispatch
seams in ``jax.transfer_guard_host_to_device("disallow")`` scopes: any
IMPLICIT host->device transfer on a dispatch path (a host array or
scalar silently committed by jit) raises inside the test that caused
it.  This is the runtime twin of devlint's transfer-discipline pass:
the discipline says every intended transfer is explicit (`device_put`
through the counted seams — devices.put_counted / mesh._put /
ShardedResidency), so the guard can reject everything implicit without
false positives.  Direct kernel calls outside the scheduler seams
(parity tests feeding host arrays on purpose) are unaffected.

**BudgetWitnessSanitizer** — the runtime twin of faultlint's deadline
pass.  While a thread is inside an admitted RPC body
(``Endpoints._admitted_body``, heartbeat/liveness lane excluded), the
blocking primitives (``Event.wait`` / ``Condition.wait`` /
``Queue.get``) are wrapped to record any wait entered with NO timeout:
a ``timeout=None`` that the static pass can't see (a variable that
evaluates to None at runtime, a default leaking through a helper)
is caught on the actual serving thread, with the wait's stack, and
fails the test that caused it at its teardown.  Observe-only: the
wait still runs; cross-thread handoffs (a serving thread parking work
for an applier thread) are out of scope — faultlint's loop-surface
entries cover those statically.

All are opt-in via install()/uninstall() and wired into the test suite
by tests/test_static_analysis.py (and conftest, env-gated) — see
README "Static analysis & sanitizers".
"""
from __future__ import annotations

import os
import sys
import threading
from typing import Optional

_real_lock = threading.Lock
_real_rlock = threading.RLock
_real_condition = threading.Condition


class _WrappedLock:
    """Order-tracking proxy around a real Lock/RLock."""

    __slots__ = ("_inner", "_site", "_witness")

    def __init__(self, inner, site: str, witness: "LockOrderWitness"):
        self._inner = inner
        self._site = site
        self._witness = witness

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self._inner.acquire(blocking, timeout)
        if got:
            self._witness._on_acquire(self._site, id(self))
        return got

    def release(self) -> None:
        self._inner.release()
        self._witness._on_release(self._site, id(self))

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        return self._inner.locked()

    # Condition(wrapped_lock) support: Condition feature-detects
    # _release_save/_acquire_restore/_is_owned via attribute existence,
    # so these must exist exactly when the INNER lock has them (RLock
    # yes, Lock no) — hence __getattr__, not plain methods.  The
    # save/restore round-trip stays order-tracked.
    def __getattr__(self, name: str):
        if name == "_release_save":
            inner_fn = self._inner._release_save
            witness, site, me = self._witness, self._site, id(self)

            def _release_save():
                state = inner_fn()
                witness._on_release(site, me)
                return state
            return _release_save
        if name == "_acquire_restore":
            inner_fn = self._inner._acquire_restore
            witness, site, me = self._witness, self._site, id(self)

            def _acquire_restore(state):
                inner_fn(state)
                witness._on_acquire(site, me)
            return _acquire_restore
        if name in ("_is_owned", "_at_fork_reinit"):
            return getattr(self._inner, name)
        raise AttributeError(name)


class LockOrderWitness:
    """Records real lock-acquisition chains; reports order cycles."""

    def __init__(self, package_prefix: Optional[str] = None) -> None:
        if package_prefix is None:
            package_prefix = os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))
        self.package_prefix = os.path.abspath(package_prefix)
        self._tls = threading.local()
        self._graph_lock = _real_lock()
        self.edges: dict = {}     # (site_a, site_b) -> count
        self.self_edges: set = set()  # same-site, distinct-instance nests
        self.sites: set = set()
        self._installed = False
        self._saved: Optional[tuple] = None

    # -- recording ---------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _on_acquire(self, site: str, lock_id: int) -> None:
        stack = self._stack()
        if stack:
            top_site, top_id = stack[-1]
            if top_site != site:
                edge = (top_site, site)
                with self._graph_lock:
                    self.edges[edge] = self.edges.get(edge, 0) + 1
            elif top_id != lock_id:
                # Two INSTANCES of one lock class nested: advisory only
                # (hierarchical same-class locks are legitimate), kept
                # for inspection alongside the static
                # nested-self-acquire rule.
                with self._graph_lock:
                    self.self_edges.add(site)
        stack.append((site, lock_id))

    def _on_release(self, site: str, lock_id: int) -> None:
        stack = self._stack()
        # Locks are not always released LIFO: drop the innermost match.
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] == (site, lock_id):
                del stack[i]
                return

    def _site_of_caller(self) -> Optional[str]:
        frame = sys._getframe(2)
        fname = frame.f_code.co_filename
        if not os.path.abspath(fname).startswith(self.package_prefix):
            return None
        rel = os.path.relpath(os.path.abspath(fname),
                              os.path.dirname(self.package_prefix))
        return f"{rel}:{frame.f_lineno}"

    # -- install / uninstall ----------------------------------------------
    def install(self) -> "LockOrderWitness":
        """Patch the threading lock constructors; only locks created
        from files under ``package_prefix`` are wrapped."""
        if self._installed:
            return self
        # Save whatever is installed NOW (possibly another witness's
        # factories) so nested install/uninstall pairs restore correctly.
        self._saved = (threading.Lock, threading.RLock,
                       threading.Condition)
        witness = self

        def _wrap(inner, site):
            if site is None:
                return inner
            witness.sites.add(site)
            return _WrappedLock(inner, site, witness)

        def make_lock():
            return _wrap(_real_lock(), witness._site_of_caller())

        def make_rlock():
            return _wrap(_real_rlock(), witness._site_of_caller())

        def make_condition(lock=None):
            # A Condition over an (already wrapped) lock tracks through
            # the wrapper; a bare Condition() gets its own wrapped RLock
            # when created from package code (site = the Condition()
            # call, resolved HERE — one frame up would blame this file).
            if lock is None:
                lock = _wrap(_real_rlock(), witness._site_of_caller())
            return _real_condition(lock)

        threading.Lock = make_lock
        threading.RLock = make_rlock
        threading.Condition = make_condition
        self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        threading.Lock, threading.RLock, threading.Condition = self._saved
        self._installed = False
        self._saved = None

    def __enter__(self) -> "LockOrderWitness":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    # -- reporting ---------------------------------------------------------
    def find_cycles(self) -> list:
        """Elementary cycles in the observed order graph (site level)."""
        from .lockcheck import find_cycles

        graph: dict = {}
        with self._graph_lock:
            for (a, b) in self.edges:
                graph.setdefault(a, set()).add(b)
        return find_cycles(graph)

    def check(self) -> None:
        """Raise AssertionError when an order cycle was observed."""
        cycles = self.find_cycles()
        if cycles:
            lines = [" -> ".join(c + (c[0],)) for c in cycles]
            raise AssertionError(
                "lock-order cycles observed at runtime:\n  " +
                "\n  ".join(lines))


# ---------------------------------------------------------------------------
# Recompile sentinel
# ---------------------------------------------------------------------------

# Kernels the sentinel watches: (import path, attribute).  Each entry is
# the *wrapped* jit object whose cache growth is budgeted.
KERNEL_REGISTRY = (
    ("nomad_tpu.ops.binpack", "place_sequence"),
    ("nomad_tpu.ops.binpack", "place_rounds"),
    ("nomad_tpu.ops.binpack", "place_rounds_batch"),
    ("nomad_tpu.ops.binpack", "place_sequence_batch"),
)

# One kernel serves many (fleet size, placement bucket, static-arg)
# shapes per suite; buckets are powers of two so a healthy run stays far
# under this.  A kernel whose inputs stop hitting the buckets shows up
# as hundreds of entries, not tens.
DEFAULT_BUDGET = 24


def _cache_size(jitted) -> Optional[int]:
    for attr in ("_cache_size",):
        fn = getattr(jitted, attr, None)
        if callable(fn):
            try:
                return int(fn())
            except Exception:
                return None
    return None


# ---------------------------------------------------------------------------
# Transfer-guard sanitizer
# ---------------------------------------------------------------------------

# The dispatch seams the guard wraps: every scheduler-driven device
# dispatch flows through one of these.  (import path, class-or-None,
# attribute.)  Direct kernel calls — the parity suites deliberately
# feeding host arrays to ops.binpack — are NOT wrapped: the discipline
# is a property of the scheduler seams, not of the kernels.
TRANSFER_SEAMS = (
    ("nomad_tpu.scheduler.jax_binpack", "JaxBinPackScheduler",
     "dispatch_device"),
    ("nomad_tpu.scheduler.batch", "BatchEvalRunner", "_process"),
    ("nomad_tpu.models.fleet", "UsageMirror", "_update_device"),
    ("nomad_tpu.parallel.mesh", None, "place_sequence_sharded"),
    ("nomad_tpu.parallel.mesh", None, "place_rounds_sharded"),
    ("nomad_tpu.parallel.mesh", None, "place_rounds_batch_sharded"),
    ("nomad_tpu.parallel.mesh", None, "place_sequence_batch_sharded"),
)


class TransferGuardSanitizer:
    """Rejects IMPLICIT host->device transfers on the dispatch seams.

    Explicit transfers (jax.device_put through the counted seams) pass;
    a host value reaching jit commitment inside a wrapped seam raises
    XlaRuntimeError in the offending test.  The d2h direction is not
    guarded (the CPU test backend's zero-copy fetches never trip it);
    devlint's static concretize pass owns that side.
    """

    def __init__(self, seams=TRANSFER_SEAMS) -> None:
        self.seams = seams
        self._saved: list = []
        self._installed = False

    def install(self) -> "TransferGuardSanitizer":
        if self._installed:
            return self
        import importlib

        import jax

        def wrap(fn):
            def guarded(*args, **kwargs):
                with jax.transfer_guard_host_to_device("disallow"):
                    return fn(*args, **kwargs)
            guarded.__name__ = fn.__name__
            guarded.__qualname__ = getattr(fn, "__qualname__",
                                           fn.__name__)
            guarded.__wrapped__ = fn
            return guarded

        for mod_path, cls_name, attr in self.seams:
            try:
                mod = importlib.import_module(mod_path)
            except Exception:
                continue
            holder = getattr(mod, cls_name) if cls_name else mod
            fn = getattr(holder, attr, None)
            if fn is None:
                continue
            self._saved.append((holder, attr, fn))
            setattr(holder, attr, wrap(fn))
        self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        for holder, attr, fn in self._saved:
            setattr(holder, attr, fn)
        self._saved = []
        self._installed = False

    def __enter__(self) -> "TransferGuardSanitizer":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()


class RecompileSentinel:
    """Budgets jit-cache growth for the registered kernels."""

    def __init__(self, budget: int = DEFAULT_BUDGET,
                 extra: Optional[dict] = None) -> None:
        self.budget = budget
        self.extra = dict(extra or {})   # name -> jitted object
        self._baseline: dict = {}
        self.supported = True

    def _kernels(self) -> dict:
        import importlib

        out: dict = {}
        for mod_path, attr in KERNEL_REGISTRY:
            try:
                mod = importlib.import_module(mod_path)
            except Exception:
                continue
            fn = getattr(mod, attr, None)
            if fn is not None:
                out[f"{mod_path}.{attr}"] = fn
        out.update(self.extra)
        return out

    def install(self) -> "RecompileSentinel":
        sizes = {}
        for name, fn in self._kernels().items():
            size = _cache_size(fn)
            if size is None:
                self.supported = False
                continue
            sizes[name] = size
        self._baseline = sizes
        return self

    def report(self) -> dict:
        """name -> traces since install (only kernels with a baseline)."""
        out = {}
        for name, fn in self._kernels().items():
            if name not in self._baseline:
                continue
            size = _cache_size(fn)
            if size is not None:
                out[name] = size - self._baseline[name]
        return out

    def check(self) -> None:
        """Raise AssertionError when any kernel exceeded its budget."""
        over = {name: n for name, n in self.report().items()
                if n > self.budget}
        if over:
            detail = ", ".join(f"{k}: {v} traces (budget {self.budget})"
                               for k, v in sorted(over.items()))
            raise AssertionError(
                f"jit recompile budget exceeded — {detail}; either a "
                "shape stopped hitting its power-of-two bucket or a new "
                "call site passes unbucketed shapes (see "
                "nomad_tpu/ops/binpack.py docstring)")


class ReplicaDivergenceSanitizer:
    """Shadow-replica twin: the runtime proof of apply determinism.

    While installed, every ``NomadFSM`` constructed carries a hidden
    in-proc twin (no broker, no hooks, no trace spans).  Each raft
    entry the primary applies is re-applied to the twin, and
    ``store.fingerprint()`` is byte-compared at commit quiescence
    points — the first few applies (including the first applies after a
    ``restore``, which resets the count; restore itself compares lazily
    so fingerprinting doesn't materialize freshly restored columnar
    slabs), every ``interval`` thereafter, and at each test's teardown
    (``compare_all`` via conftest).  Any nondeterminism the static
    consensuslint pass can't
    see (a hash-order walk that escaped the AST patterns, a
    time-dependent value smuggled through a helper) diverges the twin
    and fails the test that caused it.

    Tests that seed state by writing the primary's store DIRECTLY
    (bypassing the raft log) would falsely diverge the twin, so each
    store counts its write-method commits (``_bump``) while the
    sanitizer is installed: a primary/twin commit-count mismatch means
    out-of-band writes, and that FSM's pair is dropped from comparison
    (counted in ``desynced``, not silent) instead of reported.

    Divergence raises inside the offending apply AND is recorded for
    ``check()`` at session teardown — a raise swallowed by a raft
    apply loop still fails the session.
    """

    def __init__(self, interval: int = 64) -> None:
        self.interval = interval
        self.mismatches: list = []
        self.desynced = 0
        self.compared = 0
        self._installed = False
        self._saved: list = []
        self._fsms: list = []     # weakrefs of primaries
        self._reg_lock = threading.Lock()
        self._tls = threading.local()

    # -- install/uninstall --------------------------------------------------
    def install(self) -> "ReplicaDivergenceSanitizer":
        if self._installed:
            return self
        import weakref

        from nomad_tpu.server.fsm import NomadFSM
        from nomad_tpu.state.store import StateStore

        san = self
        orig_init = NomadFSM.__init__
        orig_apply = NomadFSM.apply
        orig_restore = NomadFSM.restore
        orig_bump = StateStore._bump
        self._saved = [(NomadFSM, "__init__", orig_init),
                       (NomadFSM, "apply", orig_apply),
                       (NomadFSM, "restore", orig_restore),
                       (StateStore, "_bump", orig_bump)]

        def counted_bump(store, table, index):
            store._sanitizer_bumps = \
                getattr(store, "_sanitizer_bumps", 0) + 1
            return orig_bump(store, table, index)

        def init(fsm, *args, **kwargs):
            orig_init(fsm, *args, **kwargs)
            if getattr(san._tls, "constructing", False):
                return          # this IS a twin being built
            san._tls.constructing = True
            try:
                twin = NomadFSM()
            finally:
                san._tls.constructing = False
            # Shadow the span recorder on the twin: the obs plane's
            # exactly-once apply-span accounting must see each entry
            # once, not once per replica.
            twin._record_apply_spans = _noop_spans
            fsm._divergence_twin = twin
            fsm._divergence_lock = _real_lock()
            fsm._divergence_applied = 0
            with san._reg_lock:
                san._fsms.append(weakref.ref(fsm))

        def apply(fsm, index, entry):
            twin = getattr(fsm, "_divergence_twin", None)
            if twin is None:
                return orig_apply(fsm, index, entry)
            with fsm._divergence_lock:
                try:
                    result = orig_apply(fsm, index, entry)
                except BaseException:
                    # A deterministic rejection must hit the twin too,
                    # or the next compare reports a skew that isn't
                    # nondeterminism.
                    try:
                        orig_apply(twin, index, entry)
                    except BaseException:
                        pass
                    raise
                try:
                    orig_apply(twin, index, entry)
                except BaseException as e:
                    san._report(
                        fsm, index,
                        f"shadow twin raised {e!r} on an entry the "
                        f"primary applied cleanly")
                fsm._divergence_applied += 1
                n = fsm._divergence_applied
                if n <= 4 or n % san.interval == 0:
                    san._compare(fsm, twin, index)
                return result

        def restore(fsm, blob):
            twin = getattr(fsm, "_divergence_twin", None)
            if twin is None:
                return orig_restore(fsm, blob)
            with fsm._divergence_lock:
                result = orig_restore(fsm, blob)
                orig_restore(twin, blob)
                fsm._divergence_applied = 0
                # No eager compare here: fingerprint() would materialize
                # the freshly restored columnar slabs, destroying the
                # lazy-restore property tests assert on.  The first
                # post-restore applies and the per-test teardown sweep
                # compare the restored pair instead.
                return result

        NomadFSM.__init__ = init
        NomadFSM.apply = apply
        NomadFSM.restore = restore
        StateStore._bump = counted_bump
        self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        for holder, attr, fn in self._saved:
            setattr(holder, attr, fn)
        self._saved = []
        self._installed = False

    def __enter__(self) -> "ReplicaDivergenceSanitizer":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    # -- comparison ---------------------------------------------------------
    def _compare(self, fsm, twin, index: int) -> None:
        p_bumps = getattr(fsm.state, "_sanitizer_bumps", 0)
        t_bumps = getattr(twin.state, "_sanitizer_bumps", 0)
        if p_bumps != t_bumps:
            # Out-of-band direct store writes (test seeding): this
            # pair can never agree again; drop it, visibly.
            fsm._divergence_twin = None
            self.desynced += 1
            return
        self.compared += 1
        a = fsm.state.fingerprint()
        b = twin.state.fingerprint()
        if a != b:
            self._report(
                fsm, index,
                f"primary fingerprint {a[:16]}… != shadow twin "
                f"{b[:16]}… after identical entries")

    def _report(self, fsm, index: int, detail: str) -> None:
        # One report per pair: a diverged twin stays diverged, so drop
        # it rather than re-reporting at every later quiescence point.
        fsm._divergence_twin = None
        where = "restore" if index < 0 else f"index {index}"
        msg = (f"replica divergence at {where}: {detail} — the apply "
               f"path consumed a nondeterministic input (wall clock, "
               f"RNG, host env, or hash-order); see "
               f"analysis/consensuslint.py rules")
        self.mismatches.append(msg)
        raise AssertionError(msg)

    def compare_all(self) -> None:
        """Quiescence-point sweep (per-test teardown): fingerprint every
        live pair; raises on the first divergence found."""
        if not self._installed:
            return
        with self._reg_lock:
            refs = list(self._fsms)
            self._fsms = [r for r in refs if r() is not None]
        for ref in refs:
            fsm = ref()
            if fsm is None:
                continue
            twin = getattr(fsm, "_divergence_twin", None)
            if twin is None:
                continue
            with fsm._divergence_lock:
                self._compare(fsm, twin, index=fsm._divergence_applied)

    def check(self) -> None:
        """Session-teardown catch-all: any recorded divergence — even
        one whose in-apply raise was swallowed by a raft loop — fails
        the session."""
        if self.mismatches:
            raise AssertionError(
                "replica divergence observed during the session:\n" +
                "\n".join(f"  - {m}" for m in self.mismatches))


def _noop_spans(*args, **kwargs) -> None:
    return None


# ---------------------------------------------------------------------------
# Budget witness
# ---------------------------------------------------------------------------

class BudgetWitnessSanitizer:
    """Records unbounded waits taken on a thread serving an admitted RPC.

    The deadline discipline (server/overload.py) says every wait on a
    request path consumes the admitted envelope's budget.  faultlint
    proves the *syntactic* form; this witness proves the runtime one: a
    ``timeout=None`` hiding behind a variable or a default argument is
    invisible to the AST but lands here, on the actual serving thread,
    with the wait's call stack.

    Waits are recorded, never blocked — the per-test ``check_test()``
    (conftest ``budget_quiescence``) fails the offending test and
    resets; session ``check()`` is the catch-all for hits recorded
    outside any test body.  The heartbeat/liveness lane is exempt, same
    as the static pass.
    """

    def __init__(self, package_prefix: Optional[str] = None) -> None:
        if package_prefix is None:
            package_prefix = os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))
        self.package_prefix = os.path.abspath(package_prefix)
        self.hits: list = []        # (method, primitive, test, stack)
        self._tls = threading.local()
        self._hits_lock = _real_lock()
        self._installed = False
        self._saved: list = []

    # -- install/uninstall --------------------------------------------------
    def install(self) -> "BudgetWitnessSanitizer":
        if self._installed:
            return self
        import queue

        from nomad_tpu.server.endpoints import Endpoints
        from nomad_tpu.server.overload import HEARTBEAT_LANE

        san = self
        orig_body = Endpoints._admitted_body
        # Patch the REAL primitive classes saved at import time:
        # LockOrderWitness rebinds the threading.Condition *name* to a
        # factory, but its instances are still _real_condition objects,
        # so the method patch covers both installation orders.
        orig_event_wait = threading.Event.wait
        orig_cond_wait = _real_condition.wait
        orig_get = queue.Queue.get
        self._saved = [(Endpoints, "_admitted_body", orig_body),
                       (threading.Event, "wait", orig_event_wait),
                       (_real_condition, "wait", orig_cond_wait),
                       (queue.Queue, "get", orig_get)]

        def admitted_body(ep, method, handler, args):
            if method in HEARTBEAT_LANE or "heartbeat" in method.lower():
                return orig_body(ep, method, handler, args)
            prev = getattr(san._tls, "serving", None)
            san._tls.serving = method
            try:
                return orig_body(ep, method, handler, args)
            finally:
                san._tls.serving = prev

        def record(primitive: str) -> None:
            method = getattr(san._tls, "serving", None)
            if method is None:
                return
            # Only PACKAGE wait sites count — stdlib-internal waits
            # (Thread.start's _started handshake, Queue.get's internal
            # Condition) are not budget holders; this is the same
            # domain restriction the static pass has.
            caller = sys._getframe(2).f_code.co_filename
            if not os.path.abspath(caller).startswith(
                    san.package_prefix):
                return
            import traceback

            # Drop the two witness frames; keep the caller's chain.
            stack = "".join(traceback.format_stack(limit=14)[:-2])
            test = os.environ.get("PYTEST_CURRENT_TEST", "<no test>")
            with san._hits_lock:
                san.hits.append((method, primitive, test, stack))

        def event_wait(ev, timeout=None):
            if timeout is None:
                record("Event.wait")
            return orig_event_wait(ev, timeout)

        def cond_wait(cond, timeout=None):
            if timeout is None:
                record("Condition.wait")
            return orig_cond_wait(cond, timeout)

        def queue_get(q, block=True, timeout=None):
            if block and timeout is None:
                record("Queue.get")
            return orig_get(q, block, timeout)

        Endpoints._admitted_body = admitted_body
        threading.Event.wait = event_wait
        _real_condition.wait = cond_wait
        queue.Queue.get = queue_get
        self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        for holder, attr, fn in self._saved:
            setattr(holder, attr, fn)
        self._saved = []
        self._installed = False

    def __enter__(self) -> "BudgetWitnessSanitizer":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    # -- reporting ----------------------------------------------------------
    def _render(self, hits: list) -> str:
        lines = []
        for method, primitive, test, stack in hits:
            lines.append(
                f"unbounded {primitive} while serving {method} "
                f"(test: {test}):\n{stack}")
        return (
            "budget-witness: wait with no timeout on an RPC-serving "
            "thread — the admitted envelope's budget was dropped (see "
            "analysis/faultlint.py deadline pass):\n" +
            "\n".join(lines))

    def check_test(self) -> None:
        """Per-test teardown: fail THIS test on any hit it recorded,
        then reset so later tests report only their own."""
        with self._hits_lock:
            hits, self.hits = self.hits, []
        if hits:
            raise AssertionError(self._render(hits))

    def check(self) -> None:
        """Session catch-all for hits recorded outside any test body
        (module fixtures, background threads between tests)."""
        with self._hits_lock:
            hits = list(self.hits)
        if hits:
            raise AssertionError(self._render(hits))
