"""Introspection: host thread stacks + device (XLA) profiler control.

Capability parity with the reference's pprof mount
(/root/reference/command/agent/http.go:115-120 — net/http/pprof under
``enableDebug``) re-thought for this runtime: the host side dumps live
Python thread stacks (the pprof-goroutine equivalent) and the device side
toggles ``jax.profiler`` traces around the scheduler's XLA dispatches
(SURVEY §5 "add JAX profiler/XLA dump hooks around the device dispatch").
"""
from __future__ import annotations

import sys
import threading
import traceback
from typing import Optional

_lock = threading.Lock()
_trace_dir: Optional[str] = None


def thread_stacks() -> dict:
    """Stacks of every live thread, keyed by thread name — the
    goroutine-dump analogue served at /v1/agent/pprof."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for ident, frame in sys._current_frames().items():
        name = names.get(ident, f"thread-{ident}")
        out[f"{name} ({ident})"] = [
            {"file": fs.filename, "line": fs.lineno, "func": fs.name,
             "code": (fs.line or "").strip()}
            for fs in traceback.extract_stack(frame)
        ]
    return out


def start_device_trace(log_dir: str) -> None:
    """Begin a jax.profiler trace capturing every XLA dispatch until
    stopped; the directory is TensorBoard/xprof-loadable."""
    global _trace_dir
    import jax

    with _lock:
        if _trace_dir is not None:
            raise RuntimeError(f"device trace already active in "
                               f"{_trace_dir!r}")
        jax.profiler.start_trace(log_dir)
        _trace_dir = log_dir


def stop_device_trace() -> str:
    global _trace_dir
    import jax

    with _lock:
        if _trace_dir is None:
            raise RuntimeError("no device trace active")
        jax.profiler.stop_trace()
        done, _trace_dir = _trace_dir, None
        return done


def active_trace_dir() -> Optional[str]:
    # Under _lock like every other _trace_dir access: a bare read could
    # observe a torn start/stop transition from another thread (and the
    # lockcheck gate rightly flags guarded attrs read unlocked).
    with _lock:
        return _trace_dir
