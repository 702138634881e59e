"""Loader for the optional C++ extension (_nomad_native).

The extension accelerates the host scheduling plane's hot loops (dynamic
port assignment — see native/port_alloc.cpp).  The .so is never committed
(it is platform/ABI-specific): on first import we try to build it from
source with ``native/build.py``; pure-Python fallbacks keep everything
working when the toolchain is unavailable.
"""
from __future__ import annotations

import logging
import os
import subprocess
import sys

logger = logging.getLogger("nomad_tpu.utils.native")

_repo = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _try_build() -> None:
    repo = _repo
    script = os.path.join(repo, "native", "build.py")
    src = os.path.join(repo, "native", "port_alloc.cpp")
    marker = os.path.join(repo, "native", ".build_failed")
    if not os.path.exists(script):
        raise ImportError("no native source tree")
    # A failed build leaves a marker so every later interpreter start
    # doesn't re-pay the compile attempt; editing the source retries.
    if os.path.exists(marker) and \
            os.path.getmtime(marker) >= os.path.getmtime(src):
        raise ImportError("previous native build failed")
    try:
        # faultlint-ok(uninjectable-io): import-time toolchain probe;
        # any failure routes to the pure-Python fallback below.
        subprocess.run([sys.executable, script], check=True,
                       capture_output=True, timeout=120)
    except Exception as e:
        logger.warning("native extension build failed, using pure-Python "
                       "fallback: %s", e)
        try:
            with open(marker, "w") as fh:
                fh.write(str(e))
        except OSError:
            pass
        raise


# The ABI version this checkout's Python code expects; must match
# native/port_alloc.cpp's exported ABI_VERSION.  A same-name signature
# change is invisible to hasattr() probes, so a stale prebuilt .so would
# otherwise crash mid-eval.
EXPECTED_ABI = 7


def _stale(repo: str) -> bool:
    """Is the built .so older than its source?  Rebuild-before-import
    keeps an already-built checkout working across signature changes
    (the in-process module object cannot be reloaded once imported)."""
    import sysconfig
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    so = os.path.join(repo, f"_nomad_native{suffix}")
    src = os.path.join(repo, "native", "port_alloc.cpp")
    try:
        return os.path.getmtime(so) < os.path.getmtime(src)
    except OSError:
        return False  # missing .so: normal import-failure path rebuilds


try:
    if _stale(_repo):
        try:  # pragma: no cover - toolchainless host
            _try_build()
        except Exception:
            # Import whatever exists anyway: a comment-only source touch
            # leaves the on-disk .so ABI-compatible and the gate below
            # accepts it; a genuinely old ABI is rejected there.
            pass
    import _nomad_native as native  # type: ignore

    HAS_NATIVE = True
except ImportError:
    try:  # pragma: no cover - exercised on unbuilt checkouts
        _try_build()
        import _nomad_native as native  # type: ignore

        HAS_NATIVE = True
    except Exception:
        native = None
        HAS_NATIVE = False

if HAS_NATIVE and getattr(native, "ABI_VERSION", 0) != EXPECTED_ABI:
    # An already-imported C extension cannot be reloaded in-process:
    # rebuild now so the NEXT process start imports a matching build,
    # and run this process on the pure-Python fallbacks.
    try:  # pragma: no cover - stale prebuilt .so
        _try_build()
        _refreshed = "rebuilt for next start"
    except Exception as _e:
        _refreshed = f"rebuild failed ({_e}); next start will retry"
    logger.warning(
        "native extension ABI %s != expected %s (stale build); %s, "
        "using pure-Python fallbacks now",
        getattr(native, "ABI_VERSION", 0), EXPECTED_ABI, _refreshed)
    native = None
    HAS_NATIVE = False
