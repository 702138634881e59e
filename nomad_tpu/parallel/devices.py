"""Device-plane resolution: ONE authority for "which devices do we compute on".

More than one jax backend can be registered in a process (the TPU
AND the host CPU platform); ``jax.devices()`` favors whichever backend
wins registration, which is NOT necessarily the platform the runtime
was pinned to (tests pin ``jax.config.jax_default_device`` to cpu:0
over an 8-virtual-device host mesh).  Every device-plane entry point —
mirror uploads, mesh construction, backend probes — must resolve
devices through here so host tensors, meshes and jitted dispatches all
land on ONE platform.  Mixing backends (CPU mesh kernels + a
default-backend mirror upload) fails every sharded dispatch.

Capability parity role: the reference has no analogue — its compute
plane is the Go runtime itself.  This module is the TPU-native seam
between the host data plane and the XLA device plane.
"""
from __future__ import annotations

import contextlib
import os
import threading

from typing import Optional

import jax

from nomad_tpu.faultinject import FaultInjected
from nomad_tpu.obs import trace as trace_mod

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Called once by every entry point that compiles (agent boot,
    chip_smoke.py, benchmarks/run.py) before the first jit.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set here; otherwise the cache lives at the fixed,
    git-ignored ``<checkout>/.jax_cache``.  The path is part of the
    cache key, so it is never derived from a temp dir, pid or clock.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# Status codes under which XLA reports that a program does not fit or
# cannot be built for the chip.  Seen on a TPU v5e (PERF.md, bring-up):
# the compiler refuses an over-large program with JaxRuntimeError
# "RESOURCE_EXHAUSTED: Allocation (size=...) would exceed memory
# (size=...)" — no "compile" in the text — and a buffer that does not
# fit at run time comes back as a ValueError with the same code.
_REFUSAL_CODES = ("RESOURCE_EXHAUSTED", "INVALID_ARGUMENT", "UNIMPLEMENTED")


def transient_device_fault(e: Exception) -> bool:
    """Is ``e`` a RUNTIME device fault a caller may absorb by re-running
    the work on its host twin (the eval pipeline's breaker, the window
    verify's host engine)?  Injected faults, timeouts and transport
    errors, and XLA runtime errors qualify.  A refusal does not — a
    program the compiler rejects or the chip cannot hold fails the same
    way on every retry, so absorbing it would park the whole stream on
    the host twin behind a working-looking server: JaxRuntimeErrors
    carrying a refusal status code or naming compilation propagate, and
    so does every other exception type out of a dispatch (ValueError,
    TypeError, NotImplementedError from trace/lowering/allocation)."""
    if isinstance(e, jax.errors.JaxRuntimeError):
        msg = str(e)
        return not msg.startswith(_REFUSAL_CODES) and \
            "compil" not in msg.lower()
    return isinstance(e, (FaultInjected, OSError, TimeoutError))


# -- transfer accounting ----------------------------------------------------
# Every EXPLICIT host<->device transfer the runtime performs is counted
# here — the data plane's odometer.  The discipline (enforced by
# analysis/devlint.py statically and the transfer-guard sanitizer at
# runtime) is that device dispatches perform NO implicit transfers:
# everything that crosses the PCIe/ICI boundary goes through one of the
# explicit seams below (put_counted / ensure_on_default / mesh._put /
# ShardedResidency / fetch_host), so "how many transfers per eval" is a
# number a run can record instead of a guess.

_TRANSFER_LOCK = threading.Lock()
_TRANSFERS = {"h2d": 0, "d2h": 0, "d2d": 0}
# Bytes this thread moved through the counted seams since its last
# ``device.dispatch`` span closed (tracing only): a dispatch's uploads
# happen before the program is enqueued, its fetch after.
_moved = threading.local()


def note_transfer(kind: str, n: int = 1, *moved) -> None:
    """Count ``n`` explicit transfers of ``kind`` ("h2d"/"d2h"/"d2d").
    ``moved`` are the arrays themselves: while tracing is on their bytes
    accrue to the calling thread's next ``device.dispatch`` span."""
    with _TRANSFER_LOCK:
        _TRANSFERS[kind] += n
    if trace_mod.ENABLED and moved:
        held = _moved.__dict__
        held[kind] = held.get(kind, 0) + sum(
            int(getattr(x, "nbytes", 0)) for x in moved)


def moved_bytes(kind: str) -> int:
    """Bytes of ``kind`` the calling thread moved through the counted
    seams since its last ``device.dispatch`` span closed (tracing
    only): a site reads it before and after a block of uploads to say
    what that block moved, and takes nothing from the span's count."""
    return _moved.__dict__.get(kind, 0)


# -- device.dispatch spans (obs/trace.py) ----------------------------------
# What a dispatch site enters with tracing off:
#   with (device_dispatch(kernel, lanes=B, ...) if trace_mod.ENABLED
#         else NO_DISPATCH):
# so the tag dict is only ever built behind the gate.
NO_DISPATCH = contextlib.nullcontext()


@contextlib.contextmanager
def device_dispatch(program, async_: bool = False, **tags):
    """Bracket one call of a jitted program with a ``device.dispatch``
    span: enqueue -> results on the host where the caller fetches inside
    the block, enqueue -> return (tagged ``async=1``) where the caller
    collects later — the block adds no sync of its own.  ``program`` is
    the jitted callable; the tag is its ``__name__``, which is what the
    device plane's ``XLA Modules`` line shows after ``jit_``, so a
    reader can lay the k-th span of a program on its k-th module event
    and put both clocks on one axis (benchmarks/reducers/
    idle_attribution.py).  Shape tags (``lanes``, ``b_pad``, ``g_pad``,
    ``k_cap``, ``rounds``, ``n_pad``, ``rows``) are the site's;
    ``h2d_bytes``/``d2h_bytes`` are what this thread moved through the
    counted seams since its previous span.  A
    ``jax.profiler.TraceAnnotation`` of the same name is open for the
    block, so a profiler capture with the host tracer on shows the
    dispatch beside the device ops.  Yields the span's tag dict (None
    where a ``disable()`` raced the gate, as ``NO_DISPATCH`` does): what
    a site learns inside the block (``fetch_s``, the seconds of its
    fetch) it adds there.  Not to be confused with the
    fault-injection site of the same name (scheduler/pipeline.py)."""
    tracer = trace_mod.tracer()
    if tracer is None:   # a disable() raced the site's gate
        yield None
        return
    t0 = tracer.now()
    with jax.profiler.TraceAnnotation("device.dispatch"):
        yield tags
    held = _moved.__dict__
    if async_:
        tags["async"] = 1
    tracer.record("device.dispatch", t0, tracer.now() - t0,
                  parent_ctx=tracer.ctx(),
                  program=getattr(program, "__name__", str(program)),
                  h2d_bytes=held.pop("h2d", 0),
                  d2h_bytes=held.pop("d2h", 0), **tags)


def transfer_counts() -> dict:
    """Snapshot of the process-lifetime explicit-transfer counters."""
    with _TRANSFER_LOCK:
        return dict(_TRANSFERS)


def default_platform() -> Optional[str]:
    """Platform name of the pinned default device, or None when unpinned.

    ``jax.config.jax_default_device`` may hold a Device or a platform
    string (jax accepts both).
    """
    default = jax.config.jax_default_device
    if default is None:
        return None
    return getattr(default, "platform", None) or str(default).split(":")[0]


def default_platform_devices() -> list:
    """Devices of the platform the runtime actually computes on.

    When a default device is pinned, ALL devices of ITS platform (so an
    8-virtual-device CPU pin yields the whole 8-device mesh); otherwise
    whatever ``jax.devices()`` resolves to.
    """
    platform = default_platform()
    if platform is None:
        return jax.devices()
    return jax.devices(platform)


def default_device():
    """The device unsharded host->device uploads must target (or None).

    ``jax.device_put(x)`` with no device argument lands on the *default
    backend's* device 0 and IGNORES the pinned default device; passing
    this explicitly keeps single-buffer mirrors on the same platform as
    the meshes built from :func:`default_platform_devices`.  Returns
    None when nothing is pinned, which ``jax.device_put`` accepts and
    treats as the unpinned default — same behavior, one code path.
    """
    default = jax.config.jax_default_device
    if default is None:
        return None
    if isinstance(default, str):
        return jax.devices(default_platform())[0]
    return default


def current_platform() -> str:
    """Platform the runtime computes on RIGHT NOW: the pinned default
    device's platform, or the default backend's when nothing is pinned
    (what an argument-less ``jax.device_put`` / unjitted dispatch would
    use)."""
    platform = default_platform()
    if platform is None:
        platform = jax.devices()[0].platform
    return platform


def on_default_platform(arr) -> bool:
    """Is this cached device buffer resident on :func:`current_platform`?

    Device-buffer caches (mirror usage, capacity/reserved, feasibility)
    outlive a runtime re-pin of ``jax_default_device`` (e.g. the
    multi-chip dry run pins the mesh platform mid-process, then restores
    the prior pin); serving a stale buffer would recreate the
    mixed-backend dispatch this module exists to prevent, so caches call
    this and re-upload on mismatch.  Platform-level on purpose: a
    same-platform re-pin (cpu:0 -> cpu:3) must NOT invalidate
    bench-scale fleet tensors.
    """
    return next(iter(arr.devices())).platform == current_platform()


def ensure_on_default(cached, host):
    """Device copy of ``host`` on the current platform, reusing
    ``cached`` when it is still resident there.

    The one invalidation policy for every single-buffer device cache:
    callers keep whatever cache structure they need and route
    (cached, host) pairs through here.  Returns ``cached`` itself when
    it is valid, so callers can detect a re-upload by identity.
    """
    if cached is not None and on_default_platform(cached):
        return cached
    note_transfer("h2d", 1, host)
    return jax.device_put(host, default_device())


def classify_move(src_platform: str, dst_platform: str) -> str:
    """The ONE h2d/d2h/d2d classification rule for an explicit move of
    a jax.Array between platforms (shared by put_counted and
    mesh._put so the odometer cannot drift between seams): a move
    whose source or destination is the cpu backend crosses the host
    boundary — cpu jax buffers live in host memory — and counting it
    d2d would under-report the h2d odometer."""
    if src_platform == "cpu" and dst_platform != "cpu":
        return "h2d"
    if dst_platform == "cpu" and src_platform != "cpu":
        return "d2h"
    return "d2d"


def put_counted(x, device=None):
    """EXPLICIT placement of one per-dispatch host value onto the
    current platform (counted).  The dispatch seams route every
    per-eval varying argument (usage views, job counts, fused lane
    stacks) through here instead of letting jit commit them implicitly
    — an implicit transfer is invisible to the odometer AND trips the
    transfer-guard sanitizer; an explicit one is accounted.  Arrays
    already resident on the default platform pass through untouched."""
    if isinstance(x, jax.Array):
        if on_default_platform(x):
            return x
        src = next(iter(x.devices())).platform
        note_transfer(classify_move(src, current_platform()), 1, x)
        return jax.device_put(x, device or default_device())
    note_transfer("h2d", 1, x)
    return jax.device_put(x, device or default_device())


def fetch_host(x):
    """EXPLICIT device->host fetch (counted): the one sanctioned way a
    device result becomes a numpy array.  ``jax.device_get`` (not
    ``np.asarray``) so the transfer survives a d2h transfer guard; host
    values pass through untouched."""
    if isinstance(x, jax.Array):
        note_transfer("d2h", 1, x)
        return jax.device_get(x)
    return x
