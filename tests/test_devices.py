"""Device-plane resolver semantics (nomad_tpu/parallel/devices.py).

The round-4 multi-chip failure was a mixed-backend ``device_put``; the
resolver is the one authority that prevents it.  These tests pin/re-pin
``jax_default_device`` and assert the cache-invalidation policy:
same-platform re-pins keep buffers, platform changes invalidate.
"""
import jax
import numpy as np
import pytest

from nomad_tpu.parallel.devices import (
    configure_compile_cache,
    current_platform,
    default_device,
    default_platform,
    default_platform_devices,
    ensure_on_default,
    on_default_platform,
    transient_device_fault,
)


@pytest.fixture
def restore_pin():
    prior = jax.config.jax_default_device
    yield
    jax.config.update("jax_default_device", prior)


def test_default_platform_devices_follow_pin(restore_pin):
    cpus = jax.devices("cpu")
    jax.config.update("jax_default_device", cpus[0])
    assert default_platform() == "cpu"
    assert default_platform_devices() == cpus
    assert default_device() is cpus[0]


def test_string_pin_resolves(restore_pin):
    jax.config.update("jax_default_device", "cpu")
    assert default_platform() == "cpu"
    assert default_device() is jax.devices("cpu")[0]


def test_same_platform_repin_keeps_cached_buffer(restore_pin):
    cpus = jax.devices("cpu")
    jax.config.update("jax_default_device", cpus[0])
    buf = ensure_on_default(None, np.ones(4, dtype=np.float32))
    assert on_default_platform(buf)
    # Re-pin to another device of the SAME platform: bench-scale fleet
    # tensors must not be re-uploaded.
    jax.config.update("jax_default_device", cpus[-1])
    assert on_default_platform(buf)
    assert ensure_on_default(buf, np.ones(4, dtype=np.float32)) is buf


def test_unpinned_checks_default_backend_platform(restore_pin):
    jax.config.update("jax_default_device", jax.devices("cpu")[0])
    buf = ensure_on_default(None, np.ones(4, dtype=np.float32))
    jax.config.update("jax_default_device", None)
    # Unpinned: the policy compares against the default backend's
    # platform (what a bare device_put would use), not "anything goes".
    assert current_platform() == jax.devices()[0].platform
    assert on_default_platform(buf) == \
        (jax.devices()[0].platform == "cpu")


def test_usage_mirror_survives_repin(restore_pin):
    import nomad_tpu.mock as mock
    from nomad_tpu.models.fleet import build_fleet

    cpus = jax.devices("cpu")
    jax.config.update("jax_default_device", cpus[0])
    fleet = build_fleet([mock.node(i) for i in range(4)])
    cap_d, res_d = fleet.device_capacity_reserved()
    assert on_default_platform(cap_d)
    # Same-platform re-pin: cache identity must be preserved.
    jax.config.update("jax_default_device", cpus[-1])
    cap2, res2 = fleet.device_capacity_reserved()
    assert cap2 is cap_d and res2 is res_d


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself: the
    function names that directory and sets nothing in code."""
    prior = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prior


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    """Unset, the cache goes to <checkout>/.jax_cache — a fixed path
    (it is part of the cache key), git-ignored, never a temp name."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prior = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert configure_compile_cache() == configure_compile_cache() \
            == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)
    with open(os.path.join(repo, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_compiler_refusal_is_not_a_transient_fault():
    """The breaker and the window verify absorb RUNTIME device faults
    only; a compiler refusal (and any non-runtime exception type) must
    propagate instead of parking the work on the host twin."""
    from nomad_tpu.faultinject import FaultDropped, FaultInjected

    # Verbatim from a TPU v5e (PERF.md, bring-up): what its compiler
    # says to a program that cannot fit.
    refusal = jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Allocation (size=360038400000) would "
        "exceed memory (size=17179869184) :: #allocation5 [shape = "
        "'f32[300000,300000]{1,0:T(8,128)}', space=hbm, size = "
        "0xffffffffffffffff, tag = 'output of "
        "broadcast_multiply_fusion@{}'] :: <no-hlo-instruction>")
    assert not transient_device_fault(refusal)
    assert not transient_device_fault(jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel"))
    # ... and what the runtime says to a buffer that does not fit.
    assert not transient_device_fault(ValueError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting "
        "to allocate 4.00G. That was not possible. There are 3.75G "
        "free.; (0x0x0_HBM0)"))
    assert not transient_device_fault(TypeError("bad operand"))
    assert not transient_device_fault(NotImplementedError("lowering"))
    assert transient_device_fault(jax.errors.JaxRuntimeError(
        "INTERNAL: device halted"))
    assert transient_device_fault(FaultInjected("device.dispatch"))
    assert transient_device_fault(FaultDropped("lost frame"))
    assert transient_device_fault(TimeoutError("collect deadline"))
