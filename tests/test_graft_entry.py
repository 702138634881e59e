"""Smoke tests for the driver entry points (__graft_entry__.py).

The round-4 multi-chip artifact failed because nothing in the suite ever
executed ``dryrun_multichip`` — a mixed-backend ``device_put`` shipped
silently.  These tests run the REAL driver entry points in a subprocess
under the driver's own conditions (``--xla_force_host_platform_device_count=8``)
so a device-plane backend leak can never ship silently again.
"""
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout: int = 600) -> subprocess.CompletedProcess:
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    # Force EXACTLY 8 virtual devices (the driver's condition), replacing
    # any pre-existing count so the test is hermetic in any shell.
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_dryrun_multichip_8():
    """The driver's multi-chip acceptance path, end to end, 8 devices."""
    r = _run("import __graft_entry__ as g; g.dryrun_multichip(8)")
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    assert "dryrun_multichip(8)" in r.stdout
    assert "parity" in r.stdout
    # The forced-device pipeline (NOMAD_TPU_EXECUTOR=device) must
    # really dispatch on the mesh platform — AND, with sharding
    # first-class, every one of those dispatches must have ridden the
    # node-axis mesh.
    m = re.search(r"executor=device device_fraction=([0-9.]+) "
                  r"sharded_dispatches=(\d+) placed=(\d+)", r.stdout)
    assert m, r.stdout[-2000:]
    assert float(m.group(1)) > 0, r.stdout[-2000:]
    assert int(m.group(2)) > 0, r.stdout[-2000:]
    assert int(m.group(3)) > 0, r.stdout[-2000:]
    # The columnar node-table bridge phase ran.
    assert "columnar slab bridge" in r.stdout


def test_entry_compiles():
    """entry() must return a jittable fn + example args (driver contract)."""
    r = _run(
        "import __graft_entry__ as g\n"
        "import jax, numpy as np\n"
        "fn, args = g.entry()\n"
        "out = jax.jit(fn)(*args)\n"
        "chosen = np.asarray(out[0])\n"
        "assert (chosen >= 0).all(), chosen\n"
        "print('entry-ok')\n")
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    assert "entry-ok" in r.stdout
