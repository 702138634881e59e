"""chip_smoke.py on the CPU: the command runs end to end at rehearsal
size, and can never pass without a chip.

The script is the proof, run on the TPU after every PR, that the served
scheduling path still starts there.  These tests keep the COMMAND
working from a sandbox that has no accelerator: the rehearsal drives the
same phases at a tiny size on a forced four-device CPU platform (so the
multi-chip checks run too), and the real command on a CPU must exit
non-zero before printing any result.
"""
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _start(args: list, out_dir, extra_env=None) -> subprocess.Popen:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("NOMAD_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--out", str(out_dir), *args],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The invocations: three started together (the rehearsal takes
    ~13 s, and tier-1 is kill-bound), then the deployment's (5 s; on
    its own, so that the box is no busier than with three):
    name -> (returncode, out, err)."""
    out_dir = tmp_path_factory.mktemp("chip_smoke")
    files = {"config": {"nodes": 64, "node": {
                 "cpu": 9600, "memory_mb": 100000, "disk_mb": 102400,
                 "iops": 150, "mbits": 1000}},
             "traffic": {"job": {"type": "batch", "groups_cycle": [3, 4],
                                 "count": 10,
                                 "ask": {"cpu": 100, "memory_mb": 1041}}}}
    for name, content in files.items():
        (out_dir / f"{name}.json").write_text(json.dumps(content))
    waves = [lambda: {
        "rehearsal": _start(["--rehearse"], out_dir),
        "no_chip": _start([], out_dir),
        "lever_set": _start(["--rehearse"], out_dir,
                            {"NOMAD_TPU_EXECUTOR": "host"}),
    }, lambda: {
        "deployment": _start(
            ["--rehearse", "--config", str(out_dir / "config.json"),
             "--traffic", str(out_dir / "traffic.json")], out_dir),
    }]
    done = {}
    for wave in waves:
        procs = wave()
        try:
            for name, proc in procs.items():
                out, err = proc.communicate(timeout=600)
                done[name] = (proc.returncode, out, err)
        finally:
            for proc in procs.values():
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
    return done


def test_rehearsal_runs_every_phase_and_never_reads_as_a_pass(runs):
    rc, stdout, stderr = runs["rehearsal"]
    assert rc == 0, (stdout[-2000:], stderr[-4000:])
    # Stdout is the report, then the verdict in exactly the shape the
    # chip check reads.
    report_line, verdict_line = stdout.strip().splitlines()
    assert json.loads(verdict_line) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    out = json.loads(report_line)
    assert out["ok"] is False and out["rehearsal"] is True
    assert out["device"] == json.loads(verdict_line)["device"]
    assert out["native"]["built_from_source"] is True
    assert out["compile_cache"]["dir"] == os.path.join(REPO, ".jax_cache")
    for phase in ("phase_a", "phase_b"):
        assert out[phase]["placements_committed"] == \
            out["reference"]["placements"] == 5 * 24
        assert out[phase]["breaker"] == {"failures": 0, "opens": 0}
    mix = out["phase_b"]["dispatch_mix"]
    assert mix["host_dispatches"] == 0
    assert mix["device_dispatches"] >= mix["fused_batches"] >= 1
    assert mix["sharded_dispatches"] > 0
    kernels = out["kernel_phase"]["kernels"]
    assert {"place_sequence", "place_sequence_batch",
            "scatter_rows"} <= set(kernels)
    assert all(k["chosen_equal"] for k in kernels.values()
               if "chosen_equal" in k)
    multi = out["multichip"]
    assert all(v["equal"] for v in multi["sharded_vs_unsharded"].values())
    assert {k.split("@")[0] for k in multi["sharded_twins"]} == \
        {"capres", "feas", "usage"}


def test_a_deployments_fleet_and_jobs_take_the_forced_device_phase(runs):
    """``--config`` / ``--traffic``: that machine shape and those batch
    jobs (3, 4, 3, 4, 3 groups x 10 copies), the sequential ``batch``
    scheduler as the reference, every placement dispatch on the device
    plane, and no other phase."""
    rc, stdout, stderr = runs["deployment"]
    assert rc == 0, (stdout[-2000:], stderr[-4000:])
    out = json.loads(stdout.strip().splitlines()[0])
    assert out["ok"] is False and len(out["deployment"]) == 2
    assert out["reference"] == dict(
        out["reference"], scheduler="batch (sequential)", jobs=5,
        placements=170)
    assert "phase_a" not in out and "kernel_phase" not in out
    phase = out["phase_b"]
    assert phase["nodes"] == 64 and phase["placements_committed"] == 170
    assert phase["read_back_over_http"]["jobs_in_full"] == 5
    assert phase["dispatch_mix"]["host_dispatches"] == 0
    assert phase["dispatch_mix"]["device_dispatches"] >= 1


def test_without_a_chip_the_command_fails_and_prints_no_result(runs):
    rc, stdout, stderr = runs["no_chip"]
    assert rc != 0
    assert stdout.strip() == ""
    assert "no TPU" in stderr
    # A policy lever in the environment is refused up front: the smoke
    # runs the default policy, then executor=device from config.
    rc, stdout, stderr = runs["lever_set"]
    assert rc != 0 and stdout.strip() == ""
    assert "NOMAD_TPU_EXECUTOR" in stderr
