"""The rehearsal check: every cell of BENCHMARK.json runs end to end on
the CPU at a tiny size, its last line is well-formed, carries the cell's
metrics and no other, and says ``"correct": false``."""
import json
import os
import re
import subprocess
import sys

import pytest

from conftest import ROOT

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def listed(section, cell):
    return {m["name"]: m for m in BENCH[section]
            if "workloads" not in m or cell in m["workloads"]}


def rehearse(cell, trace, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_last_line(cell, trace):
    result, stderr = rehearse(cell, trace, seed=2**31 + 24 + trace)
    extra = {"checks"} | ({"breakdown"} if trace else set())
    assert KEYS <= set(result) <= KEYS | extra
    assert list(result)[-1] == "checks"
    assert result["correct"] is False
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    want = listed("per_layer" if trace else "end_to_end", cell)
    got = result["metrics"]
    assert set(got) <= set(want)
    # On a CPU there is no device plane to read: a reader that finds
    # nothing returns nothing.  Every other metric must be there.
    missing = set(want) - set(got)
    assert all(want[m]["source"] == "device_trace" for m in missing)
    for name, m in got.items():
        assert NAME.match(name) and UNIT.match(m["unit"])
        assert m["unit"] == want[name]["unit"]
        assert isinstance(m["value"], float)
    if trace:
        assert {"busy_s", "window_s"} <= set(result["device"])
    # Every number compared is inside its limit at the rehearsal's size,
    # and printed beside it as the last lines of stderr.
    for name, c in result["checks"].items():
        if c["limit"] is not None:
            assert c["value"] <= c["limit"], (name, c)
            assert f"check {name}: {c['value']} (limit {c['limit']})" \
                in stderr


def test_benchmark_json_keeps_inside_the_contract_limits():
    """What is refused before a single run: a name, a unit or a line of
    prose outside its alphabet or length, a file that is not there."""
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[section]:
            assert NAME.match(entry["name"]), entry["name"]
            for key in ("why", "source", "layer"):
                text = entry.get(key, "x")
                assert 1 <= len(text) <= 200, (entry["name"], key)
                assert "\n" not in text and "\t" not in text
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
    for config in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, config["file"]))
        assert all(NAME.match(key) for key in config["reduced"])
    for cell in BENCH["workloads"]:
        assert NAME.match(cell["traffic"])
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "traffic", f"{cell['traffic']}.json"))
    for metric in BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", f"{metric['name']}.json"))


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_policy_lever_in_the_environment_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu", NOMAD_TPU_EXECUTOR="device")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
