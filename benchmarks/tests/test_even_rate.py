"""The even-rate generator and its traffic file: the job shapes are the
means the file derives from the trace's row counts, job k is a function
of (seed, k), the rehearsal's caps hold, and the schedule is kept with
clients that answer at once."""
import json
import os

import pytest

import run as bench_run
from conftest import BENCH

TRAFFIC = json.load(open(os.path.join(BENCH, "traffic", "dagbatch.json")))
even_rate = bench_run.load_module("generators", TRAFFIC["generator"])


def test_the_job_shapes_are_the_means_of_the_row_counts():
    """4.2 M jobs, 14.3 M tasks, 1.35 G instances in 8 days (the
    configuration file's ``stated_by_source.rows``)."""
    job, seconds = TRAFFIC["job"], 8 * 86400
    cycle = job["groups_cycle"]
    assert sum(cycle) / len(cycle) == pytest.approx(14.3 / 4.2, abs=0.01)
    assert job["count"] == round(1350 / 14.3)
    assert max(cycle) == job["groups"]
    rate = TRAFFIC["arrivals"]["jobs_per_s"]
    assert rate == pytest.approx(4.2e6 / seconds, abs=0.005)
    placements = rate * job["count"] * sum(cycle) / len(cycle)
    assert placements == pytest.approx(1.35e9 / seconds, rel=0.01)
    # One core, and one core's share of a machine's memory: 96 fit a
    # machine of the configuration in both dimensions, 97 in neither.
    node = json.load(open(os.path.join(
        BENCH, "configs", "alibaba2018-4k.json")))["node"]
    assert node["cpu"] // job["ask"]["cpu"] == 96
    assert node["memory_mb"] // job["ask"]["memory_mb"] == 96


def test_job_is_a_function_of_seed_and_k():
    job = TRAFFIC["job"]
    a = [even_rate.job_spec(job, 7, k) for k in range(10)]
    assert a == [even_rate.job_spec(job, 7, k) for k in range(10)]
    assert [len(s["groups"]) for s in a] == 2 * job["groups_cycle"]
    b = [even_rate.job_spec(job, 2**31 + 5, k) for k in range(10)]
    assert not {s["id"] for s in a} & {s["id"] for s in b}
    assert [s["asked"] for s in a] == [s["asked"] for s in b]
    spec = a[2]
    assert spec["type"] == "batch" and spec["asked"] == 4 * 94
    assert [g["name"] for g in spec["groups"]] == ["t00", "t01", "t02",
                                                  "t03"]
    assert all(g == {"name": g["name"], "count": 94, "cpu": 100,
                     "memory_mb": 1041} for g in spec["groups"])


def test_rehearsal_caps_hold():
    small = bench_run.rehearsal_traffic(TRAFFIC)
    assert small["job"]["count"] == bench_run.REHEARSAL["count"] < 94
    assert small["clients"] == bench_run.REHEARSAL["clients"]
    specs = [even_rate.job_spec(small["job"], 1, k) for k in range(5)]
    assert [s["asked"] for s in specs] == [72, 72, 96, 72, 96]
    capped = dict(small["job"], groups=3)
    assert {len(even_rate.job_spec(capped, 1, k)["groups"])
            for k in range(5)} == {3}


def test_the_schedule_is_kept():
    """Clients that answer at once: the schedule alone.  The window
    opens ``open_before_job_s`` before the first job after the warm-up
    is due, jobs are due one period apart, and a job's clock starts
    when it was due."""
    traffic = json.loads(json.dumps(TRAFFIC))
    traffic["arrivals"]["jobs_per_s"] = 50.0
    traffic["warmup"] = {"jobs": 5, "open_before_job_s": 0.01}
    said, edges = [], []
    out = even_rate.run(
        traffic, 11, lambda: (lambda spec: spec["id"],
                              lambda eval_id, deadline: "complete"),
        0.3, lambda: edges.append("open"), lambda: edges.append("close"),
        said.append)
    assert edges == ["open", "close"]
    records = sorted(out["records"], key=lambda r: r["k"])
    assert [r["k"] for r in records] == list(range(len(records)))
    inside = [r for r in records
              if out["t_open"] <= r["t_submit"] <= out["t_close"]]
    assert inside[0]["k"] == 5 and 13 <= len(inside) <= 16
    due = [r["t_submit"] for r in records]
    assert [b - a for a, b in zip(due, due[1:])] == pytest.approx(
        [0.02] * (len(due) - 1))
    assert all(r["t_start"] >= r["t_submit"] and r["status"] == "complete"
               for r in records)
    assert any(line.startswith(
        f"lateness of starts over {len(records)} jobs") for line in said)
