"""The comparison that decides ``correct``, shown to fail, in every cell.

Each test drives the whole of a run but the look for a chip (a rehearsal
whose verdict is the comparison's own) and reads ``correct`` from its
result line.  A sound run comes out correct.  The control — the plain
reference's scorer put in the program's place in bfloat16, the nearest
precision below the float32 the configurations state — comes out not
correct, by ``score_gap``; the reference taking the worst nodes at
their right scores, by ``score_regret`` alone.  So does the run under
each fault of
``faults.py``, by the number named here.  At the cells' own sizes on the
chip: PERF.md section 2.  Beside the cells, the fixture of ten
constrained task groups on three node classes (``conftest.FIXTURE_CELL``,
which no ``BENCHMARK.json`` lists): the two faults that only a job with
a constraint or ``distinct_hosts`` can show run there alone.  And the
same traffic on a fleet that already runs two resident jobs
(``conftest.RESIDENT_CELL``): the two faults that only resident work
can show run there alone.
"""
import io
import json
import os
from contextlib import nullcontext, redirect_stdout

import pytest

import run as bench_run
from conftest import FIXTURE_CELL, RESIDENT_CELL, ROOT, fixture_cell
from faults import FAULTS

CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]] + [
        FIXTURE_CELL, RESIDENT_CELL]


@pytest.fixture(autouse=True)
def fixture_lookup(monkeypatch):
    listed = bench_run.find_cell
    monkeypatch.setattr(bench_run, "find_cell",
                        lambda name: fixture_cell(name) or listed(name))


def drive(cell, seed, *flags, fault=None, seconds=2):
    out = io.StringIO()
    with redirect_stdout(out), (FAULTS[fault]() if fault else nullcontext()):
        rc = bench_run.main(
            ["--workload", cell, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", "0", "--rehearse", *flags],
            rehearsal_is_never_correct=False)
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    outside = {k for k, c in result["checks"].items()
               if c["limit"] is not None and c["value"] > c["limit"]}
    assert result["correct"] is (not outside)
    return result, outside


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_the_control_is_not(cell):
    sound, outside = drive(cell, 31)
    assert sound["correct"] is True and not outside
    limit = sound["checks"]["score_gap"]["limit"]
    assert sound["checks"]["score_gap"]["value"] < limit / 10
    control, outside = drive(cell, 31, "--control", "bf16")
    assert control["correct"] is False and "score_gap" in outside
    assert control["checks"]["score_gap"]["value"] > 3 * limit
    # Everything but the scores is the program's own, and sound.
    assert outside <= {"score_gap", "score_regret"}
    assert control["checks"]["program_score_gap"]["value"] < limit / 10


@pytest.mark.parametrize("cell", CELLS)
def test_right_scores_for_the_worst_nodes_fail_the_regret_alone(cell):
    result, outside = drive(cell, 31, "--control", "worst_first")
    assert result["correct"] is False and outside == {"score_regret"}


@pytest.mark.parametrize("fault, caught_by", [
    ("state_left_unchanged", {"placement_mismatch"}),
    ("half_of_every_plan", {"placement_mismatch"}),
    # A moved pick that the applier accepts is caught by its score, one
    # that it refuses (a full node) by the job that then fails.
    ("answer_altered", {"score_gap", "failed_jobs"}),
])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, caught_by):
    result, outside = drive(cell, 32, fault=fault)
    assert result["correct"] is False
    assert caught_by & outside


@pytest.mark.parametrize("fault, caught_by, seconds", [
    ("constraints_ignored", "constraint_violations", 2),
    # A copy lands beside its job's own only once the fleet has filled
    # unevenly: after ~80 jobs, which a loaded CPU takes more than 2 s
    # to complete (``tests/test_bench_node_classes.py``).
    ("distinct_ignored", "distinct_violations", 10),
])
def test_constraint_fault_is_not_correct(fault, caught_by, seconds):
    result, outside = drive(FIXTURE_CELL, 33, fault=fault, seconds=seconds)
    assert result["correct"] is False
    assert caught_by in outside


@pytest.mark.parametrize("fault, caught_by", [
    ("resident_dropped", "resident_lost"),
    ("resident_usage_ignored", "oversubscribed_nodes"),
])
def test_resident_fault_is_not_correct(fault, caught_by):
    result, outside = drive(RESIDENT_CELL, 34, fault=fault)
    assert result["correct"] is False
    assert caught_by in outside
