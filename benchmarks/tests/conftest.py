"""Tests of the benchmark's own files.  Run them from the root of the
repo on the CPU: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``
(the repo's ``tests/`` suite does not collect this directory)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

FIXTURES = os.path.join(HERE, "fixtures")
# Cells no BENCHMARK.json lists: ten constrained task groups on three
# node classes, and the same on a fleet that already runs two resident
# jobs (``fixtures/``).  ``fixture_cell`` is what ``run.find_cell``
# returns for them; the tests that drive them patch that lookup.
FIXTURE_CELL = "fixture.classes3.constrained10"
RESIDENT_CELL = "fixture.resident3.constrained10"


def fixture_cell(workload: str):
    import json

    if workload not in (FIXTURE_CELL, RESIDENT_CELL):
        return None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    _kind, config, traffic = workload.split(".")
    cell = {"name": workload, "config": config, "traffic": traffic,
            "chips": 1}
    files = []
    for name in (config, traffic):
        with open(os.path.join(FIXTURES, f"{name}.json")) as fh:
            files.append(json.load(fh))
    return (dict(bench, workloads=[cell]), cell, *files)
