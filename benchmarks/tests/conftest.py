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
