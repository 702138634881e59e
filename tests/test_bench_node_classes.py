"""The benchmark's node classes, constraint stanzas and ``distinct_hosts``,
checked in this suite: every case of
``benchmarks/tests/test_node_classes.py`` (which this suite does not
collect by itself), so that a change to the reference, the harness's
fleet builder or the program's constraint compiler that gives the
one-class cells another fleet, or judges a stanza otherwise than the
program does, fails here.

That file imports its directory's ``conftest`` by name; this suite's own
``conftest`` holds that name while the suite runs, so the benchmark's is
loaded under another and lent the name for the one import.
"""
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
HERE = os.path.join(BENCH, "tests")


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _node_classes():
    # Last on the path: first, ``benchmarks/`` would lend its ``tests``
    # directory to this suite's ``tests.conftest`` imports.  ``faults``
    # is imported by one case.
    for path in (BENCH, ROOT, HERE):
        if path not in sys.path:
            sys.path.append(path)
    ours = sys.modules.get("conftest")
    sys.modules["conftest"] = _load("bench_tests_conftest",
                                    os.path.join(HERE, "conftest.py"))
    try:
        return _load("bench_test_node_classes",
                     os.path.join(HERE, "test_node_classes.py"))
    finally:
        if ours is None:
            del sys.modules["conftest"]
        else:
            sys.modules["conftest"] = ours


_cases = _node_classes()
globals().update({name: getattr(_cases, name) for name in dir(_cases)
                  if name.startswith("test_")
                  or name in ("small_fleet", "device_windows")})


def _longer(main, seconds: str):
    """``run.main`` with a window of ``seconds`` where the case asks
    two."""
    def longer(argv, **kw):
        argv = list(argv)
        argv[argv.index("--seconds") + 1] = seconds
        return main(argv, **kw)
    return longer


def test_the_fixture_on_the_kernel(device_windows, fault, caught_by,
                                   monkeypatch):
    """The benchmark file's case over a longer window.  A planted
    ``distinct_ignored`` lands a copy beside its job's own only once the
    fleet has filled unevenly (the anti-affinity penalty outweighs the
    gap between near-empty nodes), so it shows after enough jobs, not
    after two seconds: under this suite's runtime sanitizers and six
    workers a two-second rehearsal completes ~35 jobs and reads 0 (the
    same run alone completes ~80 and reads 36)."""
    monkeypatch.setattr(_cases.bench_run, "main",
                        _longer(_cases.bench_run.main,
                                "20" if fault == "distinct_ignored" else "5"))
    _cases.test_the_fixture_on_the_kernel(device_windows, fault, caught_by)


test_the_fixture_on_the_kernel.pytestmark = \
    _cases.test_the_fixture_on_the_kernel.pytestmark


def test_every_case_is_here():
    assert _cases.CONFIGS == ["alibaba2018-4k", "baseline4-10k", "c1m-5k",
                              "fleet100k", "fleet131k"]
    tests = [name for name in dir(_cases) if name.startswith("test_")]
    assert all(name in globals() for name in tests) and len(tests) == 13
