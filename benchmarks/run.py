#!/usr/bin/env python3
"""benchmarks/run.py — one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>  [--rehearse]  [--control bf16|worst_first]

One process.  It finds the cell's configuration and traffic files by the
names in ``BENCHMARK.json``, boots a server-only agent with a raft log on
disk, registers the configuration's fleet over ``Node.Register`` and
keeps it heartbeating, warms up, runs the traffic file's generator for
``--seconds``, then reads the committed allocations back and holds them
to the plain reference (``reference.py``).  Progress goes to stderr; the
last line of stdout is the result.  ``--rehearse`` shrinks fleet and jobs
for a CPU and always reports ``"correct": false``.  Nothing in here
branches on a cell's or a configuration's name.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# --rehearse: the command at a size a CPU holds.  Never a measurement.
# 1,024 nodes: at 512 a 2 s rehearsal of four stack clients filled the
# fleet (16 placements a job, 7 copies a node) on a CPU.  ``tiered``:
# the copies a job of tiers asks in all, at most (a tier keeps at least
# one): at ten copies of ten tiers, a 2 s rehearsal filled the 65 rows
# of the one class that a tier of ``batch10x64`` admits at 1,024 nodes.
REHEARSAL = {"nodes": 1024, "groups": 24, "count": 24, "clients": 4,
             "tiered": 32}


def say(msg: str) -> None:
    print(f"# [{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def percentile(sorted_values: list, q: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    rank = max(1, math.ceil(q * len(sorted_values)))
    return sorted_values[min(rank, len(sorted_values)) - 1]


def rehearsal_traffic(traffic: dict) -> dict:
    out = json.loads(json.dumps(traffic))
    job = out["job"]
    job["groups"] = min(job["groups"], REHEARSAL["groups"])
    job["count"] = min(job["count"], REHEARSAL["count"])
    if "tiers" in job:
        tiers = min(len(job["tiers"]), job["groups"])
        job["count"] = min(job["count"],
                           max(1, REHEARSAL["tiered"] // tiers))
    out["clients"] = min(out["clients"], REHEARSAL["clients"])
    return out


class CompileCounter:
    """Counts what JAX compiles (backend compiles and persistent-cache
    hits) between two marks."""

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event.endswith("cache_hits"):
            self.cache_hits += 1

    def mark(self) -> tuple:
        return (self.compiles, self.cache_hits)


class DeviceTrace:
    """A ``jax.profiler`` trace of a slice of the window, taken on a
    thread of its own; host-side tracing as low as it goes."""

    def __init__(self, out_dir: str, delay_s: float,
                 slice_s: float) -> None:
        self.out_dir, self.delay_s, self.slice_s = out_dir, delay_s, slice_s
        self.slice_perf = None
        self.error = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-trace",
                                        daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            if self._stop.wait(self.delay_s):
                return
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 0
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            t0 = time.perf_counter()
            self._stop.wait(self.slice_s)
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.slice_perf = (t0, t1)
        except Exception as e:  # reported by the caller
            self.error = e

    def finish(self) -> None:
        self._stop.set()
        self._thread.join(300.0)
        if self.error is not None:
            raise self.error
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop")


def find_cell(workload: str):
    """(``BENCHMARK.json``, the cell, its configuration, its traffic
    file) by the names in ``BENCHMARK.json``; None for an unknown cell.
    The tests point it at fixtures that no cell lists."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        print(f"unknown workload {workload!r}; BENCHMARK.json has "
              f"{sorted(cells)}", file=sys.stderr)
        return None
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     f"{cell['traffic']}.json"))
    return bench, cell, config, traffic


def main(argv=None, rehearsal_is_never_correct: bool = True) -> int:
    """One run.  The tests of the comparison (``tests/``) drive a
    rehearsal with ``rehearsal_is_never_correct`` off, to see the verdict
    the comparison itself gives; the command never does."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on JAX_PLATFORMS=cpu; never correct")
    ap.add_argument("--control", choices=("bf16", "worst_first"),
                    help="hold the scores of the reference in bfloat16, or "
                    "of the reference taking the worst nodes, not the "
                    "program's, to the limits: must come out not correct")
    args = ap.parse_args(argv)
    t_process = T_PROCESS if argv is None else time.perf_counter()

    found = find_cell(args.workload)
    if found is None:
        return 2
    bench, cell, config, traffic = found
    n_nodes = int(config["nodes"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        traffic = rehearsal_traffic(traffic)
        n_nodes = min(n_nodes, REHEARSAL["nodes"])

    for path in (HERE, ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    import served as served_mod

    levers = [v for v in served_mod.FORBIDDEN_ENV if os.environ.get(v)]
    if levers:
        print(f"unset {levers}: every cell runs the default executor "
              "policy", file=sys.stderr)
        return 2

    native = served_mod.ensure_native()
    from nomad_tpu.parallel.devices import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    # Programs that compile in under a second are cached too (PR 21 saw
    # them recompiled in every process).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse:
        if device["platform"] != "tpu" or len(devices) < int(cell["chips"]):
            print(f"no accelerator for this cell: JAX sees {device}, the "
                  f"cell asks for {cell['chips']} TPU chip(s)",
                  file=sys.stderr)
            return 3
    say(f"device {device}; native {native}; compile cache {cache_dir}")
    compiles = CompileCounter()

    import check
    import reference
    from nomad_tpu.obs import trace as obs_trace

    generator = load_module("generators", traffic["generator"])
    fleet = reference.make_fleet(config, args.seed, n_nodes)
    resident = reference.resident_layout(config, fleet, args.seed)
    work_dir = tempfile.mkdtemp(prefix="nomad-bench-")
    served = None
    trace = None
    marks = {}
    try:
        try:
            served = served_mod.Served(config, fleet, args.seed,
                                       os.path.join(work_dir, "raft"), say,
                                       resident)
            for plug in traffic.get("prewarm", ()):
                t0 = time.perf_counter()
                load_module("prewarm", plug["module"]).prewarm(
                    plug, n_nodes, traffic)
                say(f"prewarm {plug['module']}: "
                    f"{time.perf_counter() - t0:.2f}s")

            def on_open() -> None:
                nonlocal trace
                marks["counters_open"] = served.counters()
                marks["compiles_open"] = compiles.mark()
                marks["cpu_open"] = time.process_time()
                marks["setup_s"] = time.perf_counter() - t_process
                if args.trace:
                    tracer = obs_trace.enable(
                        seed=args.seed & 0xFFFFFFFF,
                        ring=int(traffic["trace"]["span_ring"]))
                    marks["span_clock_offset"] = \
                        time.perf_counter() - tracer.now()
                    trace = DeviceTrace(
                        os.path.join(work_dir, "profile"),
                        float(traffic["trace"]["delay_s"]),
                        min(float(traffic["trace"]["slice_s"]),
                            max(0.5, args.seconds - 1.0)))
                    trace.start()

            def on_close() -> None:
                marks["counters_close"] = served.counters()
                marks["compiles_close"] = compiles.mark()
                marks["cpu_close"] = time.process_time()
                if args.trace:
                    tracer = obs_trace.tracer()
                    marks["spans"] = tracer.snapshot()
                    marks["span_stats"] = tracer.stats()
                    obs_trace.disable()

            run = generator.run(traffic, args.seed,
                                lambda: served.client(
                                    float(traffic["wait"]["wait_time_s"])),
                                args.seconds, on_open, on_close, say)
            if trace is not None:
                trace.finish()
            window_s = run["t_close"] - run["t_open"]
            device["memory_peak_bytes"] = max(
                ((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
                for d in devices)

            # Answers: the whole store, and a sample of allocations over
            # HTTP held against it.
            t0 = time.perf_counter()
            records = run["records"]
            answers = served.read_back({r["spec"]["id"] for r in records})
            allocs = answers["allocs"]
            rng = random.Random(f"{args.seed}:readback")
            n_http = min(int(traffic["check"]["http_allocs"]),
                         len(allocs["id"]))
            answers["readback_mismatch"] = sum(
                1 for i in rng.sample(range(len(allocs["id"])), n_http)
                if served.http_alloc(allocs["id"][i]) != (
                    allocs["job"][i], int(allocs["node"][i]),
                    [float(x) for x in allocs["vec"][i]], "run"))
            counters_end = served.counters()
            say(f"read back {len(allocs['id'])} allocations ({n_http} over "
                f"HTTP) in {time.perf_counter() - t0:.1f}s")
            say("bytes on disk at the end (raft log, profile): " + str(sum(
                os.path.getsize(os.path.join(d, f))
                for d, _dirs, files in os.walk(work_dir) for f in files)))
        finally:
            if obs_trace.ENABLED:
                obs_trace.disable()
            if served is not None:
                served.shutdown()

        for r in records:
            r["in_window"] = run["t_open"] <= r["t_done"] <= run["t_close"]
        checks, info = check.compare(args.seed, traffic, fleet, records,
                                     answers, counters_end, args.control,
                                     say, resident)
        done = [r for r in records
                if r["in_window"] and r["status"] == "complete"]
        lat = sorted(1e3 * (r["t_done"] - r["t_submit"]) for r in done)
        placed = sum(r["spec"]["asked"] for r in done)
        c_open, c_close = marks["counters_open"], marks["counters_close"]
        c_open["bench.jobs_completed"] = 0
        c_close["bench.jobs_completed"] = len(done)
        values = {"placements_per_s": placed / window_s,
                  "setup_s": marks["setup_s"]}
        if lat:
            values["job_commit_p50_ms"] = percentile(lat, 0.50)
            values["job_commit_p95_ms"] = percentile(lat, 0.95)
        new_compiles = [b - a for a, b in zip(marks["compiles_open"],
                                              marks["compiles_close"])]
        say(f"window {window_s:.3f}s: {len(done)} jobs, {placed} placements; "
            f"slowest job {lat[-1] if lat else float('nan'):.1f} ms; "
            f"{sum(1 for r in records if not r['in_window'])} jobs outside "
            "the window (warm-up, in flight at its close)")
        fifths = [0] * 5
        for r in done:
            fifths[min(4, int(5 * (r["t_done"] - run["t_open"])
                              / window_s))] += 1
        say(f"jobs completed in each fifth of the window: {fifths}")
        say(f"compilations inside the window: {new_compiles[0]} programs "
            f"built or loaded, {new_compiles[1]} of them from the cache")
        say("counters over the window: " + json.dumps(
            {k.split(".")[-1]: c_close[k] - c_open[k] for k in c_open}))
        say("this process's CPU seconds over the window: "
            f"{marks['cpu_close'] - marks['cpu_open']:.2f} "
            f"({os.cpu_count()} cores)")
        say(f"peak bytes in use {device['memory_peak_bytes']}")

        def listed(metric: dict) -> bool:
            return "workloads" not in metric or \
                args.workload in metric["workloads"]

        metrics = {}
        breakdown = None
        if not args.trace:
            for m in bench["end_to_end"]:
                if listed(m) and m["name"] in values:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        else:
            say(f"spans: {marks['span_stats']}")
            if marks["span_stats"]["dropped"]:
                raise RuntimeError("the span ring dropped "
                                   f"{marks['span_stats']['dropped']} spans")
            reduced = None
            if trace.slice_perf is not None:
                import xplane

                path = xplane.find_xplane(trace.out_dir)
                t0 = time.perf_counter()
                reduced = xplane.reduce(
                    xplane.load(path),
                    trace.slice_perf[1] - trace.slice_perf[0])
                reduced["slice_perf"] = trace.slice_perf
                say(f"profile {os.path.getsize(path)} bytes, reduced in "
                    f"{time.perf_counter() - t0:.1f}s")
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                breakdown = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
            ctx = {
                "spans": marks["spans"], "counters_open": c_open,
                "counters_close": c_close, "trace": reduced,
                "span_clock_offset": marks["span_clock_offset"],
                "traffic": traffic, "n_nodes": n_nodes,
                "notes": [],
            }
            for m in bench["per_layer"]:
                if not listed(m):
                    continue
                spec = load_json(os.path.join(HERE, "layer_metrics",
                                              f"{m['name']}.json"))
                value = load_module("reducers", spec["reducer"]).reduce(
                    spec.get("params", {}), ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            for note in ctx["notes"]:
                say(note)
            if "busy_s" not in device:
                device["busy_s"], device["window_s"] = 0.0, window_s

        correct = check.passed(checks) and not (args.rehearse
                                                and rehearsal_is_never_correct)
        result = {"correct": correct,
                  "attempted": sum(1 for r in records if r["in_window"]),
                  "failed": checks["failed_jobs"]["value"],
                  "metrics": metrics, "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["checks"] = dict(checks, **{
            k: {"value": v, "limit": None} for k, v in info.items()})
        for name, c in result["checks"].items():
            print(f"check {name}: {c['value']} (limit {c['limit']})",
                  file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
        return 0
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
