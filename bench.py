"""North-star benchmark: device bin-packing vs in-process sequential packer.

Measures all five BASELINE.md configs, with p99 per-eval plan latency:

  1. service job, 1 task-group, 100 mock nodes
  2. batch job, 10 task-groups w/ constraints + distinct_hosts, 1k nodes
  3. system job, 1k nodes (host-path scheduler; parity measurement)
  4. 10k nodes x 1k task-groups bin-pack stress — single-eval latency AND
     pipelined-stream throughput (scheduler/pipeline.py hides the
     per-dispatch device round trip behind host work)
  5. optimistic eval storm: 64 concurrent evals x 1k TGs fused into one
     device dispatch by BatchEvalRunner (the headline)

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "configs": {...all five, with p99_ms...}}

Run on TPU (default backend); ``--quick`` shrinks for smoke runs.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# Multi-device host platform for the sharded rows, decided BEFORE jax
# initializes (imports below pull it in): force 8 virtual devices on
# the host CPU platform unless the caller already pinned a count.
# This only affects the *host* platform — a real TPU backend keeps its
# own device set and the mesh resolves over the TPU devices instead
# (parallel/devices.default_platform_devices).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import nomad_tpu.mock as mock  # noqa: E402
from nomad_tpu.scheduler import Harness  # noqa: E402
from nomad_tpu.structs import (  # noqa: E402
    CONSTRAINT_DISTINCT_HOSTS,
    EVAL_TRIGGER_JOB_REGISTER,
    JOB_TYPE_SERVICE,
    Constraint,
    Evaluation,
    NetworkResource,
    Resources,
    Task,
    TaskGroup,
    generate_uuid,
)


def _bench_task_group(name: str) -> TaskGroup:
    """The one benchmark workload shape, shared by configs 4 and 5."""
    return TaskGroup(
        name=name,
        count=1,
        tasks=[Task(
            name="web",
            driver="exec",
            resources=Resources(
                cpu=100, memory_mb=64,
                networks=[NetworkResource(mbits=5,
                                          dynamic_ports=["http"])],
            ),
        )],
    )


def _bench_job(n_groups: int):
    job = mock.job()
    job.task_groups = [_bench_task_group(f"tg-{g}") for g in range(n_groups)]
    return job


def make_eval(job) -> Evaluation:
    return Evaluation(
        id=generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
    )


class _RecordOnlyPlanner:
    """Accepts every plan as fully committed WITHOUT applying it to state,
    so repeated evals all see the identical snapshot."""

    def __init__(self) -> None:
        self.plans = []

    def submit_plan(self, plan):
        from nomad_tpu.structs import PlanResult
        self.plans.append(plan)
        return PlanResult(
            node_update=plan.node_update,
            node_allocation=plan.node_allocation,
            failed_allocs=plan.failed_allocs,
        ), None

    def update_eval(self, ev) -> None:
        pass

    def create_eval(self, ev) -> None:
        pass


def _harness_with_nodes(n_nodes: int) -> Harness:
    h = Harness()
    for i in range(n_nodes):
        h.state.upsert_node(h.next_index(), mock.node(i))
    return h


def _p(values, q) -> float:
    """Percentile (nearest-rank) of a list of seconds, in ms."""
    if not values:
        return 0.0
    vs = sorted(values)
    k = min(len(vs) - 1, max(0, int(round(q / 100.0 * len(vs) + 0.5)) - 1))
    return vs[k] * 1000.0


def _placed(planner) -> int:
    return sum(sum(len(v) for v in p.node_allocation.values())
               for p in planner.plans)


def bench_sequential_stream(h, jobs, scheduler: str, repeats: int = 3):
    """One-at-a-time reference-faithful processing; returns BEST-OF-N
    (total_s, per_eval_latencies, placed) — same selection as the
    pipelined side, so the reported speedups compare min against min."""
    best, best_lats, placed = float("inf"), [], 0
    for _ in range(repeats):
        total, lats, got = _sequential_rep(h, jobs, scheduler)
        if total < best:
            best, best_lats, placed = total, lats, got
    return best, best_lats, placed


def _sequential_rep(h, jobs, scheduler: str):
    recorder = _RecordOnlyPlanner()
    h.planner = recorder
    lats = []
    start = time.perf_counter()
    for job in jobs:
        t0 = time.perf_counter()
        h.process(scheduler, make_eval(job))
        lats.append(time.perf_counter() - t0)
    return time.perf_counter() - start, lats, _placed(recorder)


def bench_interleaved_stream(h, jobs, scheduler: str, depth: int,
                             repeats: int = 3):
    """Symmetric best-of-N for BOTH sides with device/sequential reps
    INTERLEAVED, so shared-host load drift between the two measurement
    phases cannot skew the ratio: each side's best is drawn from the
    same alternating load windows.  Returns
    (dev_s, dev_lats, dev_placed, seq_s, seq_lats, seq_placed)."""
    dev_best, dev_lats, dev_placed = float("inf"), [], 0
    seq_best, seq_lats, seq_placed = float("inf"), [], 0
    for _ in range(repeats):
        total, lats, got = _pipelined_rep(h, jobs, depth)
        if total < dev_best:
            dev_best, dev_lats, dev_placed = total, lats, got
        total, lats, got = _sequential_rep(h, jobs, scheduler)
        if total < seq_best:
            seq_best, seq_lats, seq_placed = total, lats, got
    return dev_best, dev_lats, dev_placed, seq_best, seq_lats, seq_placed


def _pipelined_rep(h, jobs, depth: int):
    from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner

    recorder = _RecordOnlyPlanner()
    snapshot = h.state.snapshot()
    runner = PipelinedEvalRunner(snapshot, recorder, depth=depth)
    evals = [make_eval(j) for j in jobs]
    start = time.perf_counter()
    runner.process(evals)
    total = time.perf_counter() - start
    assert len(recorder.plans) == len(jobs)
    return total, runner.latencies, _placed(recorder)


def bench_pipelined_stream(h, jobs, depth: int = 6, repeats: int = 1):
    """Device path with the dispatch pipeline; returns best-of-N
    (total_s, per_eval_latencies, placed)."""
    best, best_lats, placed = float("inf"), [], 0
    for _ in range(repeats):
        total, lats, got = _pipelined_rep(h, jobs, depth)
        if total < best:
            best, best_lats, placed = total, lats, got
    return best, best_lats, placed


def bench_single_eval(h, job, scheduler: str, repeats: int):
    """Best-of-N single-eval latency; returns (seconds, placed).

    One untimed warm eval first — the same cache-warm discipline the
    stream rows apply (prep/jit caches are per job version x fleet
    generation; the steady-state latency is the one the bar tracks,
    not the one-off cold-cache build)."""
    recorder = _RecordOnlyPlanner()
    h.planner = recorder
    h.process(scheduler, make_eval(job))  # warm
    best = float("inf")
    placed = 0
    for _ in range(repeats):
        recorder.plans.clear()
        t0 = time.perf_counter()
        h.process(scheduler, make_eval(job))
        best = min(best, time.perf_counter() - t0)
        placed = _placed(recorder)
    return best, placed


def single_eval_stage_profile(h, job, repeats: int = 3) -> dict:
    """Per-stage wall (ms) of ONE config-4 eval through the staged
    runner's stage timers (scheduler/pipeline.py stage_times): begin =
    reconcile + dispatch prep, dispatch = executor kernel start (the
    whole numpy kernel when the host executor takes it), collect =
    result fetch + rounds->placement mapping, finish = native bulk
    finish + Python tail, submit = plan submit + status.  This is the
    recorded host-floor decomposition the `single_eval_ms` bar is
    baselined against — best-of-N by total."""
    from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner

    best_total, best_times = float("inf"), {}
    for _ in range(repeats):
        recorder = _RecordOnlyPlanner()
        runner = PipelinedEvalRunner(h.state.snapshot(), recorder,
                                     depth=1)
        runner.process([make_eval(job)])
        total = sum(runner.stage_times.values())
        if total < best_total:
            best_total, best_times = total, dict(runner.stage_times)
    return {k: round(v * 1000.0, 2) for k, v in best_times.items()}


def _row_metrics() -> dict:
    """Embedded per-row metrics snapshot (ISSUE 10 satellite): the
    process metrics registry (breaker, any live swarm) plus the in-mem
    telemetry sink at row-capture time.  Counters are process-
    cumulative; samples are interval-windowed (utils/metrics.py), so
    their percentiles reflect the recent window, not the whole run."""
    from nomad_tpu.obs import REGISTRY
    from nomad_tpu.utils.metrics import metrics

    return {"providers": REGISTRY.snapshot(),
            "inmem": metrics.inmem.snapshot()}


def _span_stage_profile(tracer) -> dict:
    """Config-4 stage rows re-derived from SPANS (ISSUE 10): mean span
    duration (ms) per scheduler stage across the traced stream.
    Window-shared stages (finish/submit on the drain) report the window
    wall each eval observed — the same semantics as the runner's
    stage_times, but read from the exported trace instead of bespoke
    bench timers."""
    sums: dict = {}
    counts: dict = {}
    for s in tracer.snapshot():
        name = s["name"]
        if name.startswith("sched."):
            sums[name] = sums.get(name, 0.0) + s["dur"]
            counts[name] = counts.get(name, 0) + 1
    return {name.split(".", 1)[1]:
            round(sums[name] / counts[name] * 1000.0, 3)
            for name in sums}


def bench_traced_stream(h, jobs, depth: int, repeats: int = 3):
    """The tracing A/B on the config-4 stream: spans-ON and spans-OFF
    reps INTERLEAVED (same discipline as bench_interleaved_stream —
    load drift must not skew the ratio) and MEDIAN-of-N per side
    (ISSUE 12 satellite).  r11 recorded a *negative* overhead
    (-3.58%): the difference of two best-of-N minima from noisy
    distributions routinely crosses zero, so the <=5% assertion
    constrained nothing.  The median pair is a stable centre — the
    recorded overhead is the honest tracer cost, not which side drew
    the luckier minimum.  Returns (off_median_s, on_median_s,
    span_profile, spans_total) with the profile taken from the rep
    closest to the on-side median."""
    import statistics

    from nomad_tpu.obs import trace as obs_trace

    # Each timed rep loops the stream until the window is long enough
    # (~0.6 s) that the 5% bar clears the scheduler-noise floor — a
    # single 16-job stream is tens of milliseconds, where even a
    # median A/B measures jitter, not the tracer.
    est, _, _ = _pipelined_rep(h, jobs, depth)  # warm + estimate
    loops = max(1, min(64, int(round(0.6 / max(est, 1e-3)))))

    def timed(n):
        total = 0.0
        for _ in range(n):
            t, _, _ = _pipelined_rep(h, jobs, depth)
            total += t
        return total

    offs: list = []
    ons: list = []
    profiles: dict = {}   # on-rep wall -> (span profile, span count)
    for _ in range(repeats):
        offs.append(timed(loops))
        with obs_trace.tracing(seed=1234, ring=1 << 18) as tracer:
            t_on = timed(loops)
            profiles[t_on] = (_span_stage_profile(tracer),
                              len(tracer.snapshot()) / loops)
        ons.append(t_on)
    off_med = statistics.median(offs) / loops
    on_med = statistics.median(ons) / loops
    span_profile, spans_total = profiles[
        min(ons, key=lambda t: abs(t - statistics.median(ons)))]
    return off_med, on_med, span_profile, spans_total


def bench_pipelined_device_stream(h, jobs, depth: int, repeats: int = 3):
    """The `4_device_pipelined` row: the SAME eval stream as the host
    row, executor forced to the device (NOMAD_TPU_EXECUTOR semantics
    via scheduler/executor.executor_override) through the staged
    pipeline — eval N's RTT hides behind evals N+1..N+depth's host
    stages.  The last rep runs under a HARD
    ``jax.transfer_guard("disallow")`` for host->device: zero IMPLICIT
    transfers on the hot path is asserted by that rep completing (the
    transfer-discipline contract — every upload goes through the
    explicit counted seams), and the explicit odometer
    (parallel/devices.transfer_counts) yields the recorded
    host_transfers_per_eval.  Returns (best_s, lats, placed,
    stage_times, device_dispatches, total_dispatches,
    transfers_per_eval)."""
    import jax as _jax

    from nomad_tpu.parallel.devices import transfer_counts
    from nomad_tpu.scheduler.executor import executor_override
    from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner

    best, best_lats, best_stages, placed = float("inf"), [], {}, 0
    dev_n = total_n = 0
    transfers_per_eval = 0.0
    with executor_override("device"):
        for rep in range(repeats):
            recorder = _RecordOnlyPlanner()
            snapshot = h.state.snapshot()
            runner = PipelinedEvalRunner(snapshot, recorder, depth=depth)
            evals = [make_eval(j) for j in jobs]
            guard = _jax.transfer_guard_host_to_device("disallow") \
                if rep == repeats - 1 else contextlib.nullcontext()
            t0 = transfer_counts()
            with guard:
                start = time.perf_counter()
                runner.process(evals)
                total = time.perf_counter() - start
            t1 = transfer_counts()
            assert len(recorder.plans) == len(jobs)
            if rep == repeats - 1:
                # Every transfer this rep performed was explicit (the
                # guard proved it) and counted — the honest per-eval
                # h2d cost of the device hot path.
                transfers_per_eval = (t1["h2d"] - t0["h2d"]) / \
                    max(1, len(jobs))
            if total < best:
                best, best_lats = total, runner.latencies
                best_stages = dict(runner.stage_times)
                placed = _placed(recorder)
                dev_n = runner.device_dispatches
                total_n = dev_n + runner.host_dispatches
    return (best, best_lats, placed, best_stages, dev_n, total_n,
            transfers_per_eval)


# Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" (819 GB/s HBM bandwidth,
# 16 GB HBM per chip).  ``hbm_gbps`` feeds the rough roofline line of
# the fused storm; ``hbm_bytes`` is the per-device budget of the
# sharded-fleet row, which asserts its UNSHARDED resident footprint
# exceeds it while the per-shard slice fits — the regime where node-axis
# sharding is the only way the workload fits.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "hbm_bytes": 16 * (1 << 30)},
}


def device_peaks() -> dict:
    """Peaks of the device the bench runs on.  A device that is not in
    the table is an error, not a default: a roofline share or an HBM
    budget against another chip's peaks (or a CPU's "achieved" number
    against a TPU's) is not a measurement."""
    from nomad_tpu.parallel.devices import default_platform_devices

    kind = default_platform_devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise RuntimeError(
            f"no published peaks for device kind {kind!r}: bench.py's "
            f"roofline and HBM-budget rows run only on "
            f"{sorted(DEVICE_PEAKS)}")
    return DEVICE_PEAKS[kind]


def _storm_footprint_bytes(lanes: int, g_pad: int, n_pad: int,
                           k_cap: int, rounds: int) -> int:
    """Resident-tensor model of one fused storm dispatch: the arrays
    XLA must hold in device memory simultaneously — per-lane [G, N]
    feasibility (the dominant term), the vmapped scan's per-lane usage
    carry, job counts, the masked-score working set (double-buffered),
    the chosen/score output streams, and the shared capacity/reserved
    tensors.  Deterministic arithmetic, not a measurement — the same
    class of model as _est_traffic_bytes, used for the fits/doesn't-fit
    budget assertions."""
    from nomad_tpu.models.fleet import NDIMS

    feasible = lanes * g_pad * n_pad                  # bool
    usage = lanes * n_pad * NDIMS * 4                 # f32 scan carry
    jc = lanes * n_pad * 4                            # i32
    masked = lanes * n_pad * 4 * 2                    # score + top-k buf
    streams = lanes * g_pad * rounds * k_cap * 8      # chosen + scores
    capres = 2 * n_pad * NDIMS * 4                    # shared statics
    return feasible + usage + jc + masked + streams + capres


def bench_sharded_stream(h, jobs, depth: int, repeats: int):
    """The `4s_sharded_stream` row: the SAME config-4 eval stream,
    device executor forced, node axis SHARDED over the auto-resolved
    mesh (the first-class path) vs the single-device twin
    (NOMAD_TPU_MESH=off), reps interleaved.  Returns (sharded_s,
    sharded_lats, placed_sharded, single_s, placed_single, mesh,
    sharded_dispatches, device_dispatches)."""
    from nomad_tpu.models.fleet import fleet_cache
    from nomad_tpu.parallel.mesh import dispatch_mesh, mesh_override
    from nomad_tpu.scheduler.executor import executor_override
    from nomad_tpu.scheduler.pipeline import PipelinedEvalRunner

    statics = fleet_cache.statics_for(h.state)
    # Resolve the RECORDED mesh under the same policy the timed reps
    # force: an ambient NOMAD_TPU_MESH must not make the row describe
    # a different mesh than the one it measured.
    with mesh_override("auto"):
        mesh = dispatch_mesh(1, statics.n_pad)

    def rep(policy):
        recorder = _RecordOnlyPlanner()
        runner = PipelinedEvalRunner(h.state.snapshot(), recorder,
                                     depth=depth)
        evals = [make_eval(j) for j in jobs]
        with mesh_override(policy), executor_override("device"):
            start = time.perf_counter()
            runner.process(evals)
            total = time.perf_counter() - start
        assert len(recorder.plans) == len(jobs)
        return total, runner, _placed(recorder)

    rep("auto")  # warm sharded compile caches
    rep("off")   # warm single-device twin
    sh_best, sg_best = float("inf"), float("inf")
    sh_lats: list = []
    sh_placed = sg_placed = sh_n = dev_n = 0
    for _ in range(repeats):
        total, runner, placed = rep("auto")
        assert runner.sharded_dispatches == runner.device_dispatches \
            == len(jobs), runner.stats()
        if total < sh_best:
            sh_best, sh_lats, sh_placed = total, runner.latencies, placed
            sh_n = runner.sharded_dispatches
            dev_n = runner.device_dispatches
        total, runner, placed = rep("off")
        assert runner.sharded_dispatches == 0, runner.stats()
        if total < sg_best:
            sg_best, sg_placed = total, placed
    return (sh_best, sh_lats, sh_placed, sg_best, sg_placed, mesh,
            sh_n, dev_n)


def _fleet_storm_job(groups: int):
    """One heterogeneous storm job: ``groups`` task groups with
    DISTINCT resource asks (a prime-strided cpu/mem lattice), so slot
    dedupe keeps every group — the [lanes, G, N] feasibility tensor is
    real, which is the point of the >=100k-node row."""
    job = mock.job()
    job.task_groups = [TaskGroup(
        name=f"tg-{g}",
        count=1,
        tasks=[Task(
            name="web", driver="exec",
            resources=Resources(cpu=20 + (g % 997),
                                memory_mb=32 + (g % 499)),
        )],
    ) for g in range(groups)]
    return job


def bench_sharded_fleet_storm(n_nodes: int, lanes: int, groups: int,
                              note) -> dict:
    """The `6_sharded_fleet_storm` row: a 2-D lanes x fleet storm at
    >=100k nodes where the node axis MUST shard to fit per-device
    memory.

    The fleet loads as a columnar NodeSlab (state/store.
    upsert_node_slab — no per-node object construction), the fleet
    bridge builds statics off the slab's dense columns with
    one-representative-row constraint masks, and the fused dispatch
    rides the (lanes, fleet) storm mesh with mesh-resident
    capacity/reserved/usage.  Asserted in-bench: the UNSHARDED
    resident footprint exceeds a single device's HBM budget while the
    per-shard slice fits AND the sharded run completes with every
    placement made."""
    import math

    from nomad_tpu.models.fleet import _pad_to, fleet_cache, mirror_for
    from nomad_tpu.parallel.mesh import (FLEET_AXIS, LANE_AXIS,
                                         dispatch_mesh)
    from nomad_tpu.scheduler.batch import BatchEvalRunner

    h = Harness()
    t0 = time.perf_counter()
    h.state.upsert_node_slab(h.next_index(), mock.node_slab(n_nodes))
    load_s = time.perf_counter() - t0
    jobs = []
    for _ in range(lanes):
        job = _fleet_storm_job(groups)
        h.state.upsert_job(h.next_index(), job)
        jobs.append(job)

    hbm_budget = device_peaks()["hbm_bytes"]
    n_pad = _pad_to(n_nodes)
    g_pad = _pad_to(groups)
    k_cap, rounds = 8, 1  # count-1 slots: one top-k round, k = pad(1)
    unsharded = _storm_footprint_bytes(lanes, g_pad, n_pad, k_cap,
                                       rounds)
    mesh = dispatch_mesh(lanes, n_pad)
    assert mesh is not None, \
        "the >=100k-node storm NEEDS a mesh (single device cannot hold it)"
    assert FLEET_AXIS in mesh.axis_names and LANE_AXIS in mesh.axis_names
    n_shards = math.prod(mesh.shape.values())
    per_shard = unsharded / n_shards
    # THE point of the row, asserted: single-chip infeasible, sharded
    # fits.  Both sides of the comparison are the same deterministic
    # resident-tensor model.
    assert unsharded > hbm_budget, (
        f"storm too small to need sharding: {unsharded / 1e9:.1f}GB "
        f"unsharded vs {hbm_budget / 1e9:.1f}GB budget")
    assert per_shard <= hbm_budget, (
        f"per-shard slice does not fit: {per_shard / 1e9:.1f}GB")

    # Statics + masks off the slab columns (timed: this is the
    # state->HBM bridge that used to be the 10k-node ceiling).
    t0 = time.perf_counter()
    statics = fleet_cache.statics_for(h.state)
    assert statics.uniform and statics.n_real == n_nodes
    bridge_s = time.perf_counter() - t0

    recorder = _RecordOnlyPlanner()
    evals = [make_eval(j) for j in jobs]
    t0 = time.perf_counter()
    BatchEvalRunner(h.state.snapshot(), recorder).process(evals)
    wall = time.perf_counter() - t0
    placed = _placed(recorder)
    # Completes, and completely: every lane placed its full storm.
    assert len(recorder.plans) == lanes, len(recorder.plans)
    assert placed == lanes * groups, (placed, lanes * groups)
    mirror = mirror_for(statics)
    row = {
        "nodes": n_nodes,
        "lanes": lanes,
        "groups_per_lane": groups,
        "placed": placed,
        "window_s": round(wall, 2),
        "evals_per_sec": round(lanes / wall, 3),
        "placements_per_sec": round(placed / wall, 1),
        "mesh_shape": {k: int(v) for k, v in mesh.shape.items()},
        "approx_hbm_gb_unsharded": round(unsharded / 1e9, 2),
        "approx_hbm_gb_per_shard": round(per_shard / 1e9, 2),
        "hbm_budget_gb": round(hbm_budget / 1e9, 2),
        "node_table_load_s": round(load_s, 2),
        "fleet_bridge_s": round(bridge_s, 2),
        "mirror_rebuilds": mirror.rebuilds if mirror is not None else 0,
        "note": (f"{lanes}-lane x {groups}-distinct-group storm on a "
                 f"{n_nodes}-node columnar fleet (NodeSlab bulk load, "
                 "one-representative-row constraint masks): the 2-D "
                 "(lanes, fleet) mesh shards evals across rows and the "
                 "node axis across columns; asserted in-bench that the "
                 "unsharded resident footprint exceeds one device's "
                 f"{hbm_budget / 1e9:.1f}GB budget while "
                 "the per-shard slice fits and the sharded run "
                 "completes with every placement made"),
    }
    note(f"config6 sharded fleet storm: {n_nodes} nodes x {lanes} lanes "
         f"x {groups} groups -> {placed} placed in {wall:.1f}s "
         f"({placed / wall:.0f} placements/s) on mesh "
         f"{dict(mesh.shape)}; footprint {unsharded / 1e9:.1f}GB "
         f"unsharded (> {hbm_budget / 1e9:.1f}GB budget) "
         f"vs {per_shard / 1e9:.2f}GB/shard; node table loaded in "
         f"{load_s:.2f}s, fleet bridge {bridge_s:.2f}s")
    return row


def _deferred_args(h, job):
    """One eval's deferred device args (the real scheduler prep)."""
    from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler

    sched = JaxBinPackScheduler(h.state.snapshot(), h, batch=False)
    sched.eval = make_eval(job)
    sched.defer_device = True
    sched._begin()
    return sched.deferred[1]


def _best_of(run, repeats: int) -> float:
    run()  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def _est_traffic_bytes(a, lanes: int = 1) -> int:
    """Rough HBM-traffic model: per slot x round, score_all_nodes
    streams the four [N, D] f32 fleet tensors (capacity/reserved/
    usage/job-counts) and one [N] bool feasibility row -> lanes * G *
    rounds * N * (4*D*4 + 1) bytes.  An estimate, not a measurement —
    XLA keeps the scan carry in HBM and may fuse reads — but it bounds
    the kernel's order of magnitude."""
    from nomad_tpu.models.fleet import NDIMS

    g_pad, n_pad = a.feasible_h.shape
    return lanes * g_pad * a.rounds * n_pad * (4 * NDIMS * 4 + 1)


def device_kernel_stats(h, job, repeats: int = 5):
    """Pure device time of the config-4 rounds kernel with resident
    inputs, plus the rough HBM-traffic estimate (_est_traffic_bytes),
    so the report grounds the speedups in hardware terms
    (device_fraction + roofline) instead of ratios alone."""
    import numpy as np

    from nomad_tpu.ops.binpack import place_rounds
    from nomad_tpu.parallel.devices import ensure_on_default

    a = _deferred_args(h, job)
    cap_d, res_d = a.statics.device_capacity_reserved()
    feas_d = ensure_on_default(None, a.feasible_h)
    usage_d = ensure_on_default(None, a.view.usage)
    jc_d = ensure_on_default(None, a.view.job_counts)

    def run():
        out = place_rounds(cap_d, res_d, usage_d, jc_d, feas_d, a.asks,
                           a.distinct, a.counts, a.penalty,
                           k_cap=a.k_cap, rounds=a.rounds)
        # Fence by pulling the choices back to the host: it is what
        # the scheduler does with every dispatch anyway.
        np.asarray(out[0])

    return _best_of(run, repeats), _est_traffic_bytes(a)


def storm_kernel_stats(h, job, lanes: int, repeats: int = 2):
    """Pure device time of the fused [B, G, N] storm kernel (the config-5
    dispatch shape) with resident inputs; traffic model = per-lane
    config-4 traffic x lanes (each lane streams its own feasibility and
    evolves its own usage copy)."""
    import numpy as np

    from nomad_tpu.ops.binpack import place_rounds_batch
    from nomad_tpu.parallel.devices import ensure_on_default

    a = _deferred_args(h, job)
    cap_d, res_d = a.statics.device_capacity_reserved()
    usage_d = ensure_on_default(None, a.view.usage)

    def lane_cast(x):
        return ensure_on_default(None, np.broadcast_to(
            x, (lanes,) + x.shape).copy())

    jc_b, feas_b = lane_cast(a.view.job_counts), lane_cast(a.feasible_h)
    asks_b, dist_b = lane_cast(a.asks), lane_cast(a.distinct)
    counts_b = lane_cast(a.counts)
    pen_b = ensure_on_default(None, np.full(
        lanes, float(a.penalty), dtype=np.float32))

    def run():
        out = place_rounds_batch(cap_d, res_d, usage_d, jc_b, feas_b,
                                 asks_b, dist_b, counts_b, pen_b,
                                 k_cap=a.k_cap, rounds=a.rounds)
        np.asarray(out[0])  # honest fence, see device_kernel_stats

    return _best_of(run, repeats), _est_traffic_bytes(a, lanes)


def bench_storm_device(h, jobs, repeats: int):
    """One fused BatchEvalRunner dispatch for the whole storm."""
    from nomad_tpu.scheduler.batch import BatchEvalRunner

    best = float("inf")
    for _ in range(repeats):
        recorder = _RecordOnlyPlanner()
        evals = [make_eval(j) for j in jobs]
        snapshot = h.state.snapshot()
        start = time.perf_counter()
        BatchEvalRunner(snapshot, recorder).process(evals)
        best = min(best, time.perf_counter() - start)
        assert len(recorder.plans) == len(jobs)
    return best


# --------------------------------------------------------------------------
# Config builders


def _config1_jobs(n_jobs: int):
    """Service job, single task-group (count 10, mock shape)."""
    jobs = []
    for _ in range(n_jobs):
        j = mock.job()
        jobs.append(j)
    return jobs


def _config2_jobs(n_jobs: int):
    """Batch job, 10 TGs with constraint stanzas + distinct_hosts."""
    jobs = []
    for _ in range(n_jobs):
        j = mock.job()
        j.type = "batch"
        groups = []
        for g in range(10):
            tg = _bench_task_group(f"tg-{g}")
            tg.count = 4
            tg.constraints = [
                Constraint(hard=True, l_target="$attr.kernel.name",
                           r_target="linux", operand="="),
                Constraint(hard=True, operand=CONSTRAINT_DISTINCT_HOSTS),
            ]
            groups.append(tg)
        j.task_groups = groups
        jobs.append(j)
    return jobs


def _config3_job():
    j = mock.system_job()
    return j


def bench_client_swarm(n_agents: int, window_s: float, note) -> dict:
    """Config 5d: >=10k agents heartbeating + long-polling through ONE
    server on the event-driven serving plane.

    The structural claim measured: server resource usage is O(worker
    pools), not O(connected clients).  ``n_agents`` simulated agents
    (nomad_tpu/agent/swarm.AgentSwarm: shared mux sessions, one TTL
    wheel, async callbacks — the client side is O(connections) too, or
    the bench would measure its own thread army) register over the
    wire, park one alloc long-poll each in the watch fan-out, and
    heartbeat on the liveness lane.  Mid-window writes to the allocs
    table fire full-fleet fan-out wakeups.  Asserted invariants:
    zero node-TTL false expiries, p99 heartbeat latency bounded by a
    bar CALIBRATED against this run's measured registration rate (the
    row's own capacity measurement — raw p99 and bar both recorded; a
    fixed wall-clock bar was host-speed-sensitive and failed slower
    hosts on an unchanged tree), serving-plane thread count EXACTLY
    dispatch_workers + 1 (the loop), and a clean teardown (no leaked
    waiters/conns/threads).  The FLEET SIZE is host-calibrated too
    (a registration-rate probe bounds it): the earliest-registered
    nodes carry the minimum ~10 s TTL, so a host must be able to
    register the fleet inside that budget or early nodes genuinely
    expire — the capture host runs the full fleet, a slower host runs
    the same row at the fleet it can sustain, recorded beside the
    requested size.
    """
    import threading

    from nomad_tpu.agent.swarm import AgentSwarm
    from nomad_tpu.server import Server, ServerConfig

    def serving_threads() -> list:
        # Port-qualified names: count ONLY this server's serving
        # threads — an earlier bench's husks must not fail the
        # O(pool) structural assertion.
        port = srv.rpc_address()[1]
        # Exact loop name / dispatch prefix WITH the "-" separator: a
        # bare f"rpc-dispatch:{port}" prefix would also match another
        # server whose port has this one as a decimal prefix
        # (4646 vs 46460).
        return [t.name for t in threading.enumerate()
                if t.name == f"rpc-loop:{port}"
                or t.name.startswith(f"rpc-dispatch:{port}-")]

    workers = 8

    # Host-capacity calibration (the 5c pattern: measure THIS run's
    # capacity, then hold the invariants at that capacity).  The
    # earliest-registered nodes carry the MINIMUM rate-scaled TTL
    # (~10 s at a small armed count), so the fleet size a host can
    # honestly sustain is bounded by its measured registration+beat
    # throughput: a 500-agent throwaway swarm against a throwaway
    # server measures it, and the fleet scales to ~10 s worth of that
    # rate (3,226/s on the BENCH_r08 capture host -> the full 10k
    # fleet there; a slower host runs the same row, same invariants,
    # at the fleet it can actually register inside the early TTLs —
    # the fixed 10k fleet expired early nodes on the seed tree here).
    probe_n = min(500, n_agents)
    probe_srv = Server(ServerConfig(
        num_schedulers=0, use_device_scheduler=False, enable_rpc=True,
        rpc_dispatch_workers=workers, heartbeat_seed=13))
    probe_srv.establish_leadership()
    probe = AgentSwarm(probe_srv.rpc_address(), probe_n, conns=4,
                       hb_conns=2, beat_interval=30.0, poll_wait=5.0,
                       seed=13)
    tp = time.perf_counter()
    try:
        probe.start(register_timeout=120.0)
        probe_rate = probe_n / (time.perf_counter() - tp)
    finally:
        probe.stop()
        probe_srv.shutdown()
    n_requested = n_agents
    n_agents = min(n_agents, max(1000, int(probe_rate * 10.0)))

    srv = Server(ServerConfig(
        num_schedulers=0, use_device_scheduler=False, enable_rpc=True,
        rpc_dispatch_workers=workers, heartbeat_seed=9))
    srv.establish_leadership()
    state = srv.fsm.state
    # One beat per agent per ~window: 10k agents => ~500-800 beats/s
    # offered, every agent sampled at least once for the percentile.
    beat_interval = min(20.0, max(2.0, n_agents / 600.0))
    swarm = AgentSwarm(srv.rpc_address(), n_agents, conns=16,
                       hb_conns=4, beat_interval=beat_interval,
                       poll_wait=60.0, seed=9)
    try:
        t0 = time.perf_counter()
        swarm.start(register_timeout=600.0)
        register_s = time.perf_counter() - t0
        # Seed the allocs table (a pre-first-write index of 0 answers
        # immediately by contract) so every poll parks in the fan-out.
        base_index = srv.raft.applied_index() + 1
        state.upsert_allocs(base_index, [])
        park_deadline = time.monotonic() + 120
        while state.watch.live_waiters() < int(0.98 * n_agents) and \
                time.monotonic() < park_deadline:
            time.sleep(0.1)
        parked_peak = state.watch.live_waiters()
        threads_mid = serving_threads()
        delivered0 = state.watch.stats()["delivered"]
        beats0 = swarm.stats()["beats_ok"]

        # The measured window: heartbeats flow continuously; 4 writes
        # spaced across it each wake the ENTIRE parked fleet.  (The
        # window is deliberately NOT extended to time each drain:
        # storm time is heartbeat-starvation time on a slow host, and
        # stretching it converts a latency measurement into real TTL
        # expiries.)
        wakes = 4
        t0 = time.perf_counter()
        for i in range(wakes):
            time.sleep(window_s / (wakes + 1))
            state.upsert_allocs(base_index + 1 + i, [])
        time.sleep(window_s / (wakes + 1))
        window = time.perf_counter() - t0

        watch_stats = state.watch.stats()
        wakeups = watch_stats["delivered"] - delivered0
        st = swarm.stats()
        hb = srv.heartbeats.stats()
        loop_stats = srv.rpc_server._loop.stats()
        pool_stats = srv.rpc_server._pool.stats()
        beats = st["beats_ok"] - beats0
        not_ready = [n.id for n in state.nodes() if n.status != "ready"]
        false_expiries = hb["expiries"] + len(not_ready)

        # The no-collapse invariants (fail the bench, not just the row).
        # Heartbeats ride the dispatch liveness lane: ZERO errors even
        # through full-fleet wake storms.  Re-polls may shed at the
        # dispatch bound mid-storm (honest back-pressure, counted and
        # retried); the parked population must recover regardless.
        assert false_expiries == 0, (hb, not_ready[:3])
        assert st["beat_errors"] == 0, st
        assert parked_peak >= int(0.98 * n_agents), parked_peak
        assert wakeups >= wakes * int(0.98 * n_agents), wakeups
        recover_deadline = time.monotonic() + 60
        while state.watch.live_waiters() < int(0.98 * n_agents) and \
                time.monotonic() < recover_deadline:
            time.sleep(0.1)
        parked_after = state.watch.live_waiters()
        assert parked_after >= int(0.98 * n_agents), parked_after
        # THE structural assertion: serving threads == pool + loop,
        # with n_agents clients connected — O(pool), not O(clients).
        assert len(threads_mid) == workers + 1, threads_mid
        # Liveness bound: p99 heartbeat latency is storm-tail-dominated
        # (a full-fleet wake burns seconds of single-core Python while
        # client and server share the GIL), and both the storm drain
        # and the registration phase are bounded by the same GIL-bound
        # per-request throughput — so the bar is CALIBRATED against
        # this run's measured registration rate (the row's own
        # capacity measurement, the 5c pattern): the historical 5 s
        # bar was set where registration ran 3,226 agents/s
        # (BENCH_r08), and it scales inversely with the same-run rate,
        # floored there for fast hosts and capped at 45 s — still >4x
        # inside the ~200 s rate-scaled TTL, so a passing row always
        # means storms cannot convert into missed heartbeats (which
        # false_expiries == 0 above proves end to end regardless).
        # The fixed wall-clock bar this replaces failed slower hosts
        # on an UNCHANGED tree (PR 12 notes).
        reg_rate = n_agents / register_s
        p99_beat_bar_ms = min(45_000.0,
                              max(5000.0, 5000.0 * 3226.0 / reg_rate))
        assert st["p99_beat_ms"] < p99_beat_bar_ms, \
            (st, reg_rate, p99_beat_bar_ms)
        row = {
            "agents": n_agents,
            "agents_requested": n_requested,
            "host_probe_register_per_sec": round(probe_rate, 1),
            "window_s": round(window, 2),
            "registered_per_sec": round(n_agents / register_s, 1),
            "heartbeats_in_window": beats,
            "p50_heartbeat_ms": st["p50_beat_ms"],
            "p99_heartbeat_ms": st["p99_beat_ms"],
            "p99_heartbeat_bar_ms": round(p99_beat_bar_ms, 1),
            "beat_errors": st["beat_errors"],
            "long_polls_parked": parked_peak,
            "long_polls_parked_after_storms": parked_after,
            "poll_shed_retries": st["poll_errors"],
            "dispatch_shed": pool_stats["rejected"],
            "fanout_wakeups": wakeups,
            "fanout_wakeups_per_sec": round(wakeups / window, 1),
            "watch_timeouts": watch_stats["timeouts"],
            "server_threads": len(threads_mid),
            "dispatch_workers": workers,
            "open_conns": loop_stats["open_conns"],
            "frames_in": loop_stats["frames_in"],
            "dispatched": pool_stats["dispatched"],
            "false_expiries": false_expiries,
            "note": (f"{n_agents} agents heartbeating + long-polling "
                     "through ONE event-driven server: every poll parks "
                     "as a watch-fan-out callback (zero threads), "
                     f"{wakes} mid-window writes each wake the whole "
                     "fleet, and the serving plane holds at "
                     "dispatch_workers+1 threads — O(pool), not "
                     "O(clients); false TTL expiries must be zero"),
        }
        note(f"config5d client swarm: {n_agents} agents "
             f"(requested {n_requested}, host probe "
             f"{probe_rate:.0f} reg/s) over "
             f"{loop_stats['open_conns']} conns, registered "
             f"{n_agents / register_s:.0f}/s; window {window:.1f}s: "
             f"{beats} beats (p99 {st['p99_beat_ms']:.1f}ms vs "
             f"calibrated bar {p99_beat_bar_ms:.0f}ms at "
             f"{n_agents / register_s:.0f} reg/s, 0 errors), "
             f"{parked_peak} polls parked, {wakeups} fan-out wakeups "
             f"({wakeups / window:.0f}/s), server threads "
             f"{len(threads_mid)} (= {workers} workers + 1 loop), "
             f"false_expiries 0")
        return row
    finally:
        swarm.stop()
        srv.shutdown()


def _controller_row(ctl_stats: dict) -> dict:
    """ONE shape for the per-knob trajectory block both convergence
    rigs (5c and 5f) embed in their rows — drift between the two would
    make the canonical BENCH json structurally inconsistent."""
    return {
        "ticks": ctl_stats["ticks"],
        "adjustments": ctl_stats["adjustments"],
        "knobs": {
            name: {"initial": k["initial"],
                   "converged": k["value"],
                   "adjustments": k["adjustments"],
                   "reversals": k["reversals"],
                   "rail_hits": k["rail_hits"],
                   "trajectory": k["trajectory"]}
            for name, k in ctl_stats["knobs"].items()},
    }


def _controller_reversals(row: dict) -> int:
    return sum(k["reversals"]
               for k in row["controller"]["knobs"].values())


def _knob_moves(row: dict) -> str:
    return ", ".join(
        f"{n.split('.')[-1]} {k['initial']}->{k['converged']}"
        for n, k in row["controller"]["knobs"].items()
        if k["adjustments"])


def _overload_phase(n_agents: int, window_s: float,
                    capacity_jobs: int, note, *,
                    depth_limit: int = 64,
                    brownout_ratio: float = 0.5,
                    overload_ratio: float = 1.0,
                    controller: bool = False,
                    goodput_floor: "float | None" = 0.7,
                    label: str = "hand_tuned") -> dict:
    """One 5c world: a real Server (broker admission + plan-queue
    bound + TTL wheel + paced reconciliation, server/overload.py) with
    ``n_agents`` simulated heartbeating agents.  Phase 1 measures
    unloaded capacity (with the heartbeat tax already running, so both
    phases pay it); phase 2 offers ~5x that rate for ``window_s``
    through the overload-classified retry policy, plus a stream of
    deadline-expired synthetic evals.  Records goodput, sheds,
    expired_drops, p99 heartbeat latency — and asserts the no-collapse
    invariants: ``false_expiries == 0`` always, and (when
    ``goodput_floor`` is set) goodput >= that fraction of unloaded
    capacity.

    The admission knobs are parameters because the ISSUE 14
    convergence rows mis-set them 4x in both directions and attach the
    feedback control plane (``controller=True`` — the real Server
    wiring: ``control_enabled``, one seeded tick thread) to converge
    them back LIVE; the returned row then carries the controller's
    per-knob trajectories."""
    import math
    import random
    import threading

    from nomad_tpu.agent.agent import InprocRPC
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.utils.retry import RetryPolicy, transport_or_overload

    srv = Server(ServerConfig(
        num_schedulers=4,
        use_device_scheduler=False,
        broker_depth_limit=depth_limit,
        overload_brownout_ratio=brownout_ratio,
        overload_ratio=overload_ratio,
        heartbeat_seed=7,
        control_enabled=controller,
        control_interval=0.05,
        control_seed=11,
    ))
    srv.establish_leadership()
    rpc = InprocRPC(srv)
    try:
        state = srv.fsm.state
        base_index = srv.raft.applied_index()
        for i in range(n_agents):
            state.upsert_node(base_index + 1 + i, mock.node(i))
        for node in state.nodes():
            srv.heartbeats.reset_heartbeat_timer(node.id)
        agent_ids = [n.id for n in state.nodes()]

        # Heartbeaters run through BOTH phases: the capacity number
        # already includes the liveness tax, so the 70% floor compares
        # like against like.
        stop = threading.Event()
        beat_lat: list = []
        beat_errors: list = []

        def _beater(shard: list) -> None:
            lat: list = []
            while not stop.is_set():
                for nid in shard:
                    t0 = time.perf_counter()
                    try:
                        rpc.call("Node.Heartbeat", {"node_id": nid},
                                 timeout=5.0)
                    except Exception as e:
                        beat_errors.append(repr(e))
                    lat.append(time.perf_counter() - t0)
                stop.wait(0.1)
            beat_lat.extend(lat)

        beaters = [threading.Thread(
            target=_beater, args=(agent_ids[i::4],), daemon=True,
            name=f"bench-beater-{i}") for i in range(4)]
        for b in beaters:
            b.start()

        def _terminal_count(job_ids: set) -> int:
            return sum(1 for e in state.evals()
                       if e.job_id in job_ids
                       and e.status in ("complete", "failed"))

        policy = RetryPolicy(base=0.02, max_delay=0.5, max_attempts=200,
                             retryable=transport_or_overload,
                             name="bench.overload_submit")

        def _submit_all(jobs: list, lanes: int, stop_ev=None,
                        done=None):
            """Same 4-way submission shape for BOTH phases, so the
            goodput-vs-capacity ratio compares like against like."""
            done = [0] if done is None else done
            done_lock = threading.Lock()

            def lane_fn(lane: int) -> None:
                rng = random.Random(5000 + lane)
                for job in jobs[lane::lanes]:
                    if stop_ev is not None and stop_ev.is_set():
                        return
                    try:
                        policy.call(
                            lambda j=job: rpc.call(
                                "Job.Register", {"job": j.to_dict()},
                                timeout=2.0),
                            stop=stop_ev, rng=rng)
                    except Exception:
                        continue  # window closed mid-retry
                    with done_lock:
                        done[0] += 1

            lanes_t = [threading.Thread(target=lane_fn, args=(i,),
                                        daemon=True,
                                        name=f"bench-submitter-{i}")
                       for i in range(lanes)]
            for t in lanes_t:
                t.start()
            if stop_ev is None:
                for t in lanes_t:
                    t.join()
                return done[0]
            return lanes_t  # storm mode: caller owns the join

        # --- phase 1: unloaded capacity --------------------------------
        srv.job_register(_bench_job(2))  # compile/warm the service path
        cap_jobs = [_bench_job(2) for _ in range(capacity_jobs)]
        cap_ids = {j.id for j in cap_jobs}
        t0 = time.perf_counter()
        _submit_all(cap_jobs, lanes=4)
        while _terminal_count(cap_ids) < len(cap_jobs):
            time.sleep(0.005)
        capacity = len(cap_jobs) / (time.perf_counter() - t0)

        # --- phase 2: 5x offered overload ------------------------------
        # The cap only bounds job-object construction; when it would
        # bind (a very fast host), the window SHRINKS so the offered
        # ratio holds at 5x instead of silently degrading.
        offered_n = int(math.ceil(5.0 * capacity * window_s))
        if offered_n > 20_000:
            window_s = 20_000 / (5.0 * capacity)
            offered_n = 20_000
            note(f"config5c: fast host; window shrunk to {window_s:.2f}s "
                 f"to hold the 5x offered ratio at the 20k job cap")
        offered_ratio = offered_n / window_s / capacity
        assert offered_ratio >= 4.9, \
            f"offered load only {offered_ratio:.1f}x capacity"
        storm = [_bench_job(2) for _ in range(offered_n)]
        storm_ids = {j.id for j in storm}
        window_over = threading.Event()

        def _expired_feeder() -> None:
            # Deadline-bounded synthetics beyond capacity: their
            # usefulness expires before any worker can run them.
            while not window_over.is_set():
                ev = Evaluation(
                    id=generate_uuid(), priority=1, type="service",
                    triggered_by="job-register",
                    job_id=generate_uuid(), status="pending")
                try:
                    srv.eval_broker.enqueue(
                        ev, deadline=time.monotonic() + 0.001,
                        force=True)
                except Exception:
                    pass
                window_over.wait(0.02)

        feeder = threading.Thread(target=_expired_feeder, daemon=True,
                                  name="bench-expired-feeder")
        submitted = [0]
        t0 = time.perf_counter()
        feeder.start()
        threads = _submit_all(storm, lanes=4, stop_ev=window_over,
                              done=submitted)
        time.sleep(window_s)
        completed_in_window = _terminal_count(storm_ids)
        window_over.set()
        for t in threads + [feeder]:
            t.join(10.0)
        goodput = completed_in_window / (time.perf_counter() - t0)

        # Drain what was admitted so shutdown is clean (not counted).
        drain_deadline = time.monotonic() + 30
        while time.monotonic() < drain_deadline:
            if srv.eval_broker.stats()["total_ready"] == 0 and \
                    srv.eval_broker.stats()["total_unacked"] == 0:
                break
            time.sleep(0.05)
        stop.set()
        for b in beaters:
            b.join(5.0)

        hb = srv.heartbeats.stats()
        broker = srv.eval_broker.stats()
        ctrl = srv.overload.stats()
        not_ready = [n.id for n in state.nodes() if n.status != "ready"]
        false_expiries = hb["expiries"] + len(not_ready)

        # The no-collapse invariants are load-bearing: fail the bench,
        # not just the row, when the control plane regresses.  The
        # liveness invariants hold for EVERY phase — however mis-set
        # the admission knobs start, the heartbeat lane and the
        # brownout deferral are out of the controller's (and the
        # mis-setting's) reach.
        assert false_expiries == 0, (hb, not_ready[:3], beat_errors[:3])
        assert not beat_errors, beat_errors[:3]
        if goodput_floor is not None:
            assert goodput >= goodput_floor * capacity, \
                f"congestion collapse: goodput {goodput:.1f}/s vs " \
                f"capacity {capacity:.1f}/s"
        assert broker["expired_drops"] > 0
        p99_beat_ms = _p(beat_lat, 99)
        assert p99_beat_ms < 1000.0, \
            f"unbounded heartbeat latency: p99 {p99_beat_ms:.0f}ms"

        controller_row = _controller_row(srv.controller.stats()) \
            if controller else None

        shed_total = srv.overload.shed_count() + broker["depth_sheds"]
        row = {
            "agents": n_agents,
            "window_s": window_s,
            "initial_knobs": {"broker_depth_limit": depth_limit,
                              "brownout_ratio": brownout_ratio,
                              "overload_ratio": overload_ratio},
            "controller": controller_row,
            "capacity_evals_per_sec": round(capacity, 2),
            "offered_evals_per_sec": round(offered_n / window_s, 2),
            "goodput_evals_per_sec": round(goodput, 2),
            "goodput_vs_capacity": round(goodput / capacity, 3),
            "submitted": submitted[0],
            "shed": shed_total,
            "expired_drops": broker["expired_drops"],
            "p99_heartbeat_ms": round(p99_beat_ms, 2),
            "false_expiries": false_expiries,
            "deferred_expiries": hb["deferred_expiries"],
            "overload_state_transitions": ctrl["transitions"],
            "note": ("5x offered overload vs a real server w/ admission "
                     "control + TTL-wheel heartbeats + paced "
                     "reconciliation: goodput must hold >= 70% of "
                     "unloaded capacity with zero false TTL expiries "
                     "(no congestion collapse / metastable spiral)"),
        }
        note(f"config5c {label}: {n_agents} agents, offered "
             f"{offered_n / window_s:.0f}/s vs capacity {capacity:.0f}/s "
             f"-> goodput {goodput:.0f}/s "
             f"({goodput / capacity:.0%} of capacity), shed {shed_total}, "
             f"expired_drops {broker['expired_drops']}, p99 heartbeat "
             f"{p99_beat_ms:.1f}ms, false_expiries {false_expiries} "
             f"(deferred {hb['deferred_expiries']})")
        return row
    finally:
        srv.shutdown()


def bench_overload_brownout(n_agents: int, window_s: float,
                            capacity_jobs: int, note) -> dict:
    """Config 5c: the overload control plane under 5x offered load —
    the hand-tuned row, plus the ISSUE 14 convergence rows.

    The hand-tuned phase asserts the historical no-collapse bar
    (goodput >= 70% of same-run capacity, zero false expiries).  Then
    the SAME storm shape reruns twice against fresh servers whose
    admission constants are deliberately mis-set 4x in both directions
    — broker depth limit 16 and 256 (vs 64), brownout/overload ratios
    0.125/0.25 and clamped-high — with the feedback control plane
    attached (``control_enabled``: the real Server wiring, one seeded
    tick thread adjusting broker.depth_limit and the overload ratios
    through railed actuators).  Each convergence row must reach >= 90%
    of the hand-tuned goodput within its measurement window, keep
    ``false_expiries == 0`` (the liveness lane is out of the
    controller's reach by construction), and keep the controller's
    reversal count bounded — an oscillating loop fails the row even at
    full goodput."""
    hand = _overload_phase(n_agents, window_s, capacity_jobs, note,
                           label="hand_tuned")
    convergence: dict = {}
    for tag, knobs in (
            ("init_4x_small", dict(depth_limit=16,
                                   brownout_ratio=0.125,
                                   overload_ratio=0.25)),
            ("init_4x_large", dict(depth_limit=256,
                                   brownout_ratio=0.95,
                                   overload_ratio=1.0))):
        conv = _overload_phase(
            n_agents, window_s, capacity_jobs, note,
            controller=True, goodput_floor=None, label=tag, **knobs)
        ratio = conv["goodput_evals_per_sec"] / \
            hand["goodput_evals_per_sec"]
        assert ratio >= 0.9, (tag, conv["goodput_evals_per_sec"],
                              hand["goodput_evals_per_sec"])
        assert conv["false_expiries"] == 0, (tag, conv)
        reversals = _controller_reversals(conv)
        assert reversals <= 12, (tag, conv["controller"])
        conv["vs_hand_tuned"] = round(ratio, 3)
        convergence[tag] = conv
        note(f"config5c convergence {tag}: "
             f"{conv['goodput_evals_per_sec']:.0f}/s goodput = "
             f"{ratio:.0%} of hand-tuned; knobs {_knob_moves(conv)}; "
             f"{reversals} reversals")
    row = dict(hand)
    row["convergence"] = convergence
    return row


def _applier_saturation_phase(n_submitters: int, submits_per: int,
                              sequential: bool,
                              knobs: "dict | None" = None,
                              controller: bool = False) -> dict:
    """One 5f phase: a fresh leader commit pipeline driven to
    saturation by ``n_submitters`` worker-protocol threads.

    ``sequential=True`` runs the pre-partition applier — per-plan token
    fence on the broker, one flat verify walk — PINNED to the r10/r11
    operating point (always-full windows, occupancy ~60, via a generous
    gather): that regime is what "the same window occupancy" in the
    ISSUE 13 target means, and `serial_ms_per_plan` measured there is
    the baseline's serialized-commit-section cost under its best-case
    amortization.

    ``knobs`` overrides the applier's hand-tuned constants (the ISSUE
    14 convergence rows mis-set them 4x in both directions), and
    ``controller=True`` attaches the feedback control plane
    (control/wiring.applier_controller) so the mis-set constants must
    converge LIVE under load; the returned row then carries the
    controller's per-knob trajectories (initial -> converged)."""
    import random
    import threading

    import numpy as np

    from nomad_tpu.server.eval_broker import EvalBroker
    from nomad_tpu.server.fsm import NomadFSM
    from nomad_tpu.server.plan_apply import PlanApplier
    from nomad_tpu.server.plan_queue import PlanQueue
    from nomad_tpu.server.raft import InmemRaft
    from nomad_tpu.structs import AllocMetric, Evaluation, Plan, codec
    from nomad_tpu.structs.alloc_slab import AllocSlab
    from nomad_tpu.structs.model import proto_of

    knobs = dict(knobs or {})
    broker = EvalBroker(nack_timeout=120.0)
    fsm = NomadFSM(eval_broker=broker)
    raft = InmemRaft(fsm)
    queue = PlanQueue()
    applier = PlanApplier(queue, broker, raft,
                          state_fn=lambda: fsm.state,
                          max_window=knobs.get("max_window", 64),
                          sequential=sequential,
                          gather_s=knobs.get(
                              "gather_s",
                              0.25 if sequential else 0.02))
    if "max_inflight_commits" in knobs:
        applier.max_inflight_commits = knobs["max_inflight_commits"]
    ctl = None
    if controller:
        from nomad_tpu.control import applier_controller
        ctl = applier_controller(applier, queue, broker=broker,
                                 interval=0.05, seed=13)
    broker.set_enabled(True)
    queue.set_enabled(True)
    applier.start()

    n_nodes = 512
    for i in range(n_nodes):
        raft.apply(codec.encode(
            codec.NODE_REGISTER_REQUEST,
            {"node": mock.node(i).to_dict()})).wait()
    node_ids = [n.id for n in fsm.state.nodes()]

    # One tiny job template per submitter: 1 TG, 1 netless task with a
    # 1-cpu ask so the whole storm fits the fleet (the row measures the
    # commit section, not rejection churn).
    metric_static, _ = proto_of(AllocMetric)
    jobs = []
    for k in range(n_submitters):
        job = mock.job()
        job.constraints = []
        job.task_groups = [TaskGroup(
            name="tg", count=1,
            tasks=[Task(name="web", driver="exec",
                        resources=Resources(cpu=1, memory_mb=1))])]
        jobs.append(job)

    def mk_plan(ev, token, job, node_id) -> Plan:
        """One placement as a 1-row AllocSlab — the columnar contract
        the schedulers emit, end-to-end through verify/wire/store."""
        size = Resources(cpu=1, memory_mb=1)
        slots = [(size, [("web", {"cpu": 1, "memory_mb": 1,
                                  "disk_mb": 0, "iops": 0}, None)])]
        slab = AllocSlab(
            eval_id=ev.id, job=job, slots=slots,
            metric_proto=dict(metric_static, nodes_evaluated=n_nodes),
            groups=[0], ids=[generate_uuid()],
            names=[f"{job.id}.tg[0]"], tgs=["tg"], scores=[1.0],
            port_off=np.zeros(2, dtype=np.int64), n_rows=1)
        slab.node_ids[0] = node_id
        slab.ips[0] = ""
        slab.devs[0] = ""
        slab.seal(1)
        plan = Plan(eval_id=ev.id, eval_token=token,
                    priority=ev.priority)
        # The worker protocol's nack-window stamp (overload plane): a
        # real deadline, so `expired_drops == 0` is a live claim — the
        # deadline-promoted drain + deadline-first component order must
        # actually keep every plan inside its window under saturation.
        plan.deadline = time.monotonic() + 10.0
        plan.node_allocation[node_id] = [slab.alloc(0)]
        return plan

    total = n_submitters * submits_per
    lats: list = [None] * total
    errors: list = []
    start_gate = threading.Event()

    def submitter(k: int) -> None:
        rng = random.Random(7000 + k)
        start_gate.wait()
        for i in range(submits_per):
            try:
                # Fresh job_id per eval keeps the broker's per-job
                # serialization out of the measurement (the row is
                # about the applier, not broker contention).
                ev = Evaluation(
                    id=generate_uuid(), priority=50, type="service",
                    triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                    job_id=generate_uuid())
                broker.enqueue(ev, force=True)
                got, token = broker.dequeue(["service"], timeout=60)
                assert got is not None
                plan = mk_plan(got, token, jobs[k],
                               node_ids[rng.randrange(n_nodes)])
                t0 = time.perf_counter()
                future = queue.enqueue(plan)
                result = future.wait(120)
                lats[k * submits_per + i] = time.perf_counter() - t0
                assert result is not None and \
                    sum(len(v) for v in
                        result.node_allocation.values()) == 1, result
                broker.ack(got.id, token)
            except Exception as e:  # pragma: no cover - bench guard
                errors.append(repr(e))
                return

    threads = [threading.Thread(target=submitter, args=(k,),
                                daemon=True, name=f"bench-5f-{k}")
               for k in range(n_submitters)]
    for t in threads:
        t.start()
    if ctl is not None:
        ctl.start()
    t0 = time.perf_counter()
    start_gate.set()
    for t in threads:
        t.join(600.0)
    wall = time.perf_counter() - t0
    assert not errors, errors[:3]
    assert all(not t.is_alive() for t in threads), "stuck submitter"

    stats = applier.stats()
    ctl_stats = None
    if ctl is not None:
        ctl.stop()
        ctl_stats = ctl.stats()
    queue.set_enabled(False)
    broker.set_enabled(False)
    applier.shutdown(10.0)
    broker.shutdown()

    placed = len([a for a in fsm.state.allocs()
                  if a.node_id and not a.terminal_status()])
    # Exactly-once and fully committed: every submission landed one
    # alloc, and group commit genuinely amortized the serialized
    # section (more than two plans per raft apply at saturation).
    assert placed == total, (placed, total)
    assert stats["plans_committed"] == total, stats
    assert stats["batch_occupancy"] > 2.0, stats
    done_lats = [v for v in lats if v is not None]
    return {
        "controller": _controller_row(ctl_stats)
        if ctl_stats is not None else None,
        "submissions": total,
        "placed": placed,
        "window_s": round(wall, 3),
        "plans_per_sec": round(total / wall, 1),
        "commits": stats["commits"],
        "commits_per_sec": round(stats["commits"] / wall, 1),
        "batch_occupancy": round(stats["batch_occupancy"], 2),
        "conflict_fallbacks": stats["conflict_fallbacks"],
        "expired_drops": stats["expired_drops"],
        "components": stats["components"],
        "component_occupancy": round(stats["component_occupancy"], 2),
        "cross_component_speedup":
            round(stats["cross_component_speedup"], 2),
        "serial_ms_per_plan": round(stats["serial_ms_per_plan"], 4),
        "p50_submit_ms": round(_p(done_lats, 50), 2),
        "p99_submit_ms": round(_p(done_lats, 99), 2),
    }


def bench_applier_saturation(n_submitters: int, submits_per: int,
                             note) -> dict:
    """Config 5f: the partitioned window verify under submitter
    saturation (ROADMAP item 2, ISSUE 13), measured against an IN-RUN
    sequential baseline.

    Two phases over identical fresh worlds, same offered shape:

    - **sequential**: the pre-partition applier (per-plan token fence
      on the broker, one flat verify walk, no window gather) — the
      r10/r11 applier's behavior.  It still rides this PR's broker
      rework (wheel nack timers, targeted wakeups), so the recorded
      speedup UNDERSTATES the change vs the r10/r11 captures
      (BENCH_r10: 20 commits/s, p99 1.08s on a ~3x faster host).
    - **partitioned**: window-batched token fence, claim-graph
      component partitioning with concurrent deadline-first
      verification, adaptive window gather, wheel-backed respond.

    Asserted in-bench (the ISSUE 13 targets): partitioned p99
    submit->respond < 500 ms; the applier's serialized section
    (`serial_ms_per_plan`: token fence + window verify + overlay fold —
    the commit tail rides the committer pipeline) >= 2x cheaper per
    plan than the sequential baseline at the baseline's full-window
    occupancy — the host-portable statement of "commits/s >= 2x at the
    same window occupancy"; end-to-end plans/s >= 1.05x the baseline
    held to a >= 0.9x no-regression floor (at saturation the bench is
    bounded by its own GIL-sharing submitter herd, paid identically by
    both phases, so phase deltas are host-scheduling noise); and
    ``expired_drops == 0`` with every plan carrying a REAL 10 s
    deadline under saturation; exactly-once placement and occupancy > 2
    hold in both phases.
    """
    seq = _applier_saturation_phase(n_submitters, submits_per,
                                    sequential=True)
    part = _applier_saturation_phase(n_submitters, submits_per,
                                     sequential=False)

    # The headline ratio: the SERIALIZED commit section's per-plan cost
    # (token fence + window verify + wire encode + raft dispatch —
    # exactly what "the leader's plan applier is the last serialization
    # point" refers to), with the baseline at its best-case full-window
    # amortization.  This is "commits/s at the same window occupancy"
    # stated host-portably: a serialized section >= 2x cheaper per plan
    # sustains >= 2x the commits at any fixed occupancy.
    speed_serial = seq["serial_ms_per_plan"] / part["serial_ms_per_plan"]
    speed_plans = part["plans_per_sec"] / seq["plans_per_sec"]
    assert part["p99_submit_ms"] < 500.0, part
    assert speed_serial >= 2.0, (part, seq)
    # End-to-end plans/s moves less than the serialized section: at
    # saturation the bench is bounded by its own 256 GIL-sharing
    # submitter threads (broker protocol + slab construction), which
    # both phases pay identically — phase-to-phase deltas sit inside
    # host-scheduling noise (~±10%).  The floor asserts the pipeline
    # re-structuring never COSTS end-to-end throughput beyond noise;
    # the measured ratio is recorded either way.
    assert speed_plans >= 0.9, (part, seq)
    assert part["expired_drops"] == 0, part
    assert seq["expired_drops"] == 0, seq
    assert part["components"] > 0, part

    # --- ISSUE 14 convergence rows: the feedback control plane must
    # rescue deliberately 4x-mis-set applier constants LIVE, reaching
    # >= 90% of the same-run hand-tuned goodput within the phase,
    # with the correctness bars intact (expired_drops == 0 under real
    # 10s deadlines, exactly-once placement asserted in-phase) and
    # the controller itself well-behaved (reversal count bounded —
    # an oscillating loop would fail the row even at full goodput).
    # The convergence phases compare RATES, so they may run longer
    # than the hand-tuned phase — and must: adaptation takes a fixed
    # ~0.5 s (a handful of 50 ms ticks), which would dominate a
    # sub-second --quick phase and understate the converged rate.
    # Size each phase to >= ~3.5 s of hand-tuned throughput.
    import math as _math
    conv_submits = max(submits_per, int(_math.ceil(
        3.5 * part["plans_per_sec"] / n_submitters)))
    convergence: dict = {}
    for tag, knobs in (
            ("init_4x_small", {"max_window": 16,
                               "max_inflight_commits": 1,
                               "gather_s": 0.005}),
            ("init_4x_large", {"max_window": 256,
                               "max_inflight_commits": 8,
                               "gather_s": 0.08})):
        conv = _applier_saturation_phase(
            n_submitters, conv_submits, sequential=False,
            knobs=knobs, controller=True)
        ratio = conv["plans_per_sec"] / part["plans_per_sec"]
        assert ratio >= 0.9, (tag, conv["plans_per_sec"],
                              part["plans_per_sec"])
        assert conv["expired_drops"] == 0, (tag, conv)
        reversals = _controller_reversals(conv)
        assert reversals <= 12, (tag, conv["controller"])
        conv["initial_knobs"] = dict(knobs)
        conv["vs_hand_tuned"] = round(ratio, 3)
        convergence[tag] = conv
        note(f"config5f convergence {tag}: "
             f"{conv['plans_per_sec']:.0f} plans/s = {ratio:.0%} of "
             f"hand-tuned; knobs {_knob_moves(conv)}; "
             f"{reversals} reversals")

    row = dict(part)
    row["convergence"] = convergence
    row.update({
        "submitters": n_submitters,
        "max_window": 64,
        "sequential_baseline": seq,
        "speedup_serial_section": round(speed_serial, 2),
        "speedup_plans_per_sec": round(speed_plans, 2),
        "note": (f"{n_submitters} concurrent submitters through the "
                 "real leader commit pipeline (window-batched broker "
                 "token fence -> deadline-promoted plan-queue drain -> "
                 "claim-graph component partition -> concurrent "
                 "deadline-first component verify -> ONE raft apply "
                 "per window carrying columnar slab references -> FSM "
                 "batch decode -> batched store upsert); measured "
                 "against a same-run sequential-applier baseline over "
                 "an identical world pinned to the r10/r11 full-window "
                 "occupancy (the baseline still benefits from this "
                 "round's broker rework, so the speedup is "
                 "conservative); partitioned p99 < 500ms, serialized "
                 "section >= 2x cheaper per plan, plans/s held to a "
                 "no-regression floor, expired_drops == 0 with real "
                 "10s plan deadlines, exactly-once placement — all "
                 "asserted"),
    })
    note(f"config5f applier saturation: {n_submitters} submitters x "
         f"{submits_per} -> partitioned {part['plans_per_sec']:.0f} "
         f"plans/s via {part['commits_per_sec']:.0f} commits/s "
         f"(occupancy {part['batch_occupancy']:.1f}, "
         f"{part['components']} components, serial "
         f"{part['serial_ms_per_plan']:.3f}ms/plan), p50 "
         f"{part['p50_submit_ms']:.0f}ms / p99 "
         f"{part['p99_submit_ms']:.0f}ms vs sequential baseline "
         f"{seq['plans_per_sec']:.0f} plans/s via "
         f"{seq['commits_per_sec']:.0f} commits/s (occupancy "
         f"{seq['batch_occupancy']:.1f}, serial "
         f"{seq['serial_ms_per_plan']:.3f}ms/plan, p99 "
         f"{seq['p99_submit_ms']:.0f}ms) -> serial section x"
         f"{speed_serial:.2f}, plans/s x{speed_plans:.2f}, "
         f"expired_drops 0, {part['placed']} placed exactly-once")
    return row


def _verify_fleet_phase(n_nodes: int, policy: str, windows: int,
                        window_plans: int, seed: int) -> dict:
    """One 5f fleet-scaling cell: the window-verify section measured
    over a ``NodeSlab`` fleet of ``n_nodes`` under one verify policy
    (ops/verify_policy: "host" or "device"), same storm shape at every
    size.

    Each window is ``window_plans`` single-placement plans on distinct
    rng-sampled nodes — fixed shape, so the device path pads every
    window to ONE bucket and the measured loop never retraces.  Warm-up
    runs OUTSIDE the timed loop: the first window after a store's
    mirror build always punts on the device path (the residency-lease
    rule — a rebuild drops the twins, and the lease is lookup-only
    under the lock), so the device phase warms twice and then every
    measured window must genuinely dispatch (asserted).

    The timed loop runs with the post-setup heap FROZEN
    (``gc.freeze``): the fleet's columnar slab is static data, but
    CPython's generational collector re-scans its million-row columns
    on every collection, an O(fleet) per-window cost that has nothing
    to do with the verify path (measured: ~2x inflation at 1M nodes,
    gone under freeze).  Frozen-heap timing is the apples-to-apples
    basis for the flatness bar; the unfrozen number is a CPython
    artifact any long-lived server avoids the same way."""
    import gc
    import random

    from nomad_tpu.ops.plan_conflict import evaluate_window
    from nomad_tpu.ops.verify_policy import verify_override
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import (
        ALLOC_CLIENT_STATUS_PENDING,
        ALLOC_DESIRED_STATUS_RUN,
        Allocation,
        Plan,
    )

    store = StateStore()
    slab = mock.node_slab(n_nodes)
    store.upsert_node_slab(1, slab)
    node_ids = list(slab.ids)

    def alloc_on(nid: str) -> Allocation:
        return Allocation(
            id=generate_uuid(), node_id=nid, job_id="bench-5f-fleet",
            task_group="web",
            resources=Resources(cpu=100, memory_mb=64),
            desired_status=ALLOC_DESIRED_STATUS_RUN,
            client_status=ALLOC_CLIENT_STATUS_PENDING)

    # Standing usage on a slice of the fleet so the mirror's usage rows
    # are non-trivial (the verify reads them; an all-zero fleet would
    # understate the gather).
    rng = random.Random(seed)
    standing = [alloc_on(nid)
                for nid in rng.sample(node_ids, min(2048, n_nodes // 4))]
    store.upsert_allocs(2, standing)

    def mk_window() -> list:
        plans = []
        for nid in rng.sample(node_ids, window_plans):
            plan = Plan(eval_id=generate_uuid())
            plan.append_alloc(alloc_on(nid))
            plans.append(plan)
        return plans

    dispatched = 0
    h2d = d2h = 0
    with verify_override(policy):
        # Host: one warm window builds statics + mirror.  Device: the
        # first warm window rebuilds the mirror (dropping any twins),
        # the second re-warms them pre-lock and traces the kernel at
        # this fleet's n_pad and the storm's one bucket.
        for _ in range(2 if policy == "device" else 1):
            evaluate_window(store, mk_window())
        gc.collect()
        gc.freeze()
        try:
            t0 = time.perf_counter()
            for _ in range(windows):
                out = evaluate_window(store, mk_window())
                dev = (out.info or {}).get("device")
                if policy == "device":
                    assert dev is not None and dev["dispatched"], dev
                    dispatched += 1
                    h2d += dev["h2d"]
                    d2h += dev["d2h"]
            wall = time.perf_counter() - t0
        finally:
            gc.unfreeze()
    total = windows * window_plans
    return {
        "fleet_nodes": n_nodes,
        "policy": policy,
        "windows": windows,
        "window_plans": window_plans,
        "serial_ms_per_plan": round(wall * 1000.0 / total, 4),
        "verify_ms": round(wall * 1000.0 / windows, 3),
        "device_dispatches": dispatched,
        "h2d_per_window": round(h2d / windows, 1) if dispatched else 0.0,
        "d2h_per_window": round(d2h / windows, 1) if dispatched else 0.0,
    }


def bench_verify_fleet_scaling(sizes: list, windows: int,
                               window_plans: int, note) -> dict:
    """5f fleet-scaling sub-table (the device-verify headline, ISSUE
    17): the window-verify serialized section per plan across fleet
    sizes, device path vs the host twin measured same-run over the same
    storm shape.

    The claim under test: the device path's ``serial_ms_per_plan`` is
    FLAT in fleet size — verify cost scales with the WINDOW (claims,
    descriptors, one kernel dispatch against the mesh-resident twins),
    not the fleet.  Asserted in-bench: at every size beyond the first,
    device ``serial_ms_per_plan`` <= 1.5x its smallest-fleet value.
    The host twin rides the same storm for the record (its dense pass
    gathers by claim too, but its mirror scans scale with the fleet);
    no growth bar is asserted on it."""
    table: dict = {}
    for k, n in enumerate(sizes):
        host = _verify_fleet_phase(n, "host", windows, window_plans,
                                   seed=9000 + k)
        dev = _verify_fleet_phase(n, "device", windows, window_plans,
                                  seed=9000 + k)
        table[str(n)] = {"host": host, "device": dev}
        note(f"config5f fleet {n}: device "
             f"{dev['serial_ms_per_plan']:.3f}ms/plan "
             f"({dev['device_dispatches']}/{windows} windows dispatched, "
             f"d2h {dev['d2h_per_window']:.0f}/window) vs host "
             f"{host['serial_ms_per_plan']:.3f}ms/plan")
    base = table[str(sizes[0])]["device"]["serial_ms_per_plan"]
    for n in sizes[1:]:
        got = table[str(n)]["device"]["serial_ms_per_plan"]
        assert got <= 1.5 * base, (
            f"device verify not flat: {got}ms/plan at {n} nodes vs "
            f"{base}ms/plan at {sizes[0]}")
    return {
        "sizes": sizes,
        "flat_bar": 1.5,
        "table": table,
        "note": ("same storm shape per size (fixed window_plans x "
                 "windows, distinct sampled nodes, one device bucket); "
                 "device flatness asserted vs the smallest fleet; host "
                 "twin recorded same-run, no bar"),
    }


def bench_failover(kills: int, jobs_per_kill: int, note) -> dict:
    """Config 5e: rolling leader-kill failover on a durable 3-server
    NetRaft cluster (the crash-recovery headline).

    Each round starts a fresh 2-lane submission burst, then hard-kills
    the current leader mid-burst via faultinject.crash.CrashHarness
    (storage frozen + process shell abandoned — no graceful teardown of
    any kind), so every kill lands with client writes in flight.
    Measured per kill, from an independent probe writer issuing small
    raft writes continuously on its own conn pool: recovery (kill ->
    first client write committed by the new leader) and the full
    client-visible write-unavailability window (last pre-kill ack ->
    first post-kill ack); survivor election latency is recorded
    separately as observed from inside the cluster.  The killed
    node reboots from its own data_dir (snapshot threshold kept low so
    some rejoins ride InstallSnapshot, others log replay) and must
    catch up before the next round.  After the last round the cluster
    must converge to identical stores with exactly-once placement and
    ``committed_plan_loss == 0``: every client-acked write is present
    in the final state — asserted, not just recorded.
    """
    import shutil
    import socket
    import tempfile
    import threading

    from nomad_tpu.faultinject.crash import CrashHarness
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.server.rpc import ConnPool

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def wait_for(pred, timeout: float, what: str, tick: float = 0.002):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            v = pred()
            if v:
                return v
            time.sleep(tick)
        raise AssertionError(f"config5e: timed out waiting for {what}")

    def small_job():
        job = mock.job()
        job.constraints = []
        job.task_groups = [
            TaskGroup(name=f"tg-{g}", count=1,
                      tasks=[Task(name="web", driver="exec",
                                  resources=Resources(cpu=100,
                                                      memory_mb=32))])
            for g in range(2)]
        return job

    tmp = tempfile.mkdtemp(prefix="nomad-tpu-5e-")
    ports = [free_port() for _ in range(3)]
    peer_addrs = [("127.0.0.1", p) for p in ports]

    def cfg(i: int) -> ServerConfig:
        return ServerConfig(
            data_dir=os.path.join(tmp, f"s{i}"), raft_mode="net",
            rpc_port=ports[i], raft_peers=list(peer_addrs),
            num_schedulers=1,
            raft_election_timeout=(0.10, 0.20),
            raft_heartbeat_interval=0.03,
            raft_snapshot_threshold=64)

    servers = {i: Server(cfg(i)) for i in range(3)}
    alive = dict(servers)
    harness = CrashHarness()
    pool = ConnPool()
    stop = threading.Event()
    rr = [0]

    def addr_fn():
        targets = list(alive.values())
        rr[0] += 1
        return targets[rr[0] % len(targets)].rpc_address()

    def submit_retry(method: str, args: dict, deadline: float = 120.0,
                     timeout: float = 0.5):
        end = time.monotonic() + deadline
        while True:
            try:
                return pool.call(addr_fn(), method, args,
                                 timeout=timeout)
            except Exception:
                if stop.is_set() or time.monotonic() >= end:
                    raise
                time.sleep(0.01)

    def leader_of(timeout: float = 15.0):
        def one_leader():
            leaders = [s for s in alive.values() if s.raft.is_leader()]
            return leaders[0] if len(leaders) == 1 else None
        return wait_for(one_leader, timeout, "a single leader")

    # Independent probe writer: small idempotent raft writes (re-upsert
    # of one probe node) issued continuously through every kill.  The
    # gap between the last ack before a kill's first failure and the
    # first ack after it IS the client-visible unavailability window.
    # The probe rides its OWN ConnPool: shared mux conns would queue
    # its calls behind lane traffic and measure contention, not
    # availability.
    probe_node = mock.node(990)
    probe_pool = ConnPool()
    probe_log: list = []  # (t_start, t_end, ok)

    def probe() -> None:
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                probe_pool.call(addr_fn(), "Node.Register",
                                {"node": probe_node.to_dict()},
                                timeout=0.25)
                probe_log.append((t0, time.perf_counter(), True))
            except Exception:
                probe_log.append((t0, time.perf_counter(), False))
            time.sleep(0.004)

    jobs: list = []
    acked: dict = {}
    election_s: list = []
    recovery_s: list = []
    rejoin_s: list = []
    kill_times: list = []
    all_lanes: list = []
    try:
        leader_of()
        for i in range(8):
            submit_retry("Node.Register",
                         {"node": mock.node(i).to_dict()})
        prober = threading.Thread(target=probe, daemon=True,
                                  name="bench-5e-probe")
        prober.start()

        def lane(lane_jobs: list) -> None:
            for job in lane_jobs:
                if stop.is_set():
                    return
                resp = submit_retry("Job.Register",
                                    {"job": job.to_dict()})
                acked[job.id] = resp.get("index", 0)

        for kill in range(kills):
            # Fresh burst every round, kill while it is in flight.
            batch = [small_job() for _ in range(jobs_per_kill)]
            jobs.extend(batch)
            lanes = [threading.Thread(target=lane, args=(batch[i::2],),
                                      daemon=True,
                                      name=f"bench-5e-lane-{kill}-{i}")
                     for i in range(2)]
            all_lanes.extend(lanes)
            for t in lanes:
                t.start()

            leader = leader_of()
            victim = next(i for i, s in alive.items() if s is leader)
            t_kill = time.perf_counter()
            kill_times.append(t_kill)
            harness.kill(leader)
            del alive[victim]

            # Survivors elect among themselves: time kill -> a single
            # stable leader visible, BEFORE the canary write — the
            # canary blocks on commit + retry backoff and would
            # inflate the election number with commit latency.
            new_leader = leader_of()
            election_s.append(time.perf_counter() - t_kill)
            assert new_leader is not leader

            # The canary is a fresh committed write the reborn node
            # must catch up to; recovery latency itself is derived
            # from the probe writer's log after the run (the probe is
            # the uncontended client — the canary shares the lanes'
            # conn pool and would measure THEIR queueing).
            canary = mock.node(200 + kill)
            submit_retry("Node.Register", {"node": canary.to_dict()},
                         timeout=0.25)

            # The killed node reboots from its own disk and catches up
            # (log replay or InstallSnapshot) before the next round.
            t_boot = time.perf_counter()
            reborn = harness.reboot(cfg(victim))
            alive[victim] = reborn
            wait_for(lambda: reborn.fsm.state.node_by_id(canary.id)
                     is not None, 30.0, f"rejoin catch-up (kill {kill})")
            rejoin_s.append(time.perf_counter() - t_boot)

        for t in all_lanes:
            t.join(150.0)
        assert all(not t.is_alive() for t in all_lanes), "stuck lane"
        assert set(acked) == {j.id for j in jobs}, "lost submissions"

        # Quiesce the probe before the convergence checks: replicas
        # can only digest identically once writes stop arriving (the
        # last kill's post-kill acks landed long ago — the lanes'
        # post-kill submissions all committed before their join).
        stop.set()
        prober.join(5.0)

        leader = leader_of()
        state = leader.fsm.state

        def terminal() -> bool:
            for job in jobs:
                evals = state.evals_by_job(job.id)
                if not evals or any(e.status not in
                                    ("complete", "failed", "canceled")
                                    for e in evals):
                    return False
            return True
        wait_for(terminal, 90.0, "storm terminal after the kills",
                 tick=0.02)

        # committed_plan_loss: every client-acked write survived into
        # the final converged store.
        lost = [jid for jid in acked if state.job_by_id(jid) is None]
        if state.node_by_id(probe_node.id) is None and \
                any(ok for _, _, ok in probe_log):
            lost.append(probe_node.id)
        committed_plan_loss = len(lost)
        assert committed_plan_loss == 0, f"committed writes lost: {lost}"

        # Exactly-once placement: full coverage, zero duplicates.
        duplicate_allocs = 0
        placed = 0
        for job in jobs:
            expected = sum(tg.count for tg in job.task_groups)
            live = [a for a in state.allocs_by_job(job.id)
                    if not a.terminal_status()]
            names = [a.name for a in live]
            duplicate_allocs += len(names) - len(set(names))
            assert len(live) == expected, \
                f"job {job.id}: {len(live)} live allocs, want {expected}"
            placed += len(live)
        assert duplicate_allocs == 0

        # Replicas converge to the same tables (changelogs differ
        # legitimately across InstallSnapshot boundaries).
        wait_for(lambda: len({s.fsm.state.fingerprint(
            changelog_since=10**9) for s in alive.values()}) == 1,
            30.0, "replica convergence", tick=0.02)

        # Probe-log derived metrics, per kill: recovery = kill ->
        # first post-kill ack (the new leader committed a client
        # write); unavailability = last pre-kill ack -> first
        # post-kill ack (the full client-visible write gap).
        unavail_s: list = []
        for t_kill in kill_times:
            before = [t1 for _, t1, ok in probe_log
                      if ok and t1 <= t_kill]
            after = [t1 for _, t1, ok in probe_log
                     if ok and t1 > t_kill]
            if after:
                recovery_s.append(after[0] - t_kill)
                unavail_s.append(after[0] - (max(before) if before
                                             else t_kill))
        probe_ok = sum(1 for _, _, ok in probe_log if ok)
        probe_failed = len(probe_log) - probe_ok
        assert probe_ok > 0

        row = {
            "servers": 3,
            "kills": kills,
            "jobs": len(jobs),
            "placed": placed,
            "election_ms_p50": round(_p(election_s, 50), 1),
            "election_ms_p99": round(_p(election_s, 99), 1),
            "recovery_ms_p50": round(_p(recovery_s, 50), 1),
            "recovery_ms_p99": round(_p(recovery_s, 99), 1),
            "unavailability_ms_p50": round(_p(unavail_s, 50), 1),
            "unavailability_ms_p99": round(_p(unavail_s, 99), 1),
            "unavailability_ms_total":
                round(sum(unavail_s) * 1000.0, 1),
            "rejoin_catchup_ms_p50": round(_p(rejoin_s, 50), 1),
            "rejoin_catchup_ms_p99": round(_p(rejoin_s, 99), 1),
            "probe_writes_ok": probe_ok,
            "probe_writes_failed": probe_failed,
            "committed_plan_loss": committed_plan_loss,
            "duplicate_allocs": duplicate_allocs,
            "note": (f"{kills} rolling hard leader kills (CrashHarness: "
                     "storage frozen, no graceful teardown) on a "
                     "durable 3-server NetRaft cluster, each mid-"
                     "submission-burst; from an uncontended probe "
                     "writer: recovery = kill -> first client write "
                     "committed by the new leader, unavailability = "
                     "last pre-kill ack -> first post-kill ack; killed "
                     "node reboots from its own data_dir and catches "
                     "up (log replay or InstallSnapshot); "
                     "committed_plan_loss and duplicate allocs must "
                     "be ZERO"),
        }
        note(f"config5e failover: {kills} leader kills, election p50 "
             f"{_p(election_s, 50):.0f}ms / p99 "
             f"{_p(election_s, 99):.0f}ms, recovery (first new-leader "
             f"commit) p50 {_p(recovery_s, 50):.0f}ms / p99 "
             f"{_p(recovery_s, 99):.0f}ms, unavailability p50 "
             f"{_p(unavail_s, 50):.0f}ms / p99 "
             f"{_p(unavail_s, 99):.0f}ms, rejoin p99 "
             f"{_p(rejoin_s, 99):.0f}ms, {placed} placed exactly-once, "
             f"committed_plan_loss 0")
        return row
    finally:
        stop.set()
        pool.shutdown()
        probe_pool.shutdown()
        harness.reap(also=list(alive.values()))
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=10_000)
    ap.add_argument("--groups", type=int, default=1_000)
    ap.add_argument("--storm-jobs", type=int, default=64)
    # The spec'd storm shape (BASELINE.md config 5 at config-4 scale):
    # 64 concurrent evals x 1,000 task groups.
    ap.add_argument("--storm-groups", type=int, default=1_000)
    ap.add_argument("--stream-jobs", type=int, default=16)
    ap.add_argument("--agents", type=int, default=2000,
                    help="simulated heartbeating agents for config 5c")
    ap.add_argument("--swarm-agents", type=int, default=10_000,
                    help="simulated agents for the 5d client swarm")
    ap.add_argument("--swarm-window", type=float, default=15.0,
                    help="measured 5d swarm window in seconds")
    ap.add_argument("--overload-window", type=float, default=6.0,
                    help="seconds of 5x offered overload in config 5c")
    ap.add_argument("--failover-kills", type=int, default=6,
                    help="rolling leader kills in config 5e")
    ap.add_argument("--submitters", type=int, default=256,
                    help="concurrent submitter threads in config 5f")
    ap.add_argument("--submits-per", type=int, default=24,
                    help="plans each 5f submitter pushes")
    ap.add_argument("--fleet-nodes", type=int, default=131072,
                    help="node count for the sharded fleet storm "
                    "(config 6; >=100k so the unsharded footprint "
                    "exceeds one device's HBM)")
    ap.add_argument("--fleet-lanes", type=int, default=96,
                    help="eval lanes in the sharded fleet storm")
    ap.add_argument("--fleet-groups", type=int, default=2048,
                    help="distinct task groups per fleet-storm lane")
    ap.add_argument("--depth", type=int, default=6)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="256 nodes, 64 groups, 8-job storm smoke config")
    ap.add_argument("--profile-dir", default="",
                    help="write a jax.profiler trace of the storm here")
    args = ap.parse_args()

    if args.quick:
        args.nodes, args.groups = 256, 64
        args.storm_jobs, args.storm_groups = 8, 16
        args.stream_jobs = 4
        args.agents, args.overload_window = 200, 2.5

    # Before anything compiles: place the persistent compile cache, and
    # fail now — not minutes in, at the first roofline row — on a
    # device whose peaks are not published in DEVICE_PEAKS.
    from nomad_tpu.parallel.devices import configure_compile_cache
    configure_compile_cache()
    device_peaks()

    # Server-process GC tuning, applied identically to the device and
    # sequential paths (default thresholds cost both ~100-200ms pauses
    # per full collection over a 10k-node store).
    from nomad_tpu.utils.gctune import tune_gc
    tune_gc()

    class _RowDict(dict):
        """Every config row gains an embedded metrics snapshot stamped
        AT ITS capture time (ISSUE 10 satellite): one __setitem__ hook
        instead of eleven copy-pasted stamp lines."""

        def __setitem__(self, key, row):
            if isinstance(row, dict) and "metrics_snapshot" not in row:
                row["metrics_snapshot"] = _row_metrics()
            super().__setitem__(key, row)

    configs: dict = _RowDict()

    def note(line: str) -> None:
        print(f"# {line}", file=sys.stderr)

    # --- config 1: service job, 1 TG, 100 nodes --------------------------
    # Cheap evals: use a longer stream so the pipeline reaches steady
    # state and p99 reflects it.
    cheap_stream = args.stream_jobs if args.quick \
        else max(args.stream_jobs, 64)
    h1 = _harness_with_nodes(100)
    jobs1 = _config1_jobs(cheap_stream)
    for j in jobs1:
        h1.state.upsert_job(h1.next_index(), j)
    bench_pipelined_stream(h1, jobs1, depth=args.depth)  # warm caches
    dev_s, dev_lats, dev_placed = bench_pipelined_stream(
        h1, jobs1, depth=args.depth, repeats=3)
    seq_s, seq_lats, seq_placed = bench_sequential_stream(
        h1, jobs1, "service")
    assert dev_placed == seq_placed, (dev_placed, seq_placed)
    configs["1_service_100n"] = {
        "evals_per_sec": round(len(jobs1) / dev_s, 2),
        "seq_evals_per_sec": round(len(jobs1) / seq_s, 2),
        "speedup": round(seq_s / dev_s, 2),
        "p99_ms": round(_p(dev_lats, 99), 2),
        "seq_p99_ms": round(_p(seq_lats, 99), 2),
    }
    note(f"config1 service 100n: device {len(jobs1) / dev_s:.1f}/s "
         f"(p99 {_p(dev_lats, 99):.1f}ms) vs seq {len(jobs1) / seq_s:.1f}/s "
         f"-> {seq_s / dev_s:.1f}x")

    # --- config 2: constrained batch, 10 TGs, 1k nodes -------------------
    h2 = _harness_with_nodes(1_000)
    jobs2 = _config2_jobs(cheap_stream)
    for j in jobs2:
        h2.state.upsert_job(h2.next_index(), j)
    bench_pipelined_stream(h2, jobs2, depth=args.depth)  # warm caches
    dev_s, dev_lats, dev_placed = bench_pipelined_stream(
        h2, jobs2, depth=args.depth, repeats=3)
    seq_s, seq_lats, seq_placed = bench_sequential_stream(
        h2, jobs2, "batch")
    assert dev_placed == seq_placed, (dev_placed, seq_placed)
    configs["2_batch_constrained_1kn"] = {
        "evals_per_sec": round(len(jobs2) / dev_s, 2),
        "seq_evals_per_sec": round(len(jobs2) / seq_s, 2),
        "speedup": round(seq_s / dev_s, 2),
        "p99_ms": round(_p(dev_lats, 99), 2),
        "seq_p99_ms": round(_p(seq_lats, 99), 2),
    }
    note(f"config2 batch+distinct_hosts 1kn: device "
         f"{len(jobs2) / dev_s:.1f}/s (p99 {_p(dev_lats, 99):.1f}ms) vs "
         f"seq {len(jobs2) / seq_s:.1f}/s -> {seq_s / dev_s:.1f}x")

    # --- config 3: system job, 1k nodes ----------------------------------
    # Vectorized system scheduler (scheduler/system_vec.py: compiled
    # fleet-wide masks + vector fit, node-pinned so no argmax) vs the
    # sequential iterator chain ("system-seq").
    h3 = _harness_with_nodes(1_000)
    job3 = _config3_job()
    h3.state.upsert_job(h3.next_index(), job3)
    t3, placed3 = bench_single_eval(h3, job3, "system", args.repeats)
    t3_seq, placed3_seq = bench_single_eval(h3, job3, "system-seq",
                                            args.repeats)
    assert placed3 == placed3_seq, (placed3, placed3_seq)
    configs["3_system_1kn"] = {
        "evals_per_sec": round(1.0 / t3, 2),
        "seq_evals_per_sec": round(1.0 / t3_seq, 2),
        "speedup": round(t3_seq / t3, 2),
        "placed": placed3,
        "p99_ms": round(t3 * 1000.0, 2),
        "seq_p99_ms": round(t3_seq * 1000.0, 2),
    }
    note(f"config3 system 1kn: vectorized {t3 * 1000:.1f}ms/eval vs seq "
         f"{t3_seq * 1000:.1f}ms -> {t3_seq / t3:.1f}x "
         f"({placed3} nodes placed)")

    # --- config 4: 10k nodes x 1k TGs ------------------------------------
    h4 = _harness_with_nodes(args.nodes)
    jobs4 = [_bench_job(args.groups) for _ in range(args.stream_jobs)]
    for j in jobs4:
        h4.state.upsert_job(h4.next_index(), j)
    tune_gc()  # re-freeze the 10k-node store
    # Single-eval latency (latency-bound: one device round trip per eval).
    lat_dev, placed_dev = bench_single_eval(
        h4, jobs4[0], "jax-binpack", args.repeats)
    lat_seq, placed_seq = bench_single_eval(h4, jobs4[0], "service",
                                          args.repeats)
    assert placed_dev == placed_seq == args.groups, (placed_dev, placed_seq)
    # Recorded host-floor decomposition: per-stage wall of one host-
    # executor eval (scheduler/pipeline.py stage timers).  This profile
    # IS the `single_eval_ms` bar's baseline — the bar is the sum of
    # these stages, not a number picked in a vacuum.  Measured HERE,
    # adjacent to its object-contract twin below and BEFORE the stream
    # phase heats the shared host — same interleaving discipline as
    # the stream columns (load drift between measurement windows must
    # not skew a recorded A/B).  The profile is a min-statistic over a
    # ~2 ms eval, so extra repeats are near-free and cut the noise
    # floor.
    profile_reps = max(args.repeats, 6)
    stage_ms = single_eval_stage_profile(h4, jobs4[0], profile_reps)
    # Columnar-contract proof for the headline shape: the SAME eval
    # through the legacy object contract must place byte-identically
    # (the slab is a representation change, never a semantic one); the
    # recorded latency/finish delta is the contract's share of the
    # host floor.
    from nomad_tpu.structs import alloc_slab
    _columnar_was = alloc_slab.COLUMNAR
    alloc_slab.COLUMNAR = False
    try:
        lat_obj, placed_obj = bench_single_eval(
            h4, jobs4[0], "jax-binpack", args.repeats)
        stage_obj = single_eval_stage_profile(h4, jobs4[0], profile_reps)
    finally:
        alloc_slab.COLUMNAR = _columnar_was
    assert placed_obj == placed_dev, (placed_obj, placed_dev)
    # Stream throughput: the pipeline hides the round trip behind host
    # work, so evals/sec is bound by per-eval host time, not the RTT.
    # Device/sequential reps interleave so shared-host load drift can't
    # skew the ratio between the two measurement phases.
    bench_pipelined_stream(h4, jobs4, depth=args.depth)  # warm caches
    dev_s, dev_lats, _, seq_s, seq_lats, _ = bench_interleaved_stream(
        h4, jobs4, "service", depth=args.depth)
    # Hardware grounding (SURVEY §6): one device dispatch of this shape,
    # fenced by pulling the result back — the round trip the executor
    # policy (host numpy for single evals, device for the fused storm)
    # weighs per-eval compute against.
    kernel_s, est_bytes = device_kernel_stats(h4, jobs4[0])
    per_eval_s = dev_s / len(jobs4)
    # --- tracing A/B (ISSUE 10): the SAME stream with spans ON -----------
    # Asserted IN-bench: the always-on tracer must cost <= 5% of the
    # headline stream, or the observability plane is not "always-on".
    trace_off, trace_on, span_profile, spans_total = bench_traced_stream(
        h4, jobs4, args.depth, repeats=max(3, args.repeats))
    # Median-of-N, paired: the raw ratio can still dip fractionally
    # below zero inside the noise floor; the RECORDED overhead clamps
    # at 0 (a tracer cannot have negative cost) with the raw value
    # kept beside it, and the assertion bounds the recorded value —
    # non-negative by construction, <=5% or the bench fails.  The 5%
    # bar is defined on the canonical config-4 shape; --quick shrinks
    # evals to ~1 ms toys where the tracer's fixed per-span cost is
    # honestly ~10%, so the smoke config gets a proportionally looser
    # bar rather than a meaningless pass.
    tracing_bar = 0.25 if args.quick else 0.05
    tracing_overhead_raw = trace_on / trace_off - 1.0
    tracing_overhead = max(0.0, tracing_overhead_raw)
    assert tracing_overhead <= tracing_bar, (
        f"tracing-on config-4 stream is {tracing_overhead:.1%} slower "
        f"than tracing-off (> {tracing_bar:.0%}): {trace_on:.3f}s vs "
        f"{trace_off:.3f}s")
    # The trace really covered the whole scheduler lifecycle.
    assert {"begin", "dispatch", "collect", "finish", "submit"} <= \
        set(span_profile), span_profile
    configs["4_binpack_10kn_x_1ktg"] = {
        "evals_per_sec": round(len(jobs4) / dev_s, 3),
        "seq_evals_per_sec": round(len(jobs4) / seq_s, 3),
        "speedup": round(seq_s / dev_s, 2),
        "single_eval_ms": round(lat_dev * 1000.0, 1),
        "seq_single_eval_ms": round(lat_seq * 1000.0, 1),
        "single_eval_speedup": round(lat_seq / lat_dev, 2),
        "p99_ms": round(_p(dev_lats, 99), 2),
        "seq_p99_ms": round(_p(seq_lats, 99), 2),
        # Hardware terms: a single-eval device dispatch is bound by
        # its fenced round trip (deduped groups make its compute
        # tiny), so this config runs the HOST executor and its device
        # fraction is honestly 0 — the chip carries the pipelined
        # stream (4_device_pipelined below), the fused storm (config 5)
        # and multi-chip shapes.
        "device_dispatch_rtt_ms": round(kernel_s * 1000.0, 1),
        "approx_hbm_gb_per_eval": round(est_bytes / 1e9, 4),
        "host_executor": True,
        "device_fraction": 0.0,
        "stage_profile_ms": stage_ms,
        "columnar_contract": True,
        "placed": placed_dev,
        "single_eval_object_path_ms": round(lat_obj * 1000.0, 1),
        "object_stage_profile_ms": stage_obj,
        # Trace & telemetry plane (ISSUE 10): the same stream with the
        # span recorder ON, interleaved best-of-N vs OFF; the <=5% bar
        # is asserted above, the recorded number is the honest ratio
        # (negative = measurement noise, the two are within it).
        "tracing_on_evals_per_sec": round(len(jobs4) / trace_on, 3),
        "tracing_overhead_pct": round(tracing_overhead * 100.0, 2),
        "tracing_overhead_raw_pct": round(
            tracing_overhead_raw * 100.0, 2),
        "tracing_ab": "paired-interleaved, median-of-3 per side",
        "spans_per_eval": round(spans_total / len(jobs4), 1),
        # Stage rows re-derived from spans (vs the runner-timer
        # stage_profile_ms above): mean span ms per scheduler stage.
        "span_stage_profile_ms": span_profile,
        "bottleneck": ("per-eval host floor, measured per stage "
                       "(stage_profile_ms): finish = columnar native "
                       "finish (ports into the AllocSlab buffer + lazy "
                       "SlabAllocs, native/port_alloc.cpp "
                       "bulk_finish_cols), dispatch = host rounds "
                       "kernel, begin = memoized reconcile/prep, "
                       "submit = plan bookkeeping; re-evals pay ~0 "
                       "prep (memoized per job version x fleet "
                       "generation) and burst objects are GC-"
                       "untracked; single_eval_object_path_ms / "
                       "object_stage_profile_ms record the SAME eval "
                       "through the legacy object contract (placed "
                       "byte-identical, asserted) — the delta is the "
                       "object contract's share of the host floor; "
                       "the executor policy keeps this shape host-side "
                       "because its cost model puts one fenced device "
                       "round trip above the whole eval — the "
                       "4_device_pipelined row shows what the "
                       "forced-device pipeline does to the same "
                       "stream; the single_eval_ms bar is re-baselined "
                       "to this recorded profile (README Executor "
                       "policy)"),
    }
    note(f"config4 {args.nodes}n x {args.groups}tg: stream "
         f"{len(jobs4) / dev_s:.1f} evals/s vs seq "
         f"{len(jobs4) / seq_s:.1f}/s -> {seq_s / dev_s:.1f}x; "
         f"single-eval {lat_dev * 1000:.0f}ms vs {lat_seq * 1000:.0f}ms "
         f"-> {lat_seq / lat_dev:.1f}x; per-eval host stages (ms): "
         f"{stage_ms}")
    note(f"config4 tracing A/B (paired median-of-3): spans-on "
         f"{len(jobs4) / trace_on:.1f} evals/s vs off "
         f"{len(jobs4) / trace_off:.1f}/s -> "
         f"{tracing_overhead * 100.0:.1f}% recorded "
         f"(raw {tracing_overhead_raw * 100.0:+.1f}%, {spans_total} "
         f"spans, {spans_total / len(jobs4):.1f}/eval); span-derived "
         f"stages (ms): {span_profile}")
    note(f"config4 columnar contract: single-eval "
         f"{lat_dev * 1000:.1f}ms (finish {stage_ms.get('finish', 0)}"
         f"ms) vs object path {lat_obj * 1000:.1f}ms (finish "
         f"{stage_obj.get('finish', 0)}ms), placed byte-identical "
         f"({placed_dev})")
    note(f"config4 hardware: one fenced device dispatch of this shape "
         f"costs {kernel_s * 1000:.0f}ms (fenced round trip; est HBM "
         f"traffic only {est_bytes / 1e9:.3f}GB after group dedup) vs "
         f"{per_eval_s * 1000:.1f}ms/eval host wall -> the executor "
         f"policy keeps single evals host-side; the chip carries the "
         f"pipelined stream + fused storm")

    # --- config 4dp: the SAME stream, device executor FORCED -------------
    # Put the chip behind the headline or record why it can't be.
    # Depth is tuned to hide the measured round trip behind per-eval
    # host work (kernel_s / host-stage time, capped), so the stream is
    # bound by host stages, not the round trip.  Placed count must
    # equal the host row's — same plans, different engine.
    host_stage_s = max(sum(stage_ms.values()) / 1000.0, 1e-4)
    device_depth = max(args.depth,
                       min(64, int(kernel_s / host_stage_s) + 2))
    bench_pipelined_device_stream(h4, jobs4, device_depth, 1)  # warm
    (pdev_s, pdev_lats, pdev_placed, pdev_stages, dev_n, total_n,
     pdev_transfers) = bench_pipelined_device_stream(
        h4, jobs4, device_depth, args.repeats)
    host_placed = args.groups * len(jobs4)
    assert pdev_placed == host_placed, (pdev_placed, host_placed)
    assert dev_n == total_n == len(jobs4), (dev_n, total_n)
    # Device occupancy: total in-flight dispatch wall (each dispatch
    # holds the wire+chip for ~kernel_s) over stream wall.  The capped
    # value is comparable with config 5's kernel-wall/storm-wall
    # device_fraction; the UNCAPPED ratio is the informative one for an
    # overlapped stream — occupancy_x = 4.0 means four dispatch-RTTs
    # were in flight per unit wall, i.e. the pipeline genuinely
    # overlapped them (a non-pipelined forced-device stream pins it at
    # ~1.0).  device_dispatch_share is the executor-selection truth
    # (fraction of dispatches that actually ran on the chip).
    occupancy_x = len(jobs4) * kernel_s / pdev_s
    pdev_frac = min(1.0, occupancy_x)
    configs["4_device_pipelined"] = {
        "evals_per_sec": round(len(jobs4) / pdev_s, 3),
        "speedup": round(seq_s / pdev_s, 2),
        "vs_host_row": round(dev_s / pdev_s, 3),
        "p99_ms": round(_p(pdev_lats, 99), 2),
        "placed": pdev_placed,
        "depth": device_depth,
        "device_dispatches": dev_n,
        "device_dispatch_share": round(dev_n / max(1, total_n), 3),
        "device_fraction": round(pdev_frac, 3),
        "device_occupancy_x": round(occupancy_x, 2),
        # Transfer discipline (devlint / ISSUE 15): the final rep ran
        # under jax.transfer_guard("disallow") for h2d — completing it
        # IS the zero-implicit-transfer assertion on the hot path; the
        # counted EXPLICIT uploads per eval (usage view + job counts +
        # first-touch residency) are recorded beside it.
        "host_transfers_per_eval": round(pdev_transfers, 2),
        "implicit_transfers_hot_path": 0,
        "stage_times_ms": {k: round(v * 1000.0, 1)
                           for k, v in pdev_stages.items()},
        "note": ("same stream and plans as 4_binpack_10kn_x_1ktg with "
                 "NOMAD_TPU_EXECUTOR=device through the staged "
                 "pipeline: every dispatch runs on the chip "
                 "(device_dispatch_share), collect blocks overlap "
                 "later evals' prep/dispatch (device_occupancy_x > 1 "
                 "= dispatches genuinely overlapped); vs_host_row > 1 "
                 "means the device row WINS the stream, < 1 records "
                 "by how much the host executor still leads after "
                 "the RTT is hidden"),
    }
    note(f"config4dp device-pipelined (depth {device_depth}): "
         f"{len(jobs4) / pdev_s:.1f} evals/s vs host row "
         f"{len(jobs4) / dev_s:.1f}/s -> x{dev_s / pdev_s:.2f} "
         f"device/host, device_fraction {pdev_frac:.2f} "
         f"(occupancy x{occupancy_x:.1f}), "
         f"placed {pdev_placed} (== host row), p99 "
         f"{_p(pdev_lats, 99):.1f}ms; drain stages (ms): "
         f"{ {k: round(v * 1000.0, 1) for k, v in pdev_stages.items()} }")

    # --- config 4s: the SAME stream, node axis SHARDED -------------------
    # ISSUE 12 tentpole row: the config-4 stream through the staged
    # pipeline with the device executor forced and the node axis
    # sharded over the auto-resolved fleet mesh — capacity/reserved,
    # feasibility and the usage mirror all mesh-RESIDENT — against the
    # single-device twin (NOMAD_TPU_MESH=off), reps interleaved.
    # Every dispatch is asserted to have actually run sharded, and
    # placed must match the host row (same plans, sharded engine).
    (shs, sh_lats, sh_placed, sgs, sg_placed, sh_mesh, sh_n,
     sdev_n) = bench_sharded_stream(h4, jobs4, device_depth,
                                    args.repeats)
    assert sh_placed == sg_placed == host_placed, \
        (sh_placed, sg_placed, host_placed)
    a4 = _deferred_args(h4, jobs4[0])
    eval_footprint = _storm_footprint_bytes(
        1, a4.g_pad, a4.statics.n_pad, a4.k_cap, a4.rounds)
    fleet_ways = int(sh_mesh.shape["fleet"]) if sh_mesh is not None \
        else 1
    configs["4s_sharded_stream"] = {
        "evals_per_sec": round(len(jobs4) / shs, 3),
        "single_device_evals_per_sec": round(len(jobs4) / sgs, 3),
        "vs_single_device": round(sgs / shs, 3),
        "vs_host_row": round(dev_s / shs, 3),
        "p99_ms": round(_p(sh_lats, 99), 2),
        "placed": sh_placed,
        "sharded_dispatches": sh_n,
        "device_dispatches": sdev_n,
        "mesh_shape": {k: int(v) for k, v in sh_mesh.shape.items()}
        if sh_mesh is not None else None,
        "approx_hbm_gb_per_eval": round(eval_footprint / 1e9, 4),
        "approx_hbm_gb_per_shard": round(
            eval_footprint / max(1, fleet_ways) / 1e9, 4),
        "note": ("config-4 stream with first-class node-axis sharding "
                 "(parallel/mesh.dispatch_mesh auto-resolves; "
                 "mesh-resident capacity/reserved/feasibility/usage "
                 "under ONE residency policy): every device dispatch "
                 "asserted sharded, placements byte-identical to the "
                 "unsharded twin (tier-1 tests/test_parallel.py), "
                 "placed == host row asserted here; at 10k nodes the "
                 "per-shard HBM saving is a parity demo — the "
                 "6_sharded_fleet_storm row is where it becomes the "
                 "only way the workload fits"),
    }
    note(f"config4s sharded stream: {len(jobs4) / shs:.1f} evals/s "
         f"sharded over {dict(sh_mesh.shape) if sh_mesh else None} vs "
         f"{len(jobs4) / sgs:.1f}/s single-device "
         f"(x{sgs / shs:.2f}), {sh_n}/{sdev_n} dispatches sharded, "
         f"placed {sh_placed} (== host row), per-shard HBM "
         f"{eval_footprint / max(1, fleet_ways) / 1e9:.4f}GB of "
         f"{eval_footprint / 1e9:.4f}GB/eval")

    # --- config 5: optimistic eval storm (headline) ----------------------
    h5 = _harness_with_nodes(args.nodes)
    jobs5 = []
    for _ in range(args.storm_jobs):
        job = _bench_job(args.storm_groups)
        h5.state.upsert_job(h5.next_index(), job)
        jobs5.append(job)
    tune_gc()  # re-freeze the storm store
    bench_storm_device(h5, jobs5, 1)  # warm up device compile caches
    # Interleaved symmetric best-of-N (see bench_interleaved_stream); a
    # FRESH profiler trace brackets each device rep (jax.profiler.trace
    # is a one-shot context manager — re-entering one instance raises).
    storm_dev, storm_seq = float("inf"), float("inf")
    storm_lats: list = []
    for _ in range(args.repeats):
        trace = None
        if args.profile_dir:
            import jax
            trace = jax.profiler.trace(args.profile_dir)
            trace.__enter__()
        storm_dev = min(storm_dev, bench_storm_device(h5, jobs5, 1))
        if trace is not None:
            trace.__exit__(None, None, None)
        s_total, s_lats, _ = _sequential_rep(h5, jobs5, "service")
        if s_total < storm_seq:
            storm_seq, storm_lats = s_total, s_lats
    if args.profile_dir:
        note(f"profile trace written to {args.profile_dir}")
    storm_eps = args.storm_jobs / storm_dev
    storm_seq_eps = args.storm_jobs / storm_seq
    sk_s, sk_bytes = storm_kernel_stats(h5, jobs5[0], args.storm_jobs)
    # Device compute = fused-dispatch wall minus the RTT floor the
    # config-4 probe measured; the scan-structured kernel is LATENCY-
    # bound (tiny sequential steps), so achieved bandwidth sits far
    # below the HBM roofline — the win is batching 64 evals into one
    # dispatch, not saturating HBM.
    sk_compute = max(sk_s - kernel_s, 1e-4)
    sk_gbps = sk_bytes / sk_compute / 1e9
    hbm_gbps = device_peaks()["hbm_gbps"]
    configs["5_storm_64x"] = {
        "evals_per_sec": round(storm_eps, 2),
        "seq_evals_per_sec": round(storm_seq_eps, 2),
        "speedup": round(storm_eps / storm_seq_eps, 2),
        "storm_jobs": args.storm_jobs,
        "storm_groups": args.storm_groups,
        "seq_p99_ms": round(_p(storm_lats, 99), 2),
        # Hardware terms for the fused [B, G, N] dispatch.
        "kernel_wall_ms": round(sk_s * 1000.0, 1),
        "kernel_compute_ms": round(sk_compute * 1000.0, 1),
        "device_fraction": round(min(1.0, sk_s / storm_dev), 3),
        "approx_hbm_gb": round(sk_bytes / 1e9, 2),
        "achieved_hbm_gbps": round(sk_gbps, 1),
        "hbm_roofline_fraction": round(sk_gbps / hbm_gbps, 4),
        "roofline_note": ("scan-latency-bound, not bandwidth-bound: "
                          "the fused win is 64 evals per dispatch"),
    }
    note(f"config5 storm {args.storm_jobs} evals x {args.storm_groups}tg "
         f"on {args.nodes}n: device {storm_dev:.3f}s ({storm_eps:.1f}/s) "
         f"vs sequential {storm_seq:.3f}s ({storm_seq_eps:.1f}/s) -> "
         f"{storm_eps / storm_seq_eps:.1f}x; fused kernel wall "
         f"{sk_s * 1000:.0f}ms ({min(1.0, sk_s / storm_dev):.0%} of "
         f"storm wall), ~{sk_gbps:.1f} GB/s achieved of "
         f"~{hbm_gbps:.0f} nominal -> scan-latency-bound; "
         f"the fused win is batching, not bandwidth")

    # --- config 5b: contended storm WITH plan-apply conflicts ------------
    # BASELINE.md config 5 spells out "with plan_apply conflicts": a
    # tight fleet where the optimistic lanes' argmax picks collide, the
    # verifying applier partially rejects, and schedulers retry against
    # refreshed state.  Both sides run through the identical applier
    # (scheduler/harness.VerifyingPlanner) so the comparison includes
    # conflict-resolution cost, not just planning.
    from nomad_tpu.scheduler.batch import BatchEvalRunner
    from nomad_tpu.scheduler.harness import VerifyingPlanner

    cont_nodes = 160 if not args.quick else 24
    cont_groups = 100 if not args.quick else 8

    def _contended_setup():
        h = _harness_with_nodes(cont_nodes)
        jobs = []
        for _ in range(args.storm_jobs):
            job = _bench_job(cont_groups)
            h.state.upsert_job(h.next_index(), job)
            jobs.append(job)
        h.planner = VerifyingPlanner(h)
        return h, jobs

    def _placed_in_state(h):
        return len([a for a in h.state.allocs()
                    if a.node_id and not a.terminal_status()])

    # Warm compile caches on a throwaway copy, then best-of-N per side
    # with a FRESH state per rep (plans COMMIT here) and the reps
    # interleaved — same selection discipline as every other config, so
    # a single loaded host window can't misrepresent either side.
    hw, jw = _contended_setup()
    BatchEvalRunner(hw.state.snapshot(), hw.planner,
                    state_refresh=hw.snapshot).process(
        [make_eval(j) for j in jw])
    cont_dev = cont_seq = float("inf")
    dev_placed = dev_conflicts = seq_placed = 0
    dev_commits = dev_committed = dev_fallbacks = 0
    for _ in range(args.repeats):
        hc, jc5 = _contended_setup()
        t0 = time.perf_counter()
        BatchEvalRunner(hc.state.snapshot(), hc.planner,
                        state_refresh=hc.snapshot).process(
            [make_eval(j) for j in jc5])
        dt = time.perf_counter() - t0
        if dt < cont_dev:
            cont_dev = dt
            dev_placed = _placed_in_state(hc)
            dev_conflicts = hc.planner.conflicts
            dev_commits = hc.planner.commits
            dev_committed = hc.planner.committed_plans
            dev_fallbacks = hc.planner.conflict_fallbacks

        hs, js5 = _contended_setup()
        t0 = time.perf_counter()
        for job in js5:
            hs.process("service", make_eval(job))
        dt = time.perf_counter() - t0
        if dt < cont_seq:
            cont_seq = dt
            seq_placed = _placed_in_state(hs)
    # Same committed placement volume within rounding: contention near
    # capacity may shift a few placements between runs.
    assert abs(dev_placed - seq_placed) <= max(8, seq_placed // 50), (
        dev_placed, seq_placed)
    configs["5b_storm_contended"] = {
        "evals_per_sec": round(args.storm_jobs / cont_dev, 2),
        "seq_evals_per_sec": round(args.storm_jobs / cont_seq, 2),
        "speedup": round(cont_seq / cont_dev, 2),
        "nodes": cont_nodes, "storm_groups": cont_groups,
        "placed": dev_placed, "seq_placed": seq_placed,
        "plan_conflicts": dev_conflicts,
        # Group-commit window stats (ops/plan_conflict.py +
        # VerifyingPlanner.submit_plans): commits = serialized commit
        # operations the whole storm paid (vs one per plan before);
        # batch_occupancy = mean plans per commit; conflict_fallbacks =
        # window plans whose claims overlapped an earlier plan and took
        # the exact order-sensitive path.
        "commits": dev_commits,
        "commits_per_sec": round(dev_commits / cont_dev, 2),
        "batch_occupancy": round(dev_committed / max(1, dev_commits), 2),
        "conflict_fallbacks": dev_fallbacks,
    }
    note(f"config5b contended storm {args.storm_jobs} evals x "
         f"{cont_groups}tg on {cont_nodes}n through the verifying "
         f"applier: {cont_dev:.3f}s ({args.storm_jobs / cont_dev:.1f}/s, "
         f"{dev_conflicts} plan conflicts, {dev_placed} placed) vs "
         f"sequential {cont_seq:.3f}s ({args.storm_jobs / cont_seq:.1f}/s,"
         f" {seq_placed} placed) -> {cont_seq / cont_dev:.1f}x; "
         f"group commit: {dev_commits} commits "
         f"({dev_committed / max(1, dev_commits):.1f} plans/commit, "
         f"{dev_fallbacks} conflict fallbacks)")

    # --- config 6: sharded fleet storm at >=100k nodes --------------------
    # ISSUE 12 acceptance row: 2-D lanes x fleet storm on a columnar
    # NodeSlab fleet where the node axis MUST shard — the unsharded
    # resident footprint exceeds one device's HBM budget (asserted)
    # while the per-shard slice fits and the run completes.  Skipped
    # under --quick: the budget math needs the >=100k-node scale.
    if args.quick:
        note("config6 sharded fleet storm: skipped under --quick "
             "(needs >=100k nodes for the HBM-budget assertions)")
    else:
        configs["6_sharded_fleet_storm"] = bench_sharded_fleet_storm(
            args.fleet_nodes, args.fleet_lanes, args.fleet_groups,
            note=note)

    # --- config 5f: applier saturation (the group-commit headline) --------
    # Hundreds of concurrent submitters through the real leader commit
    # pipeline on the columnar alloc contract: commits/sec, window
    # occupancy, p99 submit->respond latency; exactly-once asserted.
    configs["5f_applier_saturation"] = bench_applier_saturation(
        32 if args.quick else args.submitters,
        8 if args.quick else args.submits_per, note=note)

    # --- 5f sub-table: device-verify fleet scaling (ISSUE 17) -------------
    # The window-verify serialized section per plan at 10k / 131k / 1M
    # NodeSlab fleets, same storm shape per size: the device path's
    # sharded base-fit + overlay-fold kernel must hold
    # serial_ms_per_plan FLAT in fleet size (<= 1.5x its smallest-fleet
    # value — asserted in _verify_fleet_phase's caller); the host twin
    # is measured same-run for the record.
    configs["5f_applier_saturation"]["fleet_scaling"] = \
        bench_verify_fleet_scaling(
            sizes=[2048, 8192, 32768] if args.quick
            else [10_000, 131_072, 1_000_000],
            windows=3 if args.quick else 8,
            window_plans=64, note=note)

    # --- config 5e: leader-kill failover (the durability headline) --------
    # Rolling hard leader kills on a durable 3-server NetRaft cluster,
    # each mid-submission-burst: recovery latency p50/p99, client-
    # visible unavailability window, committed_plan_loss == 0 asserted.
    # Runs BEFORE the 2k/10k-agent rows: election latency is timing-
    # sensitive and must not measure their teardown load.
    configs["5e_failover"] = bench_failover(
        kills=3 if args.quick else args.failover_kills,
        jobs_per_kill=2 if args.quick else 4, note=note)

    # --- config 5c: overload brownout (the robustness headline) ----------
    # A REAL server under 5x offered overload: admission sheds, TTL
    # wheel + paced reconciliation keep the fleet alive, and goodput
    # must hold >= 70% of unloaded capacity — the anti-metastable bar.
    configs["5c_overload_brownout"] = bench_overload_brownout(
        args.agents, args.overload_window,
        capacity_jobs=12 if args.quick else 48, note=note)

    # --- config 5d: client swarm (the serving-plane headline) ------------
    # >=10k agents through ONE event-driven server: parked long-polls,
    # full-fleet fan-out wakeups, O(pool) server threads, 0 false
    # expiries.
    configs["5d_client_swarm"] = bench_client_swarm(
        1000 if args.quick else args.swarm_agents,
        args.swarm_window, note=note)

    # Headline = the north-star metric BASELINE.md defines the 50x target
    # on: config 4 (10k nodes x 1k TGs) evals/sec vs the in-process
    # sequential bin-packer.  All five configs ride along in full.
    c4 = configs["4_binpack_10kn_x_1ktg"]
    result = {
        "metric": f"evals_per_sec_binpack_{args.nodes}n_x_{args.groups}tg",
        "value": c4["evals_per_sec"],
        "unit": "evals/s",
        "vs_baseline": c4["speedup"],
        "configs": configs,
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
