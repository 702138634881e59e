#!/usr/bin/env python3
"""chip_smoke.py — does the served scheduling path still run on the chip?

One process, one command, no arguments needed:

    python chip_smoke.py            # on a machine with a TPU
    python chip_smoke.py --rehearse # tiny size on the CPU, never a pass
    python chip_smoke.py --config benchmarks/configs/<c>.json \
        --traffic benchmarks/traffic/<t>.json   # a deployment's fleet and jobs

In order: builds the native finish extension from source (a child
process that never touches JAX) and places the compile cache; fails
unless JAX's first device is a TPU; runs the sequential scheduler over
the seeded fleet and jobs as the plain reference; drives the SERVED
path twice — a server-only agent with a raft data dir, 10,000 nodes
registered one by one through ``Node.Register`` and kept alive by
heartbeats, 16 service jobs of 1,000 placements each submitted over
HTTP ``PUT /v1/jobs``, allocations read back over HTTP — first under the
default executor policy (the host/device dispatch mix is REPORTED),
then under ``executor = "device"`` (every placement dispatch is
ASSERTED to have run on the chip); compiles and runs every jitted
kernel once at the smoke's shapes and compares it with its numpy twin;
and, when it sees more than one chip, checks the sharded family and
the mesh-resident twins.

With ``--config`` and ``--traffic`` (a benchmark configuration file
and an ``even_rate`` traffic file, read as data) the fleet is that
deployment's machines and the jobs are its job shapes, and the run is
the sequential reference and the ``executor = "device"`` phase alone:
the cost model sends such jobs to the numpy twin, so no benchmark run
shows them on the chip; this does.

Any failed check raises; nothing records an error and carries on.
Stdout is two lines, printed only when every check passed (progress
goes to stderr): the report — one JSON object with every fact above —
and, last, the verdict ``{"ok": ..., "device": {"platform", "kind",
"count"}}`` with the device as JAX reports it.  ``--seed`` makes all
data.
"""
from __future__ import annotations

import argparse
import glob
import importlib.metadata
import json
import os
import random
import shutil
import statistics
import subprocess
import sys
import time
import uuid

ROOT = os.path.dirname(os.path.abspath(__file__))

# Real size: BASELINE config 4's fleet (10,000 nodes; C1M ran 5,000
# hosts, a Borg cell ~12,500) and 16 jobs x 1,000 placements: 8 of
# config-4 shape, 6 with distinct asks, 2 of C1M shape; 64 lanes is the
# server's default fused batch.  The rehearsal only proves the command.
SIZES = {
    "real": {"nodes": 10_000, "placements": 1_000, "jobs": (8, 6, 2),
             "lanes": 64, "rtt_samples": 200},
    "rehearsal": {"nodes": 192, "placements": 24, "jobs": (2, 2, 1),
                  "lanes": 4, "rtt_samples": 20},
}

# Policy levers that would make the two phases something other than
# "the default" and "the operator's device setting".
FORBIDDEN_ENV = ("NOMAD_TPU_EXECUTOR", "NOMAD_TPU_MESH", "NOMAD_TPU_FAULTS")


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"# [{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# ---------------------------------------------------------------------------
# before JAX: native extension, compile cache
# ---------------------------------------------------------------------------

def build_native(t_start: float) -> dict:
    """Build _nomad_native from native/port_alloc.cpp in a child that
    never touches JAX, over whatever .so (or .build_failed marker) is
    on disk, and require the ABI this checkout's Python expects."""
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "native", "build.py")],
        capture_output=True, text=True, timeout=600)
    check(proc.returncode == 0,
          f"native extension did not build:\n{proc.stdout}\n{proc.stderr}")
    from nomad_tpu.utils.native import EXPECTED_ABI, HAS_NATIVE, native

    check(HAS_NATIVE, "native extension built but did not import")
    path = native.__file__
    check(os.path.dirname(os.path.abspath(path)) == ROOT,
          f"imported a native extension from outside the checkout: {path}")
    check(os.path.getmtime(path) >= t_start - 1.0,
          f"{path} is older than this run: not built from source now")
    check(native.ABI_VERSION == EXPECTED_ABI,
          f"native ABI {native.ABI_VERSION} != expected {EXPECTED_ABI}")
    return {"built_from_source": True, "abi": native.ABI_VERSION,
            "file": os.path.basename(path),
            "build_s": round(time.perf_counter() - t0, 2)}


def cache_entries(cache_dir: str) -> int:
    return len(glob.glob(os.path.join(cache_dir, "*")))


# ---------------------------------------------------------------------------
# seeded data
# ---------------------------------------------------------------------------

def seeded_uuid(rng: random.Random) -> str:
    return str(uuid.UUID(int=rng.getrandbits(128), version=4))


def make_fleet(seed: int, n: int) -> list:
    import nomad_tpu.mock as mock

    rng = random.Random(f"{seed}:fleet")
    fleet = []
    for i in range(n):
        node = mock.node(i)
        node.id = seeded_uuid(rng)
        fleet.append(node)
    return fleet


def mock_job(rng: random.Random, shape: str, k: int):
    import nomad_tpu.mock as mock

    job = mock.job()
    job.id = seeded_uuid(rng)
    job.name = f"smoke-{shape}-{k}"
    return job


def make_jobs(seed: int, size: dict) -> list:
    """[(shape, Job)]: config-4 shape (identical groups: cpu 100 / 64 MB
    / 5 Mbit / one dynamic port — they dedupe to ONE kernel slot),
    distinct asks (a prime-strided cpu/mem lattice from a seeded offset:
    every group keeps its own slot, so slot_step really runs once per
    group), C1M shape (one group, count = placements)."""
    from nomad_tpu.structs import NetworkResource, Resources, Task, TaskGroup

    rng = random.Random(f"{seed}:jobs")
    n_place = size["placements"]
    n_c4, n_distinct, n_c1m = size["jobs"]

    def web(res: Resources) -> list:
        return [Task(name="web", driver="exec", resources=res)]

    jobs = []
    for k in range(n_c4):
        job = mock_job(rng, "config4", k)
        job.task_groups = [TaskGroup(
            name=f"tg-{g}", count=1,
            tasks=web(Resources(cpu=100, memory_mb=64, networks=[
                NetworkResource(mbits=5, dynamic_ports=["http"])])))
            for g in range(n_place)]
        jobs.append(("config4", job))
    for k in range(n_distinct):
        job = mock_job(rng, "distinct", k)
        off = rng.randrange(997 * 499)
        job.task_groups = [TaskGroup(
            name=f"tg-{g}", count=1,
            tasks=web(Resources(cpu=20 + ((g + off) % 997),
                                memory_mb=32 + ((g + off) % 499))))
            for g in range(n_place)]
        jobs.append(("distinct", job))
    for k in range(n_c1m):
        job = mock_job(rng, "c1m", k)
        job.task_groups = [TaskGroup(
            name="web", count=n_place,
            tasks=web(Resources(cpu=250, memory_mb=128, networks=[
                NetworkResource(mbits=10, dynamic_ports=["http"])])))]
        jobs.append(("c1m", job))
    return jobs


def deployment(config: dict, traffic: dict, seed: int, size: dict) -> tuple:
    """(fleet, jobs) of a benchmark configuration (``nodes``, ``node``:
    one machine shape) and an ``even_rate`` traffic file (``job``: type,
    groups_cycle, count, one ask without network), at most
    ``size["nodes"]`` nodes and ``size["placements"]`` copies a group."""
    from nomad_tpu.structs import Resources, Task, TaskGroup

    shape, spec = config["node"], traffic["job"]
    fleet = make_fleet(seed, min(int(config["nodes"]), size["nodes"]))
    for node in fleet:
        res = node.resources
        res.cpu, res.memory_mb = shape["cpu"], shape["memory_mb"]
        res.disk_mb, res.iops = shape["disk_mb"], shape["iops"]
        res.networks[0].mbits = shape["mbits"]
        node.reserved = Resources()
    rng = random.Random(f"{seed}:jobs")
    count = min(int(spec["count"]), size["placements"])
    jobs = []
    for k in range(sum(size["jobs"])):
        job = mock_job(rng, "deployment", k)
        job.type = spec["type"]
        job.task_groups = [TaskGroup(
            name=f"t{g:02d}", count=count,
            tasks=[Task(name="web", driver="exec", resources=Resources(
                cpu=spec["ask"]["cpu"],
                memory_mb=spec["ask"]["memory_mb"]))])
            for g in range(spec["groups_cycle"][
                k % len(spec["groups_cycle"])])]
        jobs.append(("deployment", job))
    return fleet, jobs


def register_eval(job):
    from nomad_tpu.structs import (EVAL_TRIGGER_JOB_REGISTER, Evaluation,
                                   generate_uuid)

    return Evaluation(id=generate_uuid(), priority=job.priority,
                      type=job.type, job_id=job.id,
                      triggered_by=EVAL_TRIGGER_JOB_REGISTER)


def running(allocs: list) -> list:
    return [a for a in allocs if a.node_id and not a.terminal_status()]


# ---------------------------------------------------------------------------
# the plain reference: the sequential scheduler over the same data
# ---------------------------------------------------------------------------

def sequential_reference(fleet: list, jobs: list):
    """Harness + the sequential scheduler of each job's type over the
    same seeded fleet and jobs, one eval at a time.  Returns (harness,
    placed per job id); the harness — a real store now carrying 16
    jobs' usage — also feeds the kernel phase its fleet tensors."""
    from nomad_tpu.scheduler import Harness

    h = Harness()
    for node in fleet:
        h.state.upsert_node(h.next_index(), node.copy())
    placed = {}
    for _shape, job in jobs:
        h.state.upsert_job(h.next_index(), job.copy())
        h.process(job.type, register_eval(job))
        placed[job.id] = len(running(h.state.allocs_by_job(job.id)))
    return h, placed


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------

def resident_arrays(statics) -> list:
    """(label, jax.Array) for every fleet tensor the served path keeps
    resident on the device plane: single-buffer copies and mesh twins of
    capacity/reserved, feasibility rows and the usage mirror."""
    out = []
    hit = statics.device_cache.get("capres")
    if hit is not None:
        out += [("capacity", hit[0]), ("reserved", hit[1])]
    for key, entry in statics.device_cache.items():
        if key != "capres" and entry[1] is not None:
            out.append(("feasible", entry[1]))
    for key in statics.sharded.keys():
        for arr in statics.sharded.lookup(key):
            out.append((f"{key[0]}@mesh", arr))
    mirror = statics.mirror
    if mirror is not None:
        if mirror._usage_d is not None:
            out.append(("usage", mirror._usage_d))
        for key in mirror._sharded.keys():
            for arr in mirror._sharded.lookup(key):
                out.append((f"{key[0]}@mesh", arr))
    return out


def check_sharded_twins(twins: list) -> dict:
    """Every mesh twin has one addressable shard on EACH device of its
    mesh, each smaller than the whole (sharded, not parked on device 0
    or replicated)."""
    classes = {}
    for label, arr in twins:
        if not label.endswith("@mesh"):
            continue
        mesh_devs = set(arr.sharding.mesh.devices.flat)
        shards = arr.addressable_shards
        check({s.device for s in shards} == mesh_devs
              and len(shards) == len(mesh_devs),
              f"{label}: shards on {sorted(str(s.device) for s in shards)}"
              f" but mesh has {len(mesh_devs)} devices")
        check(all(s.data.size < arr.size for s in shards),
              f"{label}: a shard holds the whole array")
        classes[label] = classes.get(label, 0) + 1
    return classes


def served_phase(name: str, executor: str, fleet: list, jobs: list,
                 reference: dict, seed: int, out_dir: str,
                 platform: str) -> dict:
    """One server, the whole fleet, all jobs, through the entry points a
    user calls.  ``executor`` "" is the default config (dispatch mix
    reported); "device" asserts every placement dispatch ran on the
    device plane."""
    import jax

    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.agent.swarm import AgentSwarm
    from nomad_tpu.api import APIClient
    from nomad_tpu.models.fleet import fleet_cache
    from nomad_tpu.parallel.devices import transfer_counts
    from nomad_tpu.scheduler.breaker import GLOBAL_BREAKER
    from nomad_tpu.structs import allocs_fit

    asserted = executor == "device"
    raft_dir = os.path.join(out_dir, f"raft-{name}")
    shutil.rmtree(raft_dir, ignore_errors=True)
    n_dev = len(jax.devices())
    transfers0 = transfer_counts()
    breaker0 = GLOBAL_BREAKER.stats()
    t0 = time.perf_counter()
    agent = Agent(AgentConfig(
        server_enabled=True, http_port=0, rpc_port=0, serf_port=0,
        server_data_dir=raft_dir, executor=executor, log_level="WARNING"))
    swarm = None
    try:
        server = agent.server
        check(server.is_leader(), f"{name}: leadership not established")
        check(os.path.isdir(os.path.join(raft_dir, "raft")),
              f"{name}: no raft log on disk under {raft_dir}")

        # Nodes: one Node.Register RPC each, then heartbeats — the TTL
        # of the first registrations is ~10 s, far shorter than a phase.
        # They beat at half the TTL the server grants (capped at 60 s:
        # the rate-scaled TTL of a 10,000-node fleet is ~200 s).
        swarm = AgentSwarm(server.rpc_address(), len(fleet),
                           node_factory=lambda i: fleet[i],
                           beat_interval=60.0, long_polls=False, seed=seed)
        swarm.start(register_timeout=600.0)
        t_registered = time.perf_counter()
        host, port = agent.http.address
        api = APIClient(f"http://{host}:{port}")
        check(len(api.nodes_list()[0]) == len(fleet),
              f"{name}: GET /v1/nodes does not list the whole fleet")
        say(f"{name}: {len(fleet)} nodes registered in "
            f"{t_registered - t0:.1f}s")

        # Jobs over HTTP; wait for their evals over HTTP.
        eval_ids = [api.job_register(job)["eval_id"] for _s, job in jobs]
        deadline = time.monotonic() + 900.0
        statuses = {}
        for eid in eval_ids:
            while True:
                ev, _meta = api.eval_info(eid)
                if ev.terminal_status():
                    statuses[eid] = ev.status
                    break
                check(time.monotonic() < deadline,
                      f"{name}: eval {eid} not terminal after 900s")
                time.sleep(0.05)
        t_placed = time.perf_counter()
        check(all(s == "complete" for s in statuses.values()),
              f"{name}: evals not complete: {statuses}")

        # Correct: read back over HTTP, then the committed state as a
        # whole.  The API returns every allocation with its whole job
        # embedded, so the C1M-shaped jobs (one group) are read in full
        # (GET /v1/job/<id>/allocations) and the 1,000-group jobs by a
        # few allocations each (GET /v1/allocation/<id>).
        state = server.fsm.state
        asked = {job.id: sum(tg.count for tg in job.task_groups)
                 for _s, job in jobs}
        read_back = {"jobs_in_full": 0, "allocs_sampled": 0}
        sampled_shapes = set()
        for shape, job in jobs:
            in_state = {a.id: a for a in state.allocs_by_job(job.id)}
            if shape in ("c1m", "deployment"):
                got = running(api.job_allocations(job.id)[0])
                check(len(got) == asked[job.id],
                      f"{name}: HTTP shows {len(got)} allocs of job "
                      f"{job.name}, asked {asked[job.id]}")
                check({a.id for a in got} <= set(in_state),
                      f"{name}: HTTP allocs of {job.name} not in the store")
                read_back["jobs_in_full"] += 1
            elif shape not in sampled_shapes:
                sampled_shapes.add(shape)
                for aid in sorted(in_state)[:4]:
                    got, _meta = api.alloc_info(aid)
                    check(got.job_id == job.id
                          and got.node_id == in_state[aid].node_id
                          and got.desired_status == "run",
                          f"{name}: GET /v1/allocation/{aid} != the store")
                    read_back["allocs_sampled"] += 1
        committed = {job.id: len(running(state.allocs_by_job(job.id)))
                     for _s, job in jobs}
        check(committed == asked,
              f"{name}: committed != asked: "
              f"{ {j: (committed[j], asked[j]) for j in asked if committed[j] != asked[j]} }")
        check(committed == reference,
              f"{name}: the sequential scheduler placed {reference}")
        all_ids = [a.id for _s, job in jobs
                   for a in state.allocs_by_job(job.id)]
        check(len(all_ids) == len(set(all_ids)),
              f"{name}: duplicate alloc ids")
        nodes = state.nodes()
        check(len(nodes) == len(fleet) and
              all(n.status == "ready" for n in nodes),
              f"{name}: fleet not whole and ready at the end")
        used_nodes = 0
        for node in nodes:
            allocs = running(state.allocs_by_node(node.id))
            if not allocs:
                continue
            used_nodes += 1
            fit, dim, _used = allocs_fit(node, allocs)
            check(fit, f"{name}: node {node.name} oversubscribed: {dim}")

        # Witnesses, from the monitoring surface (GET /v1/agent/metrics).
        m = api.agent_metrics()["providers"]
        mix = {k: m[f"nomad.batch_runner.{k}"] for k in
               ("host_dispatches", "device_dispatches",
                "sharded_dispatches", "fused_batches")}
        transfers = {k: v - transfers0[k]
                     for k, v in transfer_counts().items()}
        breaker1 = GLOBAL_BREAKER.stats()
        breaker = {k: breaker1[k] - breaker0[k]
                   for k in ("failures", "opens")}
        applier = server.plan_applier.stats()
        check(breaker == {"failures": 0, "opens": 0},
              f"{name}: device breaker fired: {breaker}")
        check(applier["dispatch_failures"] == 0,
              f"{name}: applier dispatch_failures "
              f"{applier['dispatch_failures']}")
        check(m["nomad.broker.nacks"] == 0 and
              m["nomad.workers.dispatch_failures"] == 0,
              f"{name}: evals were redelivered (nacks "
              f"{m['nomad.broker.nacks']}, failed batches "
              f"{m['nomad.workers.dispatch_failures']})")
        check(m["nomad.heartbeat.expiries"] == 0,
              f"{name}: {m['nomad.heartbeat.expiries']} nodes expired")
        twins = resident_arrays(fleet_cache.statics_for(state))
        wrong = [label for label, arr in twins
                 if any(d.platform != platform for d in arr.devices())]
        check(not wrong, f"{name}: resident off {platform}: {wrong}")
        sharded_twins = {}
        if asserted:
            check(mix["host_dispatches"] == 0,
                  f"{name}: {mix['host_dispatches']} placement dispatches "
                  "ran on the numpy twin under executor=device")
            check(mix["device_dispatches"] >= mix["fused_batches"] >= 1,
                  f"{name}: dispatch mix {mix}")
            check(transfers["d2h"] > 0,
                  f"{name}: no device->host fetch was counted")
            check(any(l in ("capacity", "capres@mesh") for l, _a in twins),
                  f"{name}: no fleet tensor resident on the device")
            if n_dev > 1:
                check(mix["sharded_dispatches"] > 0,
                      f"{name}: {n_dev} devices, no sharded dispatch")
                sharded_twins = check_sharded_twins(twins)
                check(any(k.startswith("capres") for k in sharded_twins)
                      and any(k.startswith("usage") for k in sharded_twins),
                      f"{name}: mesh twins resident: {sharded_twins}")
        return {
            "executor": executor or "auto",
            "asserted_on_device": asserted,
            "nodes": len(nodes),
            "jobs": len(jobs),
            "placements_committed": sum(committed.values()),
            "nodes_used": used_nodes,
            "evals_complete": len(statuses),
            "read_back_over_http": read_back,
            "dispatch_mix": mix,
            "transfers": transfers,
            "breaker": breaker,
            "applier": {k: applier[k] for k in (
                "commits", "dispatch_failures")},
            "broker_nacks": m["nomad.broker.nacks"],
            "resident_arrays": sorted({l for l, _a in twins}),
            "sharded_twins": sharded_twins,
            "heartbeats_ok": swarm.stats()["beats_ok"],
            "register_s": round(t_registered - t0, 2),
            "place_s": round(t_placed - t_registered, 2),
            "wall_s": round(time.perf_counter() - t0, 2),
        }
    finally:
        if swarm is not None:
            swarm.stop()
        agent.shutdown()


# ---------------------------------------------------------------------------
# kernels, one by one
# ---------------------------------------------------------------------------

def prep_eval(h, job):
    """The real prep of one eval (reconcile + constraint masks + usage
    view), paused before its dispatch: the scheduler, with
    ``sched.deferred == (place, DeviceArgs)``."""
    from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler

    h.state.upsert_job(h.next_index(), job.copy())
    sched = JaxBinPackScheduler(h.state.snapshot(), h, batch=False)
    sched.eval = register_eval(job)
    sched.defer_device = True
    sched._begin()
    return sched


def shape_evals(h, jobs: list, tag: str) -> tuple:
    """A fresh config-4-shaped and a fresh distinct-asks eval, prepped
    against the harness's (used) fleet."""
    out = {}
    for shape, job in jobs:
        if shape in ("config4", "distinct") and shape not in out:
            twin = job.copy()
            twin.id = f"{job.id}-{tag}"
            out[shape] = prep_eval(h, twin)
    return out["config4"], out["distinct"]


def lane_stack(x, lanes: int):
    """One copy of ``x`` per storm lane."""
    import numpy as np

    x = np.asarray(x)
    return np.broadcast_to(x, (lanes,) + x.shape).copy()


def timed_kernel(call, platform: str) -> tuple:
    """(host outputs, timings): first call (trace + compile + run), a
    steady call, and a call after every in-memory executable was dropped
    (trace + persistent-cache lookup + run).  The outputs must live on
    ``platform`` devices before they are fetched."""
    import jax
    import numpy as np

    def fenced():
        t0 = time.perf_counter()
        outs = call()
        off = {d.platform for x in outs for d in x.devices()} - {platform}
        check(not off, f"kernel outputs live on {off}, not {platform}")
        return [np.asarray(x) for x in outs], time.perf_counter() - t0

    first, t_first = fenced()
    _steady, t_steady = fenced()
    jax.clear_caches()
    again, t_again = fenced()
    check(all(np.array_equal(a, b) for a, b in zip(first, again)),
          "kernel output changed after the executable was rebuilt")
    return first, {
        "first_call_s": round(t_first, 3),
        "steady_call_s": round(t_steady, 4),
        "rebuilt_call_s": round(t_again, 3),
        "cold_compile_s": round(t_first - t_steady, 3),
        "cached_compile_s": round(t_again - t_steady, 3),
    }


def twin_parity(chosen_d, scores_d, chosen_h, scores_h) -> dict:
    """Device kernel vs numpy twin, REPORTED: node-for-node equality,
    the number of differing choices, whether they are the same nodes in
    another order, and max |dscore| where both picked the same node.
    (What is CHECKED is the contract the system promises of the two
    engines — binpack_host.check_*_host along the device's trajectory —
    because a TPU rounds 10^x differently from numpy and near-tied
    nodes may legitimately swap.)"""
    import numpy as np

    same = (chosen_d == chosen_h) & (chosen_d >= 0)
    delta = np.abs(scores_d.astype(np.float64)
                   - scores_h.astype(np.float64))[same]
    return {"chosen_equal": bool(np.array_equal(chosen_d, chosen_h)),
            "chosen_mismatches": int((chosen_d != chosen_h).sum()),
            "same_nodes_other_order": bool(np.array_equal(
                np.sort(chosen_d, axis=-1), np.sort(chosen_h, axis=-1))),
            "placed": int((chosen_d >= 0).sum()),
            "max_abs_dscore_same_node":
                float(delta.max()) if same.any() else 0.0}


def kernel_phase(h, jobs: list, size: dict, seed: int,
                 platform: str) -> dict:
    """Every jitted entry, compiled and run once at the smoke's shapes
    on the default device, compared with its ops/binpack_host twin."""
    import jax
    import numpy as np

    from nomad_tpu.models import fleet as fleet_mod
    from nomad_tpu.ops import binpack, binpack_host
    from nomad_tpu.scheduler.pipeline import PROBE_SCORE_ATOL

    lanes = size["lanes"]
    a_c4, a_dist = (s.deferred[1] for s in shape_evals(h, jobs, "kernels"))
    statics = a_c4.statics
    n_real, n_pad = statics.n_real, statics.n_pad
    check(a_c4.rounds_eligible and a_dist.rounds_eligible,
          "smoke jobs must take the rounds path")
    k_cap = max(a_c4.k_cap, a_dist.k_cap)
    put = jax.device_put
    cap, res = put(statics.capacity), put(statics.reserved)
    usage, jc = put(a_c4.view.usage), put(a_c4.view.job_counts)
    usage_h = np.asarray(a_c4.view.usage)
    check(float(usage_h.sum()) > 0, "kernel phase wants a used fleet")
    out = {"shapes": {"n_real": n_real, "n_pad": n_pad,
                      "g_pad": [a_c4.g_pad, a_dist.g_pad],
                      "k_cap": k_cap, "lanes": lanes,
                      "p_pad": a_dist.p_pad}}
    kernels = out["kernels"] = {}

    def host_args(a, jc_h=None):
        return (statics.capacity, statics.reserved, usage_h,
                a.view.job_counts if jc_h is None else jc_h,
                a.feasible_h, a.asks, a.distinct)

    def record(label, call, host, agrees):
        """``agrees(chosen_d)``: does the host scorer rank every device
        pick best, within PROBE_SCORE_ATOL, along the device's own
        trajectory — the contract the breaker's probe holds the chip
        to (scheduler/pipeline.probe_agrees)?"""
        say(f"kernel {label}")
        (chosen_d, scores_d, *_rest), times = timed_kernel(call, platform)
        t0 = time.perf_counter()
        chosen_h, scores_h = host()
        times["host_twin_s"] = round(time.perf_counter() - t0, 3)
        check(agrees(chosen_d),
              f"{label}: the host scorer rejects the device's picks")
        kernels[label] = {**times, "host_scorer_agrees": True,
                          **twin_parity(chosen_d, scores_d,
                                        np.asarray(chosen_h),
                                        np.asarray(scores_h))}

    def seq_agrees(a, chosen, jc_h=None):
        return binpack_host.check_sequence_host(
            *host_args(a, jc_h), a.group_idx, a.valid,
            np.float32(a.penalty), chosen, atol=PROBE_SCORE_ATOL,
            n_real=n_real)

    def rounds_agrees(a, k, streams, jc_h=None):
        picks = {s: row[row >= 0] for s, row in enumerate(streams)}
        return binpack_host.check_rounds_host(
            *host_args(a, jc_h), a.counts, np.float32(a.penalty), picks,
            k_cap=k, rounds=a.rounds, atol=PROBE_SCORE_ATOL, n_real=n_real)

    def lanes_agree(chosen_b, lane_agrees):
        """The storm's lanes are two problems repeated: every lane must
        equal its first twin lane, and those two must pass."""
        return all(np.array_equal(chosen_b[b], chosen_b[b % 2])
                   for b in range(2, lanes)) and \
            all(lane_agrees(b, chosen_b[b]) for b in (0, 1))

    # place_sequence: one scan step per placement (distinct asks).
    dev = [put(x) for x in (a_dist.feasible_h, a_dist.asks, a_dist.distinct,
                            a_dist.group_idx, a_dist.valid)]
    pen = put(np.float32(a_dist.penalty))
    record("place_sequence",
           lambda: binpack.place_sequence(cap, res, usage, jc, *dev, pen),
           lambda: binpack_host.place_sequence_host(
               *host_args(a_dist), a_dist.group_idx, a_dist.valid,
               np.float32(a_dist.penalty), n_real=n_real)[:2],
           lambda chosen: seq_agrees(a_dist, chosen))

    # place_rounds at the three served shapes.
    for label, a, k in (("place_rounds[g8,k_cap]", a_c4, k_cap),
                        ("place_rounds[g_big,k8]", a_dist, a_dist.k_cap),
                        ("place_rounds[g_big,k_cap]", a_dist, k_cap)):
        dev = [put(x) for x in (a.feasible_h, a.asks, a.distinct, a.counts)]
        record(label,
               lambda dev=dev, a=a, k=k: binpack.place_rounds(
                   cap, res, usage, jc, *dev, put(np.float32(a.penalty)),
                   k_cap=k, rounds=a.rounds),
               lambda a=a, k=k: binpack_host.place_rounds_host(
                   *host_args(a), a.counts, np.float32(a.penalty),
                   k_cap=k, rounds=a.rounds, n_real=n_real)[:2],
               lambda chosen, a=a, k=k: rounds_agrees(a, k, chosen))

    # The fused storm: ``lanes`` evals per dispatch.  Odd lanes carry a
    # same-job alloc on every third node (the anti-affinity term then
    # moves their choices), so the lanes are two problems, not one; the
    # twin runs once per distinct lane.
    def stack(x):
        return lane_stack(x, lanes)

    def lane_job_counts(a):
        jc_b = stack(a.view.job_counts)
        jc_b[1::2, :n_real:3] += 1
        return jc_b

    def twin_lanes(run_lane):
        """The twin's (chosen, scores) for the storm: run the two
        distinct lanes, repeat them across the lane axis."""
        outs = [run_lane(b) for b in (0, 1)]
        return tuple(np.stack([outs[b % 2][i] for b in range(lanes)])
                     for i in (0, 1))

    for label, a, k in (("place_rounds_batch[g8,k_cap]", a_c4, k_cap),
                        ("place_rounds_batch[g_big,k_cap]", a_dist, k_cap)):
        jc_b = lane_job_counts(a)
        dev = [put(x) for x in (
            jc_b, stack(a.feasible_h), stack(a.asks), stack(a.distinct),
            stack(a.counts), np.full(lanes, a.penalty, dtype=np.float32))]

        def host(a=a, k=k, jc_b=jc_b):
            return twin_lanes(lambda b: binpack_host.place_rounds_host(
                *host_args(a, jc_b[b]), a.counts, np.float32(a.penalty),
                k_cap=k, rounds=a.rounds, n_real=n_real)[:2])

        record(label,
               lambda dev=dev, a=a, k=k: binpack.place_rounds_batch(
                   cap, res, usage, *dev, k_cap=k, rounds=a.rounds),
               host,
               lambda chosen_b, a=a, k=k, jc_b=jc_b: lanes_agree(
                   chosen_b, lambda b, c: rounds_agrees(a, k, c, jc_b[b])))

    a = a_dist
    jc_b = lane_job_counts(a)
    dev = [put(x) for x in (
        jc_b, stack(a.feasible_h), stack(a.asks), stack(a.distinct),
        stack(a.group_idx), stack(a.valid),
        np.full(lanes, a.penalty, dtype=np.float32))]

    def host_seq_batch():
        return twin_lanes(lambda b: binpack_host.place_sequence_host(
            *host_args(a, jc_b[b]), a.group_idx, a.valid,
            np.float32(a.penalty), n_real=n_real)[:2])

    record("place_sequence_batch",
           lambda: binpack.place_sequence_batch(cap, res, usage, *dev),
           host_seq_batch,
           lambda chosen_b: lanes_agree(
               chosen_b, lambda b, c: seq_agrees(a, c, jc_b[b])))

    # The usage mirror's row scatter.
    say("kernel scatter_rows")
    rng = random.Random(f"{seed}:scatter")
    idx = np.array(rng.sample(range(n_real), 48), dtype=np.int32)
    rows = usage_h[idx] + np.float32(3.0)
    (scattered,), times = timed_kernel(
        lambda: (fleet_mod._scatter_rows(usage, idx, rows),), platform)
    want = usage_h.copy()
    want[idx] = rows
    check(np.array_equal(scattered, want), "scatter_rows != numpy")
    kernels["scatter_rows"] = {**times, "equal_numpy": True}

    out["outputs_on_platform"] = platform
    return out


def dispatch_round_trip(samples: int) -> dict:
    """Median fenced round trip of a tiny kernel: enqueue + run +
    device->host copy (what every scheduler dispatch pays), and the
    same fenced by block_until_ready alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tiny = jax.jit(lambda x: x + 1)
    x = jax.device_put(jnp.zeros(8, dtype=jnp.int32))
    np.asarray(tiny(x))
    fetch, block = [], []
    for _ in range(samples):
        t0 = time.perf_counter()
        np.asarray(tiny(x))
        fetch.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        tiny(x).block_until_ready()
        block.append(time.perf_counter() - t0)
    return {"samples": samples,
            "median_fetch_fenced_ms": statistics.median(fetch) * 1e3,
            "median_block_fenced_ms": statistics.median(block) * 1e3}


# ---------------------------------------------------------------------------
# more than one chip
# ---------------------------------------------------------------------------

def multichip_phase(h, jobs: list, size: dict) -> dict:
    """Sharded == unsharded placements at the smoke's shape."""
    import jax
    import numpy as np

    from nomad_tpu.ops import binpack
    from nomad_tpu.parallel import mesh as mesh_mod

    n_dev = len(jax.devices())
    lanes = size["lanes"]
    out = {"n_devices": n_dev}
    evals = dict(zip(("config4", "distinct"),
                     shape_evals(h, jobs, "mesh")))
    args = {shape: s.deferred[1] for shape, s in evals.items()}
    statics = args["config4"].statics
    cap, res = statics.capacity, statics.reserved
    parity = out["sharded_vs_unsharded"] = {}

    def same(label, sharded, plain, tie_permuted=False):
        cs, cu = np.asarray(sharded[0]), np.asarray(plain[0])
        if tie_permuted:
            # A 2-D mesh halves the fleet shard width and top_k's tie
            # order is shard-dependent: equal-score winners may permute.
            ok = all(sorted(cs[b].ravel().tolist())
                     == sorted(cu[b].ravel().tolist())
                     for b in range(cs.shape[0]))
        else:
            ok = bool(np.array_equal(cs, cu))
        check(ok, f"{label}: sharded placements != unsharded")
        check(int((cu >= 0).sum()) > 0, f"{label}: nothing placed")
        parity[label] = {"equal": True, "placed": int((cu >= 0).sum())}

    mesh1 = mesh_mod.dispatch_mesh(1, statics.n_pad)
    check(mesh1 is not None and mesh1.size == n_dev,
          f"no {n_dev}-device fleet mesh resolved")
    for shape, sched in evals.items():
        # Through the scheduler's own single-eval dispatch, which keeps
        # capacity/reserved, this eval's feasibility rows and the usage
        # mirror resident on the mesh.
        say(f"sharded {shape}")
        a = args[shape]
        handles = sched.dispatch_device(a, force=True)
        check(sched.dispatched_sharded, f"{shape}: dispatch not sharded")
        same(f"place_rounds[{shape}]", handles,
             binpack.place_rounds(
                 cap, res, a.view.usage, a.view.job_counts, a.feasible_h,
                 a.asks, a.distinct, a.counts, np.float32(a.penalty),
                 k_cap=a.k_cap, rounds=a.rounds))
    twins = check_sharded_twins(resident_arrays(statics))
    check({k.split("@")[0] for k in twins} >= {"capres", "feas", "usage"},
          f"mesh twins resident after sharded dispatches: {twins}")
    out["sharded_twins"] = twins
    a = args["distinct"]
    common = (cap, res, a.view.usage, a.view.job_counts, a.feasible_h,
              a.asks, a.distinct)
    pen = np.float32(a.penalty)
    same("place_sequence",
         mesh_mod.place_sequence_sharded(mesh1, *common, a.group_idx,
                                         a.valid, pen),
         binpack.place_sequence(*common, a.group_idx, a.valid, pen))

    def stack(x):
        return lane_stack(x, lanes)

    mesh2 = mesh_mod.dispatch_mesh(lanes, statics.n_pad)
    two_d = mesh_mod.LANE_AXIS in mesh2.axis_names
    a = args["config4"]
    batch = (cap, res, a.view.usage, stack(a.view.job_counts),
             stack(a.feasible_h), stack(a.asks), stack(a.distinct))
    pen_b = np.full(lanes, a.penalty, dtype=np.float32)
    say("sharded storm")
    same("place_rounds_batch",
         mesh_mod.place_rounds_batch_sharded(
             mesh2, *batch, stack(a.counts), pen_b, k_cap=a.k_cap,
             rounds=a.rounds),
         binpack.place_rounds_batch(*batch, stack(a.counts), pen_b,
                                    k_cap=a.k_cap, rounds=a.rounds),
         tie_permuted=two_d)
    a = args["distinct"]
    batch = (cap, res, a.view.usage, stack(a.view.job_counts),
             stack(a.feasible_h), stack(a.asks), stack(a.distinct))
    same("place_sequence_batch",
         mesh_mod.place_sequence_batch_sharded(
             mesh2, *batch, stack(a.group_idx), stack(a.valid), pen_b),
         binpack.place_sequence_batch(*batch, stack(a.group_idx),
                                      stack(a.valid), pen_b),
         tie_permuted=two_d)
    out["storm_mesh"] = {k: int(v) for k, v in mesh2.shape.items()}
    return out


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21,
                    help="makes every node, job and window of the run")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on JAX_PLATFORMS=cpu, to debug the "
                    "command; reports ok=false by construction")
    ap.add_argument("--out", default=os.path.join(ROOT, ".chip_smoke"),
                    help="raft data dirs of the two served phases")
    ap.add_argument("--config", help="a benchmark configuration file: "
                    "its fleet, with --traffic")
    ap.add_argument("--traffic", help="an even_rate traffic file: its "
                    "job shapes, with --config")
    args = ap.parse_args()
    check(bool(args.config) == bool(args.traffic),
          "--config and --traffic go together")
    t_start = time.time()
    wall0 = time.perf_counter()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    set_levers = [v for v in FORBIDDEN_ENV if os.environ.get(v)]
    check(not set_levers,
          f"unset {set_levers}: the smoke runs the default policy, then "
          "executor=device from config")
    size = SIZES["rehearsal" if args.rehearse else "real"]
    os.makedirs(args.out, exist_ok=True)
    sys.path.insert(0, ROOT)

    result = {"ok": not args.rehearse, "rehearsal": args.rehearse,
              "seed": args.seed}
    result["native"] = build_native(t_start)
    say(f"native extension built: {result['native']}")

    from nomad_tpu.parallel.devices import configure_compile_cache
    cache_dir = configure_compile_cache()
    result["compile_cache"] = {
        "dir": cache_dir,
        "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "entries_at_start": cache_entries(cache_dir)}

    import jax
    import jaxlib

    devices = jax.devices()
    platform = devices[0].platform
    check(args.rehearse or platform == "tpu",
          f"no TPU: jax.devices()[0].platform is {platform!r}")
    result["device"] = {"platform": platform,
                        "kind": devices[0].device_kind,
                        "count": len(devices)}
    result.update(platform=platform, device_kind=devices[0].device_kind,
                  n_devices=len(devices))
    result["versions"] = {
        "python": sys.version.split()[0], "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "numpy": importlib.metadata.version("numpy")}
    say(f"device: {result['device']} versions: {result['versions']}")

    phases = (("phase_a", ""), ("phase_b", "device"))
    if args.config:
        with open(args.config) as fc, open(args.traffic) as ft:
            fleet, jobs = deployment(json.load(fc), json.load(ft),
                                     args.seed, size)
        result["deployment"] = [args.config, args.traffic]
        phases = phases[1:]
    else:
        fleet = make_fleet(args.seed, size["nodes"])
        jobs = make_jobs(args.seed, size)
    t0 = time.perf_counter()
    harness, reference = sequential_reference(fleet, jobs)
    result["reference"] = {
        "scheduler": f"{jobs[0][1].type} (sequential)", "jobs": len(jobs),
        "placements": sum(reference.values()),
        "wall_s": round(time.perf_counter() - t0, 2)}
    say(f"sequential reference: {result['reference']}")

    for name, executor in phases:
        result[name] = served_phase(name, executor, fleet, jobs, reference,
                                    args.seed, args.out, platform)
        say(f"{name}: {result[name]}")

    if not args.config:
        result["kernel_phase"] = kernel_phase(harness, jobs, size,
                                              args.seed, platform)
        result["dispatch_round_trip"] = dispatch_round_trip(
            size["rtt_samples"])
        if len(devices) > 1:
            result["multichip"] = multichip_phase(harness, jobs, size)
    result["compile_cache"]["entries_at_end"] = cache_entries(cache_dir)
    result["peak_bytes_in_use"] = {
        str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in devices}
    result["wall_s"] = round(time.perf_counter() - wall0, 1)
    print(json.dumps(result))
    print(json.dumps({"ok": result["ok"], "device": result["device"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
