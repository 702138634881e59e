"""The system under test, behind the entry points a user calls.

Everything here that touches ``nomad_tpu`` is a COPY of what
``chip_smoke.py`` ran on the chip in PR 21 (server-only ``Agent`` with a
raft dir, ``AgentSwarm`` registration + heartbeats, ``APIClient`` job
register / eval read, counters from ``/v1/agent/metrics``): later PRs may
change ``chip_smoke.py``, they may not change the yardstick.  Plain job
and fleet specs (``reference.py``) go in; plain columns of the committed
allocations come out.
"""
from __future__ import annotations

import os
import random
import time

import numpy as np

import reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Policy levers that would make a run something other than the default.
FORBIDDEN_ENV = ("NOMAD_TPU_EXECUTOR", "NOMAD_TPU_MESH", "NOMAD_TPU_FAULTS")

COUNTERS = (
    "nomad.batch_runner.host_dispatches",
    "nomad.batch_runner.device_dispatches",
    "nomad.batch_runner.sharded_dispatches",
    "nomad.batch_runner.fused_batches",
    "nomad.broker.nacks",
    "nomad.workers.dispatch_failures",
    "nomad.heartbeat.expiries",
)


def ensure_native() -> dict:
    """The C++ finish extension.  The package's loader reuses the .so on
    disk when it is newer than its source and builds it otherwise
    (``native/build.py``: g++ in a child, ~2 s, no JAX); here the result
    must have loaded with the ABI this checkout expects — a run on the
    pure-Python fallback would measure another program."""
    t0 = time.perf_counter()
    before = {f for f in os.listdir(ROOT) if f.startswith("_nomad_native")}
    from nomad_tpu.utils.native import EXPECTED_ABI, HAS_NATIVE, native

    if not HAS_NATIVE or native.ABI_VERSION != EXPECTED_ABI:
        raise RuntimeError("native extension missing or wrong ABI")
    return {"built": not before, "abi": native.ABI_VERSION,
            "seconds": time.perf_counter() - t0}


def node_ip(i: int) -> str:
    """The address of fleet row ``i``'s one NIC, ``eth0``."""
    return f"192.168.0.{(i % 250) + 1}"


def program_node(fleet: dict, i: int):
    """One fleet row as the program's ``Node``, from its row's class
    (``reference.node_classes``: without classes, the reference's
    ``mock.go`` node: linux, exec driver, dc1, linux-medium, one NIC)."""
    from nomad_tpu.structs import NetworkResource, Node, Resources

    cls = fleet["classes"][fleet["class_of"][i]]
    shape = cls["node"]
    res = shape["reserved"]
    ip = node_ip(i)
    return Node(
        id=fleet["ids"][i], datacenter=cls["datacenter"], name=f"node-{i}",
        attributes=dict(cls["attributes"]), meta=dict(cls["meta"]),
        resources=Resources(
            cpu=shape["cpu"], memory_mb=shape["memory_mb"],
            disk_mb=shape["disk_mb"], iops=shape["iops"],
            networks=[NetworkResource(
                device="eth0", cidr=f"{ip}/32",
                mbits=shape["mbits"])]),
        reserved=Resources(
            cpu=res.get("cpu", 0), memory_mb=res.get("memory_mb", 0),
            disk_mb=res.get("disk_mb", 0), iops=res.get("iops", 0),
            networks=[NetworkResource(
                device="eth0", ip=ip,
                reserved_ports=list(res.get("ports", [])),
                mbits=res.get("mbits", 0))]),
        node_class=cls["node_class"], status="ready")


def program_constraints(constraints, distinct_hosts: bool = False) -> list:
    """Plain constraints (all hard) as the program's ``Constraint``s, and
    ``distinct_hosts`` as the constraint ``jobspec/parse.py`` makes of it
    (operand ``distinct_hosts``, empty targets), last."""
    from nomad_tpu.structs import Constraint

    out = [Constraint(hard=True, l_target=c.get("l_target", ""),
                      r_target=c.get("r_target", ""), operand=c["operand"])
           for c in constraints]
    if distinct_hosts:
        out.append(Constraint(hard=True, l_target="", r_target="",
                              operand="distinct_hosts"))
    return out


def program_job(spec: dict):
    """A plain job spec as the program's ``Job`` (one ``exec`` task per
    group; a group's ``constraints`` / ``distinct_hosts`` on its
    ``TaskGroup``; the job's on the ``Job``, and without a
    ``constraints`` key the reference's ``mock.go`` job constraint;
    ``datacenters`` dc1 unless stated)."""
    from nomad_tpu.structs import (Job, NetworkResource, Resources, Task,
                                   TaskGroup)

    groups = []
    for g in spec["groups"]:
        nets = []
        if g.get("mbits") or g.get("dynamic_ports"):
            nets = [NetworkResource(
                mbits=g.get("mbits", 0),
                dynamic_ports=list(g.get("dynamic_ports", ())))]
        groups.append(TaskGroup(
            name=g["name"], count=g["count"],
            constraints=program_constraints(
                g.get("constraints", ()), g.get("distinct_hosts", False)),
            tasks=[Task(name="web", driver="exec", resources=Resources(
                cpu=g["cpu"], memory_mb=g["memory_mb"],
                disk_mb=g.get("disk_mb", 0), iops=g.get("iops", 0),
                networks=nets))]))
    return Job(
        region="global", id=spec["id"], name=spec["name"],
        type=spec.get("type", "service"),
        priority=spec.get("priority", 50),
        datacenters=list(reference.job_datacenters(spec)),
        constraints=program_constraints(spec.get(
            "constraints", reference.MOCK_JOB_CONSTRAINTS)),
        task_groups=groups)


# Allocations in one ALLOC_UPDATE_REQUEST of the resident load: an
# entry applies in well under a second, inside raft_apply's 30 s wait.
RESIDENT_CHUNK = 8192


class Served:
    """One server-only agent with a raft log on disk, the whole fleet
    registered over ``Node.Register`` and heartbeating, and the
    configuration's resident allocations (``reference.resident_layout``)
    written on it."""

    def __init__(self, config: dict, fleet: dict, seed: int,
                 raft_dir: str, say, resident: "dict | None" = None) -> None:
        from nomad_tpu.agent import Agent, AgentConfig
        from nomad_tpu.agent.swarm import AgentSwarm
        from nomad_tpu.api import APIClient

        self.fleet = fleet
        self.node_index = {nid: i for i, nid in enumerate(fleet["ids"])}
        self.swarm = None
        t0 = time.perf_counter()
        self.agent = Agent(AgentConfig(
            server_enabled=True, http_port=0, rpc_port=0, serf_port=0,
            server_data_dir=raft_dir, executor="", log_level="WARNING"))
        try:
            server = self.agent.server
            if not server.is_leader():
                raise RuntimeError("leadership not established")
            if not os.path.isdir(os.path.join(raft_dir, "raft")):
                raise RuntimeError(f"no raft log on disk under {raft_dir}")
            nodes = [program_node(fleet, i)
                     for i in range(len(fleet["ids"]))]
            self.swarm = AgentSwarm(
                server.rpc_address(), len(nodes),
                node_factory=lambda i: nodes[i],
                beat_interval=float(config["heartbeat_interval_s"]),
                long_polls=False, seed=seed & 0x7FFFFFFF)
            self.swarm.start(register_timeout=600.0)
            host, port = self.agent.http.address
            self.address = f"http://{host}:{port}"
            self.api = APIClient(self.address)
            listed = len(self.api.nodes_list()[0])
            if listed != len(nodes):
                raise RuntimeError(f"GET /v1/nodes lists {listed} of "
                                   f"{len(nodes)} nodes")
            say(f"agent up, {len(nodes)} nodes registered in "
                f"{time.perf_counter() - t0:.1f}s")
            if resident is not None and resident["jobs"]:
                self.load_resident(resident, seed, say)
        except BaseException:
            self.shutdown()
            raise

    def load_resident(self, layout: dict, seed: int, say) -> None:
        """Write the resident allocations as a server replays them from
        its log: one ``JOB_REGISTER_REQUEST`` a job (its group's count is
        its allocations, so no evaluation is owed and none is made), then
        ``ALLOC_UPDATE_REQUEST`` entries of ``encode_alloc_update``
        payloads, ``RESIDENT_CHUNK`` allocations each, one columnar slab
        an entry: what a plan commit writes, without the scheduler."""
        from nomad_tpu.scheduler.jax_binpack import build_slots_c
        from nomad_tpu.structs import Resources, codec
        from nomad_tpu.structs.alloc_slab import (AllocSlab,
                                                  encode_alloc_update)

        server = self.agent.server
        state = server.fsm.state
        ids = self.fleet["ids"]
        rng = random.Random(f"{seed}:resident-evals")
        t0 = time.perf_counter()
        entries = lo = 0
        for spec in layout["jobs"]:
            job = program_job(spec)
            errors = job.validate()
            if errors:
                raise ValueError(f"resident {spec['id']}: {errors}")
            server.raft_apply(codec.JOB_REGISTER_REQUEST,
                              {"job": job.to_dict()})
            entries += 1
            job = state.job_by_id(spec["id"])
            group = job.task_groups[0]
            task = group.tasks[0]
            res = task.resources
            size = Resources(cpu=res.cpu, memory_mb=res.memory_mb,
                             disk_mb=res.disk_mb, iops=res.iops,
                             networks=list(res.networks))
            slots = build_slots_c([(size, [
                (task.name, res, res.networks[0] if res.networks
                 else None)])])
            eval_id = reference.seeded_uuid(rng)
            end = lo + group.count
            for a in range(lo, end, RESIDENT_CHUNK):
                b = min(end, a + RESIDENT_CHUNK)
                k = b - a
                ports = layout["ports"][a:b]
                port_off = np.zeros(k + 1, dtype=np.int64)
                np.cumsum([len(p) for p in ports], out=port_off[1:])
                slab = AllocSlab(
                    eval_id=eval_id, job=job, slots=slots,
                    metric_proto={"nodes_evaluated": 0,
                                  "allocation_time": 0.0},
                    groups=[0] * k, ids=layout["id"][a:b],
                    names=layout["name"][a:b], tgs=[group.name] * k,
                    scores=[0.0] * k, port_off=port_off, n_rows=k,
                    ports=np.asarray([p for row in ports for p in row],
                                     dtype=np.int32))
                rows = layout["row"][a:b].tolist()
                slab.node_ids = [ids[r] for r in rows]
                slab.ips = [node_ip(r) for r in rows]
                slab.devs = ["eth0"] * k
                slab.seal(k)
                server.raft_apply(codec.ALLOC_UPDATE_REQUEST,
                                  encode_alloc_update(
                                      [slab.alloc(r) for r in range(k)]))
                entries += 1
            lo = end
        say(f"resident: {lo} allocations of {len(layout['jobs'])} jobs "
            f"loaded in {time.perf_counter() - t0:.1f}s ({entries} "
            "entries)")

    # -- the two calls of a client -----------------------------------------
    def client(self, wait_time_s: float):
        """(submit, wait_done) for one client thread, each with its own
        ``APIClient``.  The wait is a blocking query on the evaluation
        (``?index=<last seen>&wait=<wait_time_s>``), never a poll."""
        from nomad_tpu.api import APIClient
        from nomad_tpu.api.client import QueryOptions

        api = APIClient(self.address)

        def submit(spec: dict) -> str:
            return api.job_register(program_job(spec))["eval_id"]

        def wait_done(eval_id: str, deadline: float) -> str:
            opts = None
            while True:
                ev, meta = api.eval_info(eval_id, opts)
                if ev.terminal_status():
                    return ev.status
                if time.monotonic() > deadline:
                    return "timeout"
                opts = QueryOptions(wait_index=meta.last_index,
                                    wait_time=wait_time_s)

        return submit, wait_done

    # -- counters, spans -----------------------------------------------------
    def counters(self) -> dict:
        m = self.api.agent_metrics()["providers"]
        return {k: m[k] for k in COUNTERS}

    # -- answers -------------------------------------------------------------
    def read_back(self, jobs: dict) -> dict:
        """Every committed allocation in the store as plain columns, and
        per job its registration index and its evaluations' status."""
        state = self.agent.server.fsm.state
        rows = [a for a in state.allocs()]
        n = len(rows)
        out = {
            "id": [a.id for a in rows],
            "name": [a.name for a in rows],
            "job": [a.job_id for a in rows],
            "eval": [a.eval_id for a in rows],
            "group": [a.task_group for a in rows],
            "node": np.asarray([self.node_index.get(a.node_id, -1)
                                for a in rows], dtype=np.int64),
            "vec": np.zeros((n, 6), dtype=np.float64),
            "create_index": np.asarray([a.create_index for a in rows],
                                       dtype=np.int64),
            "score": np.full(n, np.nan, dtype=np.float64),
            "running": np.asarray(
                [bool(a.node_id) and not a.terminal_status()
                 for a in rows], dtype=bool),
            "ports": [],
        }
        for i, a in enumerate(rows):
            out["vec"][i] = a.resources.as_vector()
            ports = []
            for res in a.task_resources.values():
                for net in res.networks:
                    ports.extend(net.reserved_ports)
            out["ports"].append(ports)
            scores = a.metrics.scores if a.metrics is not None else {}
            if len(scores) == 1:
                out["score"][i] = next(iter(scores.values()))
        job_info = {}
        for jid in jobs:
            job = state.job_by_id(jid)
            evals = state.evals_by_job(jid)
            job_info[jid] = {
                "register_index": job.create_index if job else -1,
                "evals": [(e.id, e.status, e.modify_index) for e in evals],
            }
        ready = sum(1 for x in state.nodes() if x.status == "ready")
        return {"allocs": out, "jobs": job_info, "nodes_ready": ready}

    def http_alloc(self, alloc_id: str) -> tuple:
        """(job id, node index, ask vector) of one allocation over
        ``GET /v1/allocation/<id>``."""
        got, _meta = self.api.alloc_info(alloc_id)
        return (got.job_id, self.node_index.get(got.node_id, -1),
                [float(x) for x in got.resources.as_vector()],
                got.desired_status)

    def shutdown(self) -> None:
        if self.swarm is not None:
            self.swarm.stop()
            self.swarm = None
        if self.agent is not None:
            self.agent.shutdown()
            self.agent = None
