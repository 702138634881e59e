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
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Policy levers that would make a run something other than the default.
FORBIDDEN_ENV = ("NOMAD_TPU_EXECUTOR", "NOMAD_TPU_MESH", "NOMAD_TPU_VERIFY",
                 "NOMAD_TPU_FAULTS")

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


def program_node(fleet: dict, i: int, config: dict):
    """One fleet row as the program's ``Node`` (the reference's
    ``mock.go`` node: linux, exec driver, one NIC)."""
    from nomad_tpu.structs import NetworkResource, Node, Resources

    shape = config["node"]
    res = shape["reserved"]
    octet = (i % 250) + 1
    return Node(
        id=fleet["ids"][i], datacenter="dc1", name=f"node-{i}",
        attributes={"kernel.name": "linux", "arch": "x86",
                    "version": "0.1.0", "driver.exec": "1"},
        resources=Resources(
            cpu=shape["cpu"], memory_mb=shape["memory_mb"],
            disk_mb=shape["disk_mb"], iops=shape["iops"],
            networks=[NetworkResource(
                device="eth0", cidr=f"192.168.0.{octet}/32",
                mbits=shape["mbits"])]),
        reserved=Resources(
            cpu=res.get("cpu", 0), memory_mb=res.get("memory_mb", 0),
            disk_mb=res.get("disk_mb", 0), iops=res.get("iops", 0),
            networks=[NetworkResource(
                device="eth0", ip=f"192.168.0.{octet}",
                reserved_ports=list(res.get("ports", [])),
                mbits=res.get("mbits", 0))]),
        node_class="linux-medium", status="ready")


def program_job(spec: dict):
    """A plain job spec as the program's ``Job`` (one ``exec`` task per
    group, the reference's ``mock.go`` job constraint)."""
    from nomad_tpu.structs import (Constraint, Job, NetworkResource,
                                   Resources, Task, TaskGroup)

    groups = []
    for g in spec["groups"]:
        nets = []
        if g.get("mbits") or g.get("dynamic_ports"):
            nets = [NetworkResource(
                mbits=g.get("mbits", 0),
                dynamic_ports=list(g.get("dynamic_ports", ())))]
        groups.append(TaskGroup(
            name=g["name"], count=g["count"],
            tasks=[Task(name="web", driver="exec", resources=Resources(
                cpu=g["cpu"], memory_mb=g["memory_mb"],
                disk_mb=g.get("disk_mb", 0), iops=g.get("iops", 0),
                networks=nets))]))
    return Job(
        region="global", id=spec["id"], name=spec["name"],
        type=spec.get("type", "service"), priority=50,
        datacenters=["dc1"],
        constraints=[Constraint(hard=True, l_target="$attr.kernel.name",
                                r_target="linux", operand="=")],
        task_groups=groups)


class Served:
    """One server-only agent with a raft log on disk, the whole fleet
    registered over ``Node.Register`` and heartbeating."""

    def __init__(self, config: dict, fleet: dict, seed: int,
                 raft_dir: str, say) -> None:
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
            nodes = [program_node(fleet, i, config)
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
        except BaseException:
            self.shutdown()
            raise

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
