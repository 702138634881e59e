"""``clients`` closed loops, every job a stack of several tiers: task
groups whose asks DIFFER, so a job's groups do not dedupe to one kernel
slot (``closed_loop`` and ``even_rate`` give every group of a job the
same ask).  What a cell varies is a parameter of its traffic file:

    clients         threads in the loop
    job.tiers       the job's task groups, in job order: {name, count,
                    cpu, memory_mb, mbits, dynamic_ports} of one copy
    job.groups      cap on the tiers of a job    (a rehearsal lowers
    job.count       cap on a tier's copies        these two)
    job_timeout_s, warmup: as ``closed_loop``

The loop itself is ``closed_loop``'s ``run``, line for line: a private
copy of that module is loaded from the file beside this one and handed
this file's ``job_spec`` (the module the harness loaded for other cells
is not touched).  Job k of client c is a function of (seed, c, k) alone;
every seed gives the same stack, under other ids.
"""
from __future__ import annotations

import importlib.util
import os
import random
import uuid


def job_spec(job: dict, seed: int, client: int, k: int) -> dict:
    """Job k of client c: the first ``groups`` tiers, each of at most
    ``count`` copies, in the traffic file's order."""
    rng = random.Random(f"{seed}:job:{client}:{k}")
    job_id = str(uuid.UUID(int=rng.getrandbits(128), version=4))
    cap = int(job["count"])
    groups = [dict(tier, count=min(int(tier["count"]), cap))
              for tier in job["tiers"][:int(job["groups"])]]
    return {"id": job_id, "name": f"stack-{client}-{k}", "type": "service",
            "groups": groups, "asked": sum(g["count"] for g in groups)}


def run(traffic: dict, seed: int, make_client, seconds: float,
        on_open, on_close, say) -> dict:
    spec = importlib.util.spec_from_file_location(
        "bench_closed_loop_of_stacks", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "closed_loop.py"))
    loop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loop)
    loop.job_spec = job_spec
    return loop.run(traffic, seed, make_client, seconds, on_open, on_close,
                    say)
