"""``clients`` closed loops of stacked jobs of a stated TYPE: the jobs
``closed_loop_stack`` makes (task groups whose asks or stanzas differ,
one tier a group), each registered as ``job.type`` (``batch`` or
``service``; ``closed_loop_stack`` always says ``service``).  What a
cell varies is a parameter of its traffic file: ``closed_loop_stack``'s
(``clients``, ``job.tiers``, ``job.constraints``, ``job.datacenters``,
``job.groups``, ``job.count``, ``job_timeout_s``, ``warmup``) and

    job.type        the job type every job is registered as

The loop is ``closed_loop``'s ``run``, line for line, and the job is
``closed_loop_stack``'s ``job_spec`` with the type set: private copies
of both modules are loaded from the files beside this one (the modules
the harness loaded for other cells are not touched).  Job k of client c
is a function of (seed, c, k) alone.
"""
from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _private(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}_of_typed_stacks", os.path.join(HERE, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_STACK = _private("closed_loop_stack")


def job_spec(job: dict, seed: int, client: int, k: int) -> dict:
    """``closed_loop_stack``'s job k of client c, of type ``job.type``."""
    return dict(_STACK.job_spec(job, seed, client, k), type=job["type"])


def run(traffic: dict, seed: int, make_client, seconds: float,
        on_open, on_close, say) -> dict:
    loop = _private("closed_loop")
    loop.job_spec = job_spec
    return loop.run(traffic, seed, make_client, seconds, on_open, on_close,
                    say)
