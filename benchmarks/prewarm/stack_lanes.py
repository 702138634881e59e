"""Set-up for jobs whose groups do NOT dedupe (``closed_loop_stack``):
compile (or load from the cache) the placement programs that a window
of such lanes can send to the device.

A lane carries one kernel slot a tier, and a fused window's ``k_cap`` is
the widest SLOT's padded copy count (the widest tier on a first plan,
any smaller number on a re-plan of what a partial commit left), not the
job's: ``place_lanes``, told of 3 groups x 10 copies, would warm a
``k_cap`` of 32 for 16 placements whose widest slot holds 10.  While
the tiers fit the slot bucket of one group (``g_pad`` 8) the programs
are those of a one-group job of the widest tier's count, so this hands
``place_lanes`` (the file beside this one) that job: which lane counts
leave the numpy twin is still asked of the program there, not copied.
More tiers than that bucket holds is an error, not a silent miss.
Parameters: ``place_lanes``'s (``max_lanes``, ``rounds``).
"""
import importlib.util
import os


def prewarm(params: dict, n_nodes: int, traffic: dict) -> None:
    from nomad_tpu.models import fleet

    job = traffic["job"]
    tiers = job["tiers"][:int(job["groups"])]
    if fleet._pad_to(len(tiers)) != fleet._pad_to(1):
        raise ValueError(f"{len(tiers)} tiers leave the slot bucket of one "
                         "group: this plug-in warms that bucket only")
    widest = min(max(int(t["count"]) for t in tiers), int(job["count"]))
    spec = importlib.util.spec_from_file_location(
        "bench_prewarm_place_lanes_of_stacks", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "place_lanes.py"))
    place_lanes = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(place_lanes)
    place_lanes.prewarm(params, n_nodes, dict(
        traffic, job={"groups": 1, "count": widest}))
