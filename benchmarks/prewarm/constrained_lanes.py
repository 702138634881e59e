"""Set-up for stacked jobs of up to sixteen task groups that do NOT
dedupe (a stanza, an ask or ``distinct_hosts`` apart): compile (or load
from the cache) the placement programs that a window of such lanes can
send to the device.

A lane carries one kernel slot a group, so its slot axis is
``_pad_to(slots)``: 16 for ten groups, where ``place_lanes`` and
``stack_lanes`` warm 8 only.  A re-plan of what a partial commit left
carries fewer slots, so every slot bucket from one slot up to the job's
is warmed, and a fused window's ``k_cap`` is the widest slot's padded
copy count, any from one copy up to the widest tier's.  Which lane
counts leave the numpy twin at a slot bucket is the program's choice
under its default policy, so it is asked (``place_lanes.host_keeps``,
the file beside this one), not copied: a bucket the twin always keeps
compiles nothing.  Parameters: ``place_lanes``'s (``max_lanes``,
``rounds``).
"""
import importlib.util
import os

import numpy as np


def _place_lanes():
    spec = importlib.util.spec_from_file_location(
        "bench_prewarm_place_lanes_of_constrained", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "place_lanes.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def programs(params: dict, n_nodes: int, traffic: dict) -> list:
    """[(entry, lanes, g_pad, k_cap, rounds)]: ``entry`` ``batch`` for a
    fused window of ``lanes`` (its bucket), ``lone`` for one eval's
    ``place_rounds``; every program a window of this traffic can meet
    on the device at ``n_nodes``."""
    from nomad_tpu.models import fleet
    from nomad_tpu.scheduler.batch import pad_lanes

    host_keeps = _place_lanes().host_keeps
    job = traffic["job"]
    tiers = job["tiers"][:int(job["groups"])]
    widest = min(max(int(t["count"]) for t in tiers), int(job["count"]))
    n_pad = fleet._pad_to(n_nodes)
    g_pads = sorted({fleet._pad_to(s) for s in range(1, len(tiers) + 1)})
    k_caps = sorted({min(fleet._pad_to(m), n_pad)
                     for m in range(1, widest + 1)})
    max_lanes = min(int(params.get("max_lanes", traffic["clients"])),
                    int(traffic["clients"]))
    out = []
    for rounds in params.get("rounds", [1]):
        for g_pad in g_pads:
            buckets = sorted({pad_lanes(b) for b in range(1, max_lanes + 1)
                              if not host_keeps(b, rounds * g_pad,
                                                n_nodes)})
            for k_cap in k_caps:
                out.extend(("batch", b, g_pad, k_cap, rounds)
                           for b in buckets)
                # A lone eval's estimate counts its REAL slots: the
                # bucket's widest.
                if not host_keeps(1, rounds * g_pad, n_nodes):
                    out.append(("lone", 1, g_pad, k_cap, rounds))
    return out


def prewarm(params: dict, n_nodes: int, traffic: dict) -> None:
    import jax

    from nomad_tpu.models import fleet
    from nomad_tpu.ops.binpack import place_rounds, place_rounds_batch
    from nomad_tpu.parallel.devices import default_device

    def put(x):
        return jax.device_put(x, default_device())

    n_pad, dims = fleet._pad_to(n_nodes), fleet.NDIMS
    fleet_f32 = [put(np.zeros((n_pad, dims), dtype=np.float32))
                 for _ in range(3)]
    for entry, b, g_pad, k_cap, rounds in programs(params, n_nodes,
                                                   traffic):
        if entry == "batch":
            out = place_rounds_batch(
                *fleet_f32,
                put(np.zeros((b, n_pad), dtype=np.int32)),
                put(np.zeros((b, g_pad, n_pad), dtype=bool)),
                put(np.zeros((b, g_pad, dims), dtype=np.float32)),
                put(np.zeros((b, g_pad), dtype=bool)),
                put(np.zeros((b, g_pad), dtype=np.int32)),
                put(np.zeros(b, dtype=np.float32)),
                k_cap=k_cap, rounds=rounds)
        else:
            out = place_rounds(
                *fleet_f32,
                put(np.zeros(n_pad, dtype=np.int32)),
                put(np.zeros((g_pad, n_pad), dtype=bool)),
                put(np.zeros((g_pad, dims), dtype=np.float32)),
                put(np.zeros(g_pad, dtype=bool)),
                put(np.zeros(g_pad, dtype=np.int32)),
                put(np.float32(0.0)),
                k_cap=k_cap, rounds=rounds)
        jax.block_until_ready(out)
