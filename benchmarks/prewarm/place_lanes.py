"""Set-up: compile (or load from the cache) the placement programs that a
window of this traffic can send to the device, at the fleet's padded
width, through the program's own entry points and with operands made as
its dispatch sites make them.

The generator's jobs give every group the same ask, so a lane's groups
dedupe to ONE kernel slot (``g_pad`` 8).  A fused window of B lanes pads
to the lane bucket ``pad_lanes(B)``; its ``k_cap`` is the widest lane's
padded copy count: the whole job on a first plan, any smaller number on
a re-plan of what a partial commit left.  Which lane counts leave the
numpy twin is the program's choice under its default policy, so it is
asked, not copied: every B from 1 to ``max_lanes``, and the lone lane of
a re-plan.  Parameters: ``max_lanes`` (the most lanes a window can hold:
the runner's batch, or the clients if fewer), ``rounds`` (the top-k round
counts to warm; more than one round takes a slot with more copies than
nodes with room).
"""
import numpy as np


def host_keeps(lanes: int, steps: int, n_real: int) -> bool:
    """Does the default policy keep ``lanes`` x ``steps`` x ``n_real``
    on the numpy twin?  The program's one comparison where it has one;
    before it had, the constant both its sites compared with."""
    from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler as Sched

    cost = lanes * steps * n_real
    if hasattr(Sched, "host_wins"):
        return Sched.host_wins(cost)
    return cost <= Sched.HOST_SINGLE_SHOT_COST


def prewarm(params: dict, n_nodes: int, traffic: dict) -> None:
    import jax

    from nomad_tpu.models import fleet
    from nomad_tpu.ops.binpack import place_rounds, place_rounds_batch
    from nomad_tpu.parallel.devices import default_device
    from nomad_tpu.scheduler.batch import pad_lanes

    def put(x):
        return jax.device_put(x, default_device())

    n_pad, g_pad, dims = fleet._pad_to(n_nodes), fleet._pad_to(1), fleet.NDIMS
    copies = int(traffic["job"]["groups"]) * int(traffic["job"]["count"])
    k_caps = sorted({min(fleet._pad_to(m), n_pad)
                     for m in range(1, copies + 1)})
    max_lanes = min(int(params.get("max_lanes", traffic["clients"])),
                    int(traffic["clients"]))
    buckets = sorted({pad_lanes(b) for b in range(1, max_lanes + 1)
                      if not host_keeps(b, g_pad, n_nodes)})
    fleet_f32 = [put(np.zeros((n_pad, dims), dtype=np.float32))
                 for _ in range(3)]
    for rounds in params.get("rounds", [1]):
        for k_cap in k_caps:
            for b in buckets:
                out = place_rounds_batch(
                    *fleet_f32,
                    put(np.zeros((b, n_pad), dtype=np.int32)),
                    put(np.zeros((b, g_pad, n_pad), dtype=bool)),
                    put(np.zeros((b, g_pad, dims), dtype=np.float32)),
                    put(np.zeros((b, g_pad), dtype=bool)),
                    put(np.zeros((b, g_pad), dtype=np.int32)),
                    put(np.zeros(b, dtype=np.float32)),
                    k_cap=k_cap, rounds=rounds)
                jax.block_until_ready(out)
            if not host_keeps(1, rounds, n_nodes):
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
