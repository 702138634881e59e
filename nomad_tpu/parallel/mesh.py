"""Device-mesh scaling for the scheduler: shard the node axis over ICI.

This is the structural cousin of sequence parallelism for a scheduler
workload (SURVEY.md section 5, "Long-context"): the problem dimension that
grows is the fleet (nodes x task groups), so the node axis of every fleet
tensor is sharded across a 1-D ``jax.sharding.Mesh``.  Per-shard work is the
elementwise fit/score math; the argmax winner is reduced across devices by
XLA-inserted collectives riding ICI — no hand-written NCCL/MPI, no host
round-trips (the reference scales this dimension with iterator laziness +
LimitIterator truncation, scheduler/stack.go:106-117; we scale it with
hardware).

Multi-slice/multi-host: the same jit runs under multi-host jax with a mesh
spanning slices; DCN carries only the (tiny) replicated ask/choice tensors,
ICI the sharded fleet math.
"""
from __future__ import annotations

import contextlib
import os

from functools import partial

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nomad_tpu.ops.binpack import _place_rounds, _place_sequence

FLEET_AXIS = "fleet"
LANE_AXIS = "lanes"

# -- mesh resolution: the ONE authority ------------------------------------
# Every dispatch that *could* shard the node axis asks dispatch_mesh();
# the answer is a property of the platform (device count) and the
# dispatch shape, overridable by NOMAD_TPU_MESH so a bench or operator
# can force the single-device twin ("off"/"0") or cap the device count
# (an integer) without editing code — the same lever shape as
# NOMAD_TPU_EXECUTOR (scheduler/executor.py).

ENV_VAR = "NOMAD_TPU_MESH"

_MESH_CACHE: dict = {}
# Process override installed by mesh_override(); a one-element holder so
# readers never see a torn update.
_OVERRIDE: list = [None]


def _mesh_policy():
    """Resolved policy: "off", "auto", or an int device cap."""
    value = _OVERRIDE[0]
    if value is None:
        value = os.environ.get(ENV_VAR, "auto")
    value = str(value).strip().lower() or "auto"
    if value in ("off", "none", "0"):
        return "off"
    if value.isdigit():
        return int(value)
    return "auto"


@contextlib.contextmanager
def mesh_override(value):
    """Temporarily force the mesh policy ("off", "auto", or a device
    count) — the tier-1 parity rigs compare sharded against
    single-device runs through this."""
    prior = _OVERRIDE[0]
    _OVERRIDE[0] = value
    try:
        yield
    finally:
        _OVERRIDE[0] = prior


def dispatch_mesh(n_lanes: int, n_pad: int):
    """Mesh for a dispatch of ``n_lanes`` evals over an ``n_pad``-wide
    (power-of-two padded) node axis, or None when one device (or the
    "off" policy, or a lane/device shape that cannot split) makes the
    plain jit the right call.

    Lane ways = largest power of two dividing n_lanes, capped at half
    the devices so the fleet axis keeps width; remaining devices shard
    the node axis, capped at n_pad so the sharding always divides it.
    ``n_lanes == 1`` therefore resolves a pure 1-D fleet mesh — the
    single-eval scheduler path — and multi-lane dispatches get the 2-D
    ``(lanes, fleet)`` storm layout when the shape splits.  Devices
    resolve through parallel/devices.default_platform_devices so the
    mesh always lives on the pinned platform."""
    policy = _mesh_policy()
    if policy == "off":
        return None
    from nomad_tpu.parallel.devices import default_platform_devices

    all_devices = default_platform_devices()
    n_dev = len(all_devices)
    if isinstance(policy, int):
        n_dev = min(n_dev, policy)
    if n_dev < 2:
        return None
    n = 1 << (n_dev.bit_length() - 1)  # power-of-two subset
    lane_ways = 1
    while lane_ways * 2 <= min(n // 2, n_lanes) and \
            n_lanes % (lane_ways * 2) == 0:
        lane_ways *= 2
    # Fleet ways must divide the padded node axis (both powers of two,
    # so <= suffices); tiny fleets on big hosts use fewer devices.
    n = min(n, lane_ways * max(1, n_pad))
    if n < 2:
        return None
    key = (all_devices[0].platform, n, lane_ways)
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        devices = all_devices[:n]
        mesh = storm_mesh(lane_ways, devices) if lane_ways > 1 \
            else fleet_mesh(devices)
        _MESH_CACHE[key] = mesh
    return mesh


def fleet_mesh(devices=None) -> Mesh:
    """1-D mesh over the default platform's (or the given) devices;
    axis name 'fleet'."""
    if devices is None:
        from nomad_tpu.parallel.devices import default_platform_devices
        devices = default_platform_devices()
    return Mesh(np.asarray(devices), (FLEET_AXIS,))


def storm_mesh(lane_ways: int, devices=None) -> Mesh:
    """2-D mesh ``(lanes, fleet)``: storm lanes data-parallel across one
    axis, the node axis sharded across the other.

    This is the scheduler's DP x "context-parallel" layout: each
    lane-axis slice holds a fleet replica serving B/lane_ways evals, so
    storm throughput scales with lane_ways while per-device fleet memory
    still shrinks by the fleet-axis factor.  With lane_ways=1 this is
    fleet_mesh semantics on a 2-D mesh."""
    if devices is None:
        from nomad_tpu.parallel.devices import default_platform_devices
        devices = default_platform_devices()
    n = len(devices)
    if lane_ways <= 0 or n % lane_ways:
        raise ValueError(
            f"lane_ways {lane_ways} must divide device count {n}")
    grid = np.asarray(devices).reshape(lane_ways, n // lane_ways)
    return Mesh(grid, (LANE_AXIS, FLEET_AXIS))


def _put(x, sharding):
    """device_put that skips arrays already resident with the target
    sharding — the seam that lets mesh-resident fleet tensors (the
    sharded usage mirror, cached capacity/reserved) flow into the
    sharded kernels without a per-dispatch upload.  Placements that DO
    happen are explicit and counted (parallel/devices transfer
    odometer): the sharded kernels below route every operand through
    here, so a sharded dispatch performs zero implicit transfers."""
    if getattr(x, "sharding", None) == sharding:
        return x
    from nomad_tpu.parallel.devices import classify_move, note_transfer
    if isinstance(x, jax.Array):
        src = next(iter(x.devices())).platform
        try:
            dst = next(iter(sharding.device_set)).platform
        except Exception:
            dst = src
        kind = classify_move(src, dst)
    else:
        kind = "h2d"
    note_transfer(kind, 1, x)
    return jax.device_put(x, sharding)


def _shardings(mesh: Mesh):
    node = NamedSharding(mesh, P(FLEET_AXIS))          # [N, ...] row-sharded
    group_node = NamedSharding(mesh, P(None, FLEET_AXIS))  # [G, N]
    repl = NamedSharding(mesh, P())
    return node, group_node, repl


def _batch_shardings(mesh: Mesh):
    """Lane-axis-aware shardings for the storm layouts: on a 1-D fleet
    mesh lanes are replicated work descriptors; on a 2-D storm_mesh the
    leading (eval) axis shards over LANE_AXIS so independent evals run
    data-parallel.  Fleet-static tensors use P(FLEET_AXIS) either way —
    on the 2-D mesh that means replicated across lanes, sharded on
    nodes, which is exactly the storm's sharing pattern."""
    lane_ax = LANE_AXIS if LANE_AXIS in mesh.axis_names else None
    node = NamedSharding(mesh, P(FLEET_AXIS))
    lane_node = NamedSharding(mesh, P(lane_ax, None, FLEET_AXIS))  # [B,G,N]
    lane_n = NamedSharding(mesh, P(lane_ax, FLEET_AXIS))           # [B,N]
    lane = NamedSharding(mesh, P(lane_ax))
    repl = NamedSharding(mesh, P())
    return node, lane_node, lane_n, lane, repl


def shard_fleet_arrays(mesh: Mesh, capacity, reserved, usage, job_counts,
                       feasible):
    """Place fleet tensors on the mesh, node axis sharded."""
    node, group_node, repl = _shardings(mesh)
    return (
        _put(capacity, node),
        _put(reserved, node),
        _put(usage, node),
        _put(job_counts, node),
        _put(feasible, group_node),
    )


@partial(jax.jit, static_argnames=("unroll",))
def _place_sharded(capacity, reserved, usage0, job_counts0, feasible, asks,
                   distinct, group_idx, valid, penalty, unroll=1):
    return _place_sequence(capacity, reserved, usage0, job_counts0, feasible,
                           asks, distinct, group_idx, valid, penalty,
                           unroll=unroll)


def place_sequence_sharded(mesh: Mesh, capacity, reserved, usage0,
                           job_counts0, feasible, asks, distinct, group_idx,
                           valid, penalty):
    """Run the placement scan with the node axis sharded over `mesh`.

    Inputs may be host numpy arrays; they are placed with node-axis
    shardings and the jitted scan lets XLA insert the cross-device argmax
    reduction + scatter updates (psum/all-gather over ICI).
    """
    capacity, reserved, usage0, job_counts0, feasible = shard_fleet_arrays(
        mesh, capacity, reserved, usage0, job_counts0, feasible)
    _, _, repl = _shardings(mesh)
    asks = _put(asks, repl)
    distinct = _put(distinct, repl)
    group_idx = _put(group_idx, repl)
    valid = _put(valid, repl)
    # The penalty scalar rides the same replicated placement as the
    # other work descriptors: left as a host scalar it was an IMPLICIT
    # per-dispatch transfer jit performed silently on every sharded
    # single-eval dispatch (devlint sharding-mix; the batch wrappers
    # below always placed it).
    penalty = _put(penalty, repl)
    return _place_sharded(capacity, reserved, usage0, job_counts0, feasible,
                          asks, distinct, group_idx, valid, penalty)


# -- sharded throughput kernels ------------------------------------------
# The single-eval scan above is the latency path; the carriers of bench
# throughput are place_rounds (top-k round placement) and the vmapped
# batch variants (ops/binpack.py).  Their sharded forms keep the SAME
# node-axis sharding: per-shard score math, with the top_k / argmax
# winner selection resolved by XLA-inserted cross-shard collectives.


@partial(jax.jit, static_argnames=("k_cap", "rounds"))
def _place_rounds_sharded_jit(capacity, reserved, usage0, jc0, feasible,
                              asks, distinct, counts, penalty,
                              k_cap: int, rounds: int):
    return _place_rounds(capacity, reserved, usage0, jc0, feasible, asks,
                         distinct, counts, penalty, k_cap=k_cap,
                         rounds=rounds)


def place_rounds_sharded(mesh: Mesh, capacity, reserved, usage0, jc0,
                         feasible, asks, distinct, counts, penalty, *,
                         k_cap: int, rounds: int):
    """place_rounds with the node axis sharded over ``mesh``: each shard
    scores its slice of the fleet; lax.top_k over the sharded axis becomes
    a per-shard top-k + cross-shard merge (XLA GSPMD)."""
    capacity, reserved, usage0, jc0, feasible = shard_fleet_arrays(
        mesh, capacity, reserved, usage0, jc0, feasible)
    _, _, repl = _shardings(mesh)
    asks = _put(asks, repl)
    distinct = _put(distinct, repl)
    counts = _put(counts, repl)
    penalty = _put(penalty, repl)  # see place_sequence_sharded
    return _place_rounds_sharded_jit(capacity, reserved, usage0, jc0,
                                     feasible, asks, distinct, counts,
                                     penalty, k_cap=k_cap, rounds=rounds)


@partial(jax.jit, static_argnames=("k_cap", "rounds"))
def _place_rounds_batch_sharded_jit(capacity, reserved, usage0, jc0,
                                    feasible, asks, distinct, counts,
                                    penalty, k_cap: int, rounds: int):
    fn = jax.vmap(partial(_place_rounds, k_cap=k_cap, rounds=rounds),
                  in_axes=(None, None, None, 0, 0, 0, 0, 0, 0))
    return fn(capacity, reserved, usage0, jc0, feasible, asks, distinct,
              counts, penalty)


def place_rounds_batch_sharded(mesh: Mesh, capacity, reserved, usage0, jc0,
                               feasible, asks, distinct, counts, penalty, *,
                               k_cap: int, rounds: int):
    """Batched (one lane per eval) rounds placement, node axis sharded.

    On a 1-D fleet mesh lanes are replicated work descriptors — every
    device's fleet slice serves every lane.  On a 2-D ``storm_mesh``
    the lane axis also shards, so independent evals run data-parallel
    across mesh rows while each row's fleet slice stays HBM-resident
    (B x G x N feasibility sharded on lanes + N, base usage shared)."""
    node, lane_node, lane_n, lane, repl = _batch_shardings(mesh)
    capacity = _put(capacity, node)
    reserved = _put(reserved, node)
    usage0 = _put(usage0, node)
    jc0 = _put(jc0, lane_n)
    feasible = _put(feasible, lane_node)
    asks = _put(asks, lane)
    distinct = _put(distinct, lane)
    counts = _put(counts, lane)
    penalty = _put(penalty, repl)
    return _place_rounds_batch_sharded_jit(
        capacity, reserved, usage0, jc0, feasible, asks, distinct, counts,
        penalty, k_cap=k_cap, rounds=rounds)


@jax.jit
def _place_sequence_batch_sharded_jit(capacity, reserved, usage0, jc0,
                                      feasible, asks, distinct, group_idx,
                                      valid, penalty):
    fn = jax.vmap(partial(_place_sequence, unroll=1),
                  in_axes=(None, None, None, 0, 0, 0, 0, 0, 0, 0))
    return fn(capacity, reserved, usage0, jc0, feasible, asks, distinct,
              group_idx, valid, penalty)


def place_sequence_batch_sharded(mesh: Mesh, capacity, reserved, usage0,
                                 jc0, feasible, asks, distinct, group_idx,
                                 valid, penalty):
    """Batched placement scan (one lane per eval), node axis sharded;
    lane axis also shards on a 2-D ``storm_mesh`` (see
    place_rounds_batch_sharded)."""
    node, lane_node, lane_n, lane, repl = _batch_shardings(mesh)
    capacity = _put(capacity, node)
    reserved = _put(reserved, node)
    usage0 = _put(usage0, node)
    jc0 = _put(jc0, lane_n)
    feasible = _put(feasible, lane_node)
    asks = _put(asks, lane)
    distinct = _put(distinct, lane)
    group_idx = _put(group_idx, lane)
    valid = _put(valid, lane)
    penalty = _put(penalty, repl)
    return _place_sequence_batch_sharded_jit(
        capacity, reserved, usage0, jc0, feasible, asks, distinct,
        group_idx, valid, penalty)
