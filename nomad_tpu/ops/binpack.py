"""Vectorized bin-packing: the TPU replacement for the iterator hot loop.

Re-expresses the reference's per-candidate scoring walk
(/root/reference/scheduler/rank.go:161-234 BinPackIterator +
/root/reference/nomad/structs/funcs.go:48-124 AllocsFit/ScoreFit +
/root/reference/scheduler/rank.go:243-302 JobAntiAffinityIterator +
/root/reference/scheduler/select.go MaxScoreIterator) as array ops over the
whole fleet at once:

  fit    = all(reserved + usage + ask <= capacity, dims)     # AllocsFit
  score  = clamp(20 - (10^freeCpu% + 10^freeMem%), 0, 18)    # ScoreFit v3
  score -= penalty * same_job_count                          # anti-affinity
  choice = argmax(where(feasible & fit, score, -inf))        # MaxScore

Placements within one evaluation interact through the usage tensor (placing
alloc i changes the residual seen by alloc i+1), so a single evaluation is a
``lax.scan`` over its placement sequence, each step O(N) elementwise + one
argmax — fully on-device, no host round-trips.  Independent evaluations are
batched with ``vmap`` (optimistic concurrency: each plans against its own
copy of the snapshot usage, conflicts resolved at plan-apply, exactly like
the reference's worker pool).

Instead of the reference's power-of-two-choices truncation
(stack.go:106-117, LimitIterator) the device scores EVERY feasible node —
a full-fleet argmax is cheaper on TPU than emulating sequential truncation,
and placement quality strictly improves (SURVEY.md section 7).

All shapes are static (node axis padded to a power of two, placement axis
bucketed) so jit caches stay hot across evals.  The node axis is the
sharding axis for multi-chip meshes (nomad_tpu/parallel/mesh.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1.0e30

# Resource dim layout (nomad_tpu/structs ALL_FIT_DIMS).
DIM_CPU = 0
DIM_MEM = 1


def score_all_nodes(capacity, reserved, usage, job_counts, ask, feasible,
                    distinct, penalty):
    """Score one ask against every node. Returns (masked_scores f32[N]).

    Exact vectorization of ScoreFit (funcs.go:92-124) + AllocsFit dimension
    check (funcs.go:48-87) + job anti-affinity (rank.go:243-302).
    """
    util = reserved + usage + ask  # == AllocsFit's `used` + this ask

    # AllocsFit: every dimension must fit within capacity.
    fit = jnp.all(util <= capacity, axis=-1)

    # ScoreFit (BestFit v3): free fraction of cpu+mem after reservation.
    node_cpu = capacity[:, DIM_CPU] - reserved[:, DIM_CPU]
    node_mem = capacity[:, DIM_MEM] - reserved[:, DIM_MEM]
    safe_cpu = jnp.where(node_cpu > 0, node_cpu, 1.0)
    safe_mem = jnp.where(node_mem > 0, node_mem, 1.0)
    free_cpu = 1.0 - util[:, DIM_CPU] / safe_cpu
    free_mem = 1.0 - util[:, DIM_MEM] / safe_mem
    score = 20.0 - (jnp.power(10.0, free_cpu) + jnp.power(10.0, free_mem))
    score = jnp.clip(score, 0.0, 18.0)
    score = jnp.where((node_cpu > 0) & (node_mem > 0), score, 0.0)

    # Job anti-affinity: spread same-job allocs across nodes.
    score = score - penalty * job_counts.astype(score.dtype)

    # distinct_hosts: no second same-job alloc on a node.
    ok = feasible & fit & jnp.where(distinct, job_counts == 0, True)
    return jnp.where(ok, score, NEG_INF)


def _place_sequence(capacity, reserved, usage0, job_counts0, feasible, asks,
                    distinct, group_idx, valid, penalty, unroll: int = 1):
    """Place a sequence of allocations for one evaluation, on device.

    Args:
      capacity, reserved: f32[N, D] node-static tensors.
      usage0:     f32[N, D] usage at plan start (existing - evictions).
      job_counts0: i32[N] proposed same-job allocs per node.
      feasible:   bool[G, N] precompiled static feasibility per task group.
      asks:       f32[G, D] total resource ask per task group.
      distinct:   bool[G] distinct_hosts flag per group.
      group_idx:  i32[P] which group each placement instance belongs to.
      valid:      bool[P] padding mask over the placement axis.
      penalty:    f32 scalar anti-affinity penalty (10 service / 5 batch).

    Returns:
      chosen: i32[P] node index per placement, -1 = no feasible node.
      scores: f32[P] winning score (meaningless where chosen == -1).
      usage:  f32[N, D] usage after all placements.
    """

    def step(carry, xs):
        usage, job_counts = carry
        g, is_valid = xs
        ask = asks[g]
        masked = score_all_nodes(capacity, reserved, usage, job_counts,
                                 ask, feasible[g], distinct[g], penalty)
        choice = jnp.argmax(masked)
        best = masked[choice]
        ok = is_valid & (best > NEG_INF / 2)

        delta = jnp.where(ok, 1.0, 0.0)
        usage = usage.at[choice].add(ask * delta)
        job_counts = job_counts.at[choice].add(delta.astype(job_counts.dtype))
        out_choice = jnp.where(ok, choice.astype(jnp.int32), -1)
        return (usage, job_counts), (out_choice, best)

    (usage, _), (chosen, scores) = lax.scan(
        step, (usage0, job_counts0), (group_idx, valid), unroll=unroll)
    return chosen, scores, usage


place_sequence = jax.jit(_place_sequence, static_argnames=("unroll",))


def _place_rounds(capacity, reserved, usage0, jc0, feasible, asks, distinct,
                  counts, penalty, k_cap: int, rounds: int):
    """Round-based placement: many copies per device step.

    For each task-group slot, one step scores the fleet once and places up
    to ``min(remaining, k_cap)`` copies on the top-scoring DISTINCT nodes
    (lax.top_k), then repeats for ``rounds`` rounds.  Equivalent to the
    one-at-a-time greedy whenever the anti-affinity penalty exceeds the
    bin-packing score gain of adding one copy (the host checks that
    condition and falls back to ``place_sequence`` otherwise) — because
    then the greedy never stacks a second copy on a node before using every
    other feasible node, i.e. it spreads exactly like top-k.

    Motivation: sequential scans pay a fixed per-iteration cost; this
    path needs S x rounds steps instead of one step per placement — a
    10k-placement eval with one deduped group runs in ~1 device step.

    Args mirror place_sequence except:
      counts: i32[G] — copies to place per slot.
      k_cap:  static — max copies placeable per round (<= padded node
              axis; may be below a slot's count, extra rounds cover it).
      rounds: static — rounds per slot (host sizes it so
              rounds * min(feasible_count, k_cap) >= count).

    Returns:
      chosen: i32[G, rounds * k_cap] node indices in placement order per
              slot (-1 = unplaced), scores alike, final usage.
    """

    def slot_step(carry, s):
        usage, jc = carry
        ask = asks[s]
        feas = feasible[s]
        dist = distinct[s]

        def round_step(carry2, _r):
            usage, jc, m = carry2
            masked = score_all_nodes(capacity, reserved, usage, jc, ask,
                                     feas, dist, penalty)
            vals, idx = lax.top_k(masked, k_cap)
            pos = lax.iota(jnp.int32, k_cap)
            valid = (pos < m) & (vals > NEG_INF / 2)
            usage = usage.at[idx].add(
                jnp.where(valid[:, None], ask[None, :], 0.0))
            jc = jc.at[idx].add(valid.astype(jc.dtype))
            placed = valid.sum()
            chosen_r = jnp.where(valid, idx.astype(jnp.int32), -1)
            return (usage, jc, m - placed), (chosen_r, vals)

        (usage, jc, _m), (chosen_rs, val_rs) = lax.scan(
            round_step, (usage, jc, counts[s]), jnp.arange(rounds))
        return (usage, jc), (chosen_rs.reshape(-1), val_rs.reshape(-1))

    (usage, _jc), (chosen, scores) = lax.scan(
        slot_step, (usage0, jc0), jnp.arange(feasible.shape[0]))
    return chosen, scores, usage


place_rounds = jax.jit(_place_rounds, static_argnames=("k_cap", "rounds"))


def _place_rounds_batched(capacity, reserved, usage0, jc0, feasible, asks,
                          distinct, counts, penalty, k_cap: int,
                          rounds: int):
    fn = jax.vmap(partial(_place_rounds, k_cap=k_cap, rounds=rounds),
                  in_axes=(None, None, None, 0, 0, 0, 0, 0, 0))
    return fn(capacity, reserved, usage0, jc0, feasible, asks, distinct,
              counts, penalty)


place_rounds_batch = jax.jit(_place_rounds_batched,
                             static_argnames=("k_cap", "rounds"))

# Batched over independent evaluations (axis 0 of per-eval args):
# optimistic concurrency on device — every eval starts from the SAME
# snapshot usage (broadcast on device, no per-eval upload) and evolves its
# own copy through the scan; job_counts IS per-eval (each eval schedules its
# own job).  The host plan-apply loop serializes commits (reference
# nomad/plan_apply.go parity).
place_sequence_batch = jax.jit(
    jax.vmap(
        partial(_place_sequence, unroll=1),
        in_axes=(None, None, None, 0, 0, 0, 0, 0, 0, 0),
    )
)
