"""Constraint compilation: host predicates -> per-node boolean masks.

Nomad constraints are stringly-typed (=, !=, lexical order, semver,
regexp over arbitrary attrs — reference scheduler/feasible.go:259-376), so
they cannot run on the MXU.  The TPU design compiles each constraint ONCE per
fleet generation into a boolean mask over the node axis, evaluated host-side
with the exact same predicate functions the sequential scheduler uses (golden
parity by construction), then ships masks to HBM where the device pipeline
just ANDs them (SURVEY.md section 7, "Constraint vectorization").

Masks are cached in ``FleetStatics.mask_cache`` keyed by the constraint's
value tuple, so a 10k-node fleet pays the Python predicate walk once per
(constraint, fleet-generation), not once per placement.

``distinct_hosts`` is NOT compiled here — it depends on the in-flight plan,
so it is evaluated on device from the per-node same-job alloc count tensor.

A group-mask miss is what costs: the counters ``nomad.constraints.
mask_compiles`` / ``.rows_judged`` (always on) count the misses and the
rows the Python predicate walks judged for them, and with tracing on the
miss is one ``constraints.compile`` span (``rows``, ``stanzas``).
"""
from __future__ import annotations

import numpy as np

from nomad_tpu.obs import trace as trace_mod
from nomad_tpu.structs import CONSTRAINT_DISTINCT_HOSTS, Constraint
from nomad_tpu.utils.metrics import metrics
from nomad_tpu.utils.predicates import (
    check_constraint_values,
    resolve_constraint_target,
)

from .fleet import FleetStatics


class _MaskCtx:
    """Minimal EvalContext stand-in carrying the predicate caches."""

    __slots__ = ("regexp_cache", "constraint_cache")

    def __init__(self) -> None:
        self.regexp_cache: dict = {}
        self.constraint_cache: dict = {}


_mask_ctx = _MaskCtx()


def _constraint_key(c: Constraint) -> tuple:
    return ("c", c.l_target, c.operand, c.r_target)


def compile_constraint_mask(fleet: FleetStatics, c: Constraint) -> np.ndarray:
    """bool[n_pad] mask of nodes meeting one hard constraint."""
    return _constraint_mask(fleet, c)[0]


def _constraint_mask(fleet: FleetStatics, c: Constraint) -> tuple:
    """(the mask, rows judged in Python to make it: 0 on a hit)."""
    key = _constraint_key(c)
    mask = fleet.mask_cache.get(key)
    if mask is not None:
        return mask, 0

    mask = np.zeros(fleet.n_pad, dtype=bool)
    if fleet.uniform and fleet.n_real and _targets_uniform(c):
        # Uniform fleet (NodeSlab-backed, shared attributes/meta/class/
        # dc): the predicate's verdict on ONE representative row holds
        # for every row — O(1) instead of a 100k-1M-node Python walk.
        mask[:fleet.n_real] = _constraint_verdict(fleet.nodes[0], c)
        judged = 1
    else:
        for i in range(fleet.n_real):
            mask[i] = _constraint_verdict(fleet.nodes[i], c)
        judged = fleet.n_real

    fleet.mask_cache[key] = mask
    return mask, judged


# Interpolation targets that resolve PER ROW even on a uniform fleet:
# ids and names are dense slab columns, never template-shared.
# ($node.datacenter IS covered by the uniform flag — it is only set
# when the slab's rows share one datacenter string; $attr.*/$meta.*
# read the shared template; literals and unknown $-targets are
# row-independent by construction.)
_PER_ROW_TARGETS = ("$node.id", "$node.name")


def _targets_uniform(c: Constraint) -> bool:
    return c.l_target not in _PER_ROW_TARGETS and \
        c.r_target not in _PER_ROW_TARGETS


def _constraint_verdict(node, c: Constraint) -> bool:
    l_val, ok = resolve_constraint_target(c.l_target, node)
    if not ok:
        return False
    r_val, ok = resolve_constraint_target(c.r_target, node)
    if not ok:
        return False
    return check_constraint_values(_mask_ctx, c.operand, l_val, r_val)


def _driver_mask(fleet: FleetStatics, driver: str) -> tuple:
    """(bool[n_pad] mask of nodes whose 'driver.<name>' attr parses
    true, rows judged in Python to make it: 0 on a hit)."""
    key = ("d", driver)
    mask = fleet.mask_cache.get(key)
    if mask is not None:
        return mask, 0

    attr = f"driver.{driver}"
    mask = np.zeros(fleet.n_pad, dtype=bool)
    rows = range(1) if fleet.uniform and fleet.n_real \
        else range(fleet.n_real)
    for i in rows:
        value = fleet.attr_rows[i].get(attr)
        if value is not None and \
                str(value).strip().lower() in ("1", "t", "true"):
            mask[i] = True
    if fleet.uniform and fleet.n_real:
        mask[:fleet.n_real] = mask[0]

    fleet.mask_cache[key] = mask
    return mask, len(rows)


def _dc_mask(fleet: FleetStatics, datacenters: list) -> tuple:
    """(bool[n_pad] mask of nodes in one of the job's datacenters, rows
    judged in Python to make it: 0 on a hit)."""
    key = ("dc", tuple(sorted(datacenters)))
    mask = fleet.mask_cache.get(key)
    if mask is not None:
        return mask, 0

    dc_set = set(datacenters)
    mask = np.zeros(fleet.n_pad, dtype=bool)
    if fleet.uniform and fleet.n_real:
        mask[:fleet.n_real] = fleet.datacenters[0] in dc_set
        judged = 1
    else:
        for i in range(fleet.n_real):
            mask[i] = fleet.datacenters[i] in dc_set
        judged = fleet.n_real

    fleet.mask_cache[key] = mask
    return mask, judged


def group_mask_key(datacenters: list, job_constraints: list,
                   tg_constraints: list, drivers) -> tuple:
    """Value-semantic cache key for a composed group mask: two task groups
    with identical constraints/drivers/datacenters share one mask row (count
    expansion makes this the common case)."""
    cons = tuple(sorted(
        (c.l_target, c.operand, c.r_target)
        for c in job_constraints + tg_constraints
        if c.hard and c.operand != CONSTRAINT_DISTINCT_HOSTS))
    return (tuple(sorted(datacenters)), cons, tuple(sorted(drivers)))


def compile_group_mask(
    fleet: FleetStatics,
    datacenters: list,
    job_constraints: list,
    tg_constraints: list,
    drivers,
) -> tuple[np.ndarray, bool]:
    """Full static feasibility mask for one task group.

    AND of: ready, datacenter, job constraints, task-group+task constraints,
    driver presence — i.e. the entire feasibility half of the iterator chain
    (reference scheduler/stack.go:126-143) as one boolean vector.

    Returns (mask, distinct_hosts?) — distinct_hosts is resolved on device.
    """
    distinct = any(
        c.hard and c.operand == CONSTRAINT_DISTINCT_HOSTS
        for c in job_constraints + tg_constraints)
    key = ("g",) + group_mask_key(datacenters, job_constraints,
                                  tg_constraints, drivers)
    hit = fleet.mask_cache.get(key)
    if hit is not None:
        return hit, distinct

    tracer = trace_mod.tracer() if trace_mod.ENABLED else None
    t0 = tracer.now() if tracer is not None else 0.0
    mask = fleet.ready.copy()
    part, judged = _dc_mask(fleet, datacenters)
    mask &= part
    for c in job_constraints + tg_constraints:
        if not c.hard or c.operand == CONSTRAINT_DISTINCT_HOSTS:
            continue
        part, rows = _constraint_mask(fleet, c)
        mask &= part
        judged += rows
    for driver in sorted(drivers):
        part, rows = _driver_mask(fleet, driver)
        mask &= part
        judged += rows
    fleet.mask_cache[key] = mask
    metrics.incr_counter("nomad.constraints.mask_compiles", 1)
    metrics.incr_counter("nomad.constraints.rows_judged", judged)
    if tracer is not None:
        tracer.record("constraints.compile", t0, tracer.now() - t0,
                      parent_ctx=tracer.ctx(), rows=judged,
                      stanzas=len(key[2]))
    return mask, distinct
