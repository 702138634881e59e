"""The timed path broken underneath, one fault at a time: each is a
context manager that alters the PROGRAM (never the harness or the
reference) while it is entered.  One chip: there is no exchange between
chips to leave out.

    state_left_unchanged    evaluations finish, nothing is committed
    half_of_every_plan      every second allocation of a plan is dropped
                            where the plan is committed
    answer_altered          every pick moved three nodes on where it is
                            produced, the score recorded for it kept
    constraints_ignored     every group's feasibility mask is every
                            ready node, where the scheduler composes it
    distinct_ignored        every slot's ``distinct_hosts`` flag off,
                            where the scheduler derives it
    resident_dropped        the store drops every allocation whose
                            evaluation it does not hold: the resident
                            allocations, written with none
    resident_usage_ignored  the usage view (``models/fleet.alloc_vec``:
                            the scheduler's and the plan verifier's)
                            counts nothing for those allocations

``constraints_ignored`` and ``distinct_ignored`` only show where a job
states a constraint or ``distinct_hosts``, the last two only where a
configuration states ``resident`` jobs (the fixtures under
``fixtures/``).

``test_control_and_faults.py`` drives a rehearsal under each.  On the
chip, at a cell's own size (the readings in PERF.md section 2):

    python3 benchmarks/tests/faults.py <fault> --workload <cell> \\
        --seed <n> --seconds <s> --trace 0
"""
import contextlib
import os
import sys

import numpy as np


@contextlib.contextmanager
def _patched(owner, name, replacement):
    sound = getattr(owner, name)
    setattr(owner, name, replacement(sound))
    try:
        yield
    finally:
        setattr(owner, name, sound)


def state_left_unchanged():
    from nomad_tpu.state.store import StateStore

    return _patched(StateStore, "upsert_allocs_batched",
                    lambda sound: lambda self, items: None)


def half_of_every_plan():
    from nomad_tpu.state.store import StateStore

    return _patched(
        StateStore, "upsert_allocs_batched",
        lambda sound: lambda self, items: sound(
            self, [(index, allocs[::2]) for index, allocs in items]))


def answer_altered():
    from nomad_tpu.scheduler import jax_binpack

    def replacement(sound):
        def altered(args, chosen_slots, score_slots):
            chosen, scores = sound(args, chosen_slots, score_slots)
            placed = chosen >= 0
            chosen[placed] = (chosen[placed] + 3) % args.statics.n_real
            return chosen, scores
        return altered

    return _patched(jax_binpack, "rounds_to_placements", replacement)


def constraints_ignored():
    from nomad_tpu.scheduler import jax_binpack

    return _patched(
        jax_binpack, "compile_group_mask",
        lambda sound: lambda statics, *args: (
            statics.ready.copy(), sound(statics, *args)[1]))


def distinct_ignored():
    from nomad_tpu.scheduler.jax_binpack import JaxBinPackScheduler

    def replacement(sound):
        def derive(self, *args):
            sem = sound(self, *args)
            return sem[:3] + (False,) + sem[4:]
        return derive

    return _patched(JaxBinPackScheduler, "_derive_sem", replacement)


def _without_evaluation(store, items):
    """``upsert_allocs_batched``'s items split by whether the store
    holds the evaluation of an allocation: (kept, the others)."""
    kept, others = [], []
    for index, allocs in items:
        known = []
        for a in allocs:
            (known if store.eval_by_id(a.eval_id) else others).append(a)
        kept.append((index, known))
    return kept, others


def resident_dropped():
    from nomad_tpu.state.store import StateStore

    def replacement(sound):
        def drop(self, items):
            return sound(self, _without_evaluation(self, items)[0])
        return drop

    return _patched(StateStore, "upsert_allocs_batched", replacement)


@contextlib.contextmanager
def resident_usage_ignored():
    from nomad_tpu.models import fleet
    from nomad_tpu.state.store import StateStore

    unplaced = set()

    def record(sound):
        def upsert(self, items):
            unplaced.update(a.eval_id for a in
                            _without_evaluation(self, items)[1])
            return sound(self, items)
        return upsert

    def ignore(sound):
        def vec(alloc):
            if alloc.eval_id in unplaced:
                return np.zeros(fleet.NDIMS, dtype=np.float32)
            return sound(alloc)
        return vec

    with _patched(StateStore, "upsert_allocs_batched", record), \
            _patched(fleet, "alloc_vec", ignore):
        yield


FAULTS = {f.__name__: f for f in (state_left_unchanged, half_of_every_plan,
                                  answer_altered, constraints_ignored,
                                  distinct_ignored, resident_dropped,
                                  resident_usage_ignored)}

if __name__ == "__main__":
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [bench, os.path.dirname(bench)]
    import run

    with FAULTS[sys.argv[1]]():
        sys.exit(run.main(sys.argv[2:]))
