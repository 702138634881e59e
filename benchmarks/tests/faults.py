"""The timed path broken underneath, one fault at a time: each is a
context manager that alters the PROGRAM (never the harness or the
reference) while it is entered.  One chip: there is no exchange between
chips to leave out.

    state_left_unchanged    evaluations finish, nothing is committed
    half_of_every_plan      every second allocation of a plan is dropped
                            where the plan is committed
    answer_altered          every pick moved three nodes on where it is
                            produced, the score recorded for it kept

``test_control_and_faults.py`` drives a rehearsal under each.  On the
chip, at a cell's own size (the readings in PERF.md section 2):

    python3 benchmarks/tests/faults.py <fault> --workload <cell> \\
        --seed <n> --seconds <s> --trace 0
"""
import contextlib
import os
import sys


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


FAULTS = {f.__name__: f for f in (state_left_unchanged, half_of_every_plan,
                                  answer_altered)}

if __name__ == "__main__":
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [bench, os.path.dirname(bench)]
    import run

    with FAULTS[sys.argv[1]]():
        sys.exit(run.main(sys.argv[2:]))
