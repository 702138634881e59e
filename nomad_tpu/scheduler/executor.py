"""Executor policy: which engine runs the placement kernels.

The jax-binpack scheduler picks between two executors per dispatch
(scheduler/jax_binpack.py ``JaxBinPackScheduler.host_executor``, the one
comparison both the lone-eval and the fused site read):

  host    numpy twin kernels (ops/binpack_host.py) — zero dispatch
          latency, wins whenever the workload is smaller than a device
          round trip (enqueue + run + device->host copy; PERF.md
          records the fenced round trip measured on the chip);
  device  jit kernels (ops/binpack.py) — wins for fused eval storms,
          multi-chip fleets, and pipelined streams deep enough to hide
          the round trip behind host work.

``auto`` (the default) applies the cost model.  ``host`` / ``device``
force one side — the multi-chip dry run and the host/device parity
smoke both need a *forcible* device path, and an operator diagnosing a
slow chip wants the same lever without editing code.

Resolution order (first set wins):

  1. the ``NOMAD_TPU_EXECUTOR`` environment variable — checked per
     dispatch so a bench or operator can flip it without a restart;
  2. the process policy set from agent/server config
     (``server { executor = "..." }``, plumbed via
     ``set_executor_policy`` at server boot);
  3. ``auto``.

What a lane took is on its ``sched.dispatch`` span (scheduler/batch.py
``dispatch_tags``): ``engine`` = ``host`` (the numpy twin: the policy
said host, or under ``auto`` lanes x steps x nodes stayed within
``HOST_SINGLE_SHOT_COST``, steps being slots x rounds under top-k
rounds and placements on the sequence kernel), ``device`` (the XLA
kernel on one chip) or ``sharded`` (the same over a mesh), beside
``mode``, ``rounds``, the estimate itself (``cost``), the ``lanes``
that shared the choice and the lane's own REAL slot count (``slots``,
also on its ``sched.begin``; a one-by-one re-plan has no
``sched.dispatch`` and states ``twin_s`` / ``twin_slots`` on its
``sched.retry``) and, on the host engine, ``twin_rows`` of
``twin_rows_full``: the rows the twin's candidate sets held, of those
whole passes score (on ``sched.retry`` and ``retry.dispatch`` too);
``nomad.batch_runner.{host,device,sharded}_dispatches`` count the same
choice always, ``nomad.batch_runner.{host,device}_lanes`` the lanes
each engine placed (a fused device window is one dispatch of many
lanes), and ``nomad.batch_runner.slots`` / ``.padded_slots`` the real
slots of every kernel call beside the padded slot axis it was shaped
to, and ``nomad.batch_runner.twin_rows`` / ``.twin_rows_full`` those
rows.

Where the break-even falls.  A fused window scans its PADDED slot axis
(``g_pad``, at least 8), so a window of single-group lanes costs lanes x
8 x nodes: it crosses ``HOST_SINGLE_SHOT_COST`` = 2^25 above 32 lanes
at 131,072 nodes and above 419 at 10,000 (the runner fuses at most 64).
At the 100,000 nodes of ``fleet100k.stacks`` a window leaves the twin
at 42 lanes, whether its lanes carry one real slot or that cell's
three.  A lone eval counts its real slots: one group on 131,072 nodes
is 2^17, inside ``HOST_ALWAYS_COST``; a three-slot stack on 100,000 is
300,000, inside ``HOST_SINGLE_SHOT_COST``.  Measured on a v5e at 131,072 nodes
(PERF.md section 6, PR 33): the twin's pass over every row takes
8.4-10.2 ms a lane (539 ms for 64 lanes; since PR 38 a lane whose fleet
is 2% occupied scores a candidate set in 1.0 ms, and these figures are
a full fleet's); a fused window on the kernel takes 165 ms at 64 lanes
(61 ms from enqueue to results, 50 ms of it device time, 101 MB
uploaded), 52 ms at 32 lanes, 14 ms at 16 and 7.7 ms at one (the twin:
10.2); a fenced round trip of a tiny kernel is 0.99 ms.  At that width
the kernel won at every lane count against the pass over every row
(not against a candidate set's 1.0 ms), and ``auto`` still keeps a window
of 32 lanes or fewer on the twin (275 ms against 52, before PR 38):
the thresholds predate these numbers, the both-engines sweep has to be
run again on the candidate-set twin (PERF.md section 7), and moving
them is ROADMAP D2's, judged on ``fleet131k.storm`` and
``baseline4-10k.small``.

The override only selects the executor; plan semantics are identical on
both sides (tests/test_executor_parity.py gates this on every run).
"""
from __future__ import annotations

import os

EXECUTOR_AUTO = "auto"
EXECUTOR_HOST = "host"
EXECUTOR_DEVICE = "device"

VALID_EXECUTORS = (EXECUTOR_AUTO, EXECUTOR_HOST, EXECUTOR_DEVICE)

ENV_VAR = "NOMAD_TPU_EXECUTOR"

_configured: str = EXECUTOR_AUTO


class ExecutorPolicyError(ValueError):
    pass


def _validate(value: str, source: str) -> str:
    v = (value or "").strip().lower()
    if v not in VALID_EXECUTORS:
        raise ExecutorPolicyError(
            f"invalid executor {value!r} from {source}: want one of "
            f"{', '.join(VALID_EXECUTORS)}")
    return v


def validate_executor(value: str, source: str = "config") -> str:
    """Public validation hook for config loaders: normalized value or
    ExecutorPolicyError."""
    return _validate(value, source)


def set_executor_policy(value: str) -> None:
    """Install the process-wide policy (config plumbing; env still
    wins).  Raises ExecutorPolicyError on unknown values so a typo in a
    config file fails the boot instead of silently running ``auto``."""
    global _configured
    _configured = _validate(value, "config")


def executor_policy() -> str:
    """The effective policy right now: env var, then configured value,
    then ``auto``.  Read per dispatch — cheap (one getenv) and it keeps
    scoped overrides race-free with respect to restarts."""
    env = os.environ.get(ENV_VAR)
    if env:
        return _validate(env, f"${ENV_VAR}")
    return _configured


class executor_override:
    """Scoped force of the executor (bench rows, parity tests).

    Sets the ENV override — the highest-precedence source — and restores
    the previous value on exit, so nesting and config interplay behave
    predictably.  Process-global like the env var itself; use from the
    thread that owns the run (the pipeline's stage threads read the
    policy only at dispatch time, on the submitting thread).
    """

    def __init__(self, value: str) -> None:
        self.value = _validate(value, "executor_override")
        self._saved: str | None = None

    def __enter__(self) -> "executor_override":
        self._saved = os.environ.get(ENV_VAR)
        os.environ[ENV_VAR] = self.value
        return self

    def __exit__(self, *exc) -> None:
        if self._saved is None:
            os.environ.pop(ENV_VAR, None)
        else:
            os.environ[ENV_VAR] = self._saved
