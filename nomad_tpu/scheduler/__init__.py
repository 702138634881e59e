"""Scheduler layer: pure placement logic behind the State/Planner seams.

Registry carries service/batch/system (sequential, parity-faithful) plus the
TPU-native jax-binpack backend.  JAX is a hard dependency: a failure to
import or register the device schedulers fails the import of this package
instead of leaving a server that silently schedules on the CPU.
"""
from .interfaces import (  # noqa: F401
    BUILTIN_SCHEDULERS,
    Factory,
    Planner,
    Scheduler,
    SetStatusError,
    State,
    new_scheduler,
    register_scheduler,
)
from .context import EvalContext  # noqa: F401
from .generic import (  # noqa: F401
    GenericScheduler,
    new_batch_scheduler,
    new_service_scheduler,
)
from .system import SystemScheduler, new_system_scheduler  # noqa: F401
from .harness import Harness, RejectPlan  # noqa: F401
from .stack import GenericStack, SystemStack  # noqa: F401
from .batch import BatchEvalRunner  # noqa: F401
from .jax_binpack import (
    new_jax_binpack_batch_scheduler,
    new_jax_binpack_scheduler,
)
from .system_vec import new_vector_system_scheduler

register_scheduler("service", new_service_scheduler)
register_scheduler("batch", new_batch_scheduler)
# The sequential iterator-chain system scheduler stays addressable for
# golden-parity tests; "system" itself is the vectorized one.
register_scheduler("system-seq", new_system_scheduler)
register_scheduler("jax-binpack", new_jax_binpack_scheduler)
register_scheduler("jax-binpack-batch", new_jax_binpack_batch_scheduler)
register_scheduler("system", new_vector_system_scheduler)
