"""Set-up: compile (or load from the cache) the usage mirror's row
scatter at every size it can take.  The mirror pads a sync's touched
rows to a power of two up to its ``MAX_SCATTER_ROWS``, so a window meets
up to eleven programs; which of them it meets depends on how commits
fall, so all are warmed.  Parameters: none."""
import numpy as np


def prewarm(params: dict, n_nodes: int, traffic: dict) -> None:
    import jax

    from nomad_tpu.models import fleet

    n_pad = fleet._pad_to(n_nodes)
    usage = jax.device_put(np.zeros((n_pad, fleet.NDIMS), dtype=np.float32))
    k = 1
    while k <= fleet.UsageMirror.MAX_SCATTER_ROWS:
        idx = np.arange(k, dtype=np.int32) % n_pad
        rows = np.zeros((k, fleet.NDIMS), dtype=np.float32)
        fleet._scatter_rows(usage, idx, rows).block_until_ready()
        k *= 2
