"""What a placement program has to move through the device's memory,
from the shapes of its call and nothing of its implementation.

``fused_rounds_window``: one fused window of top-k rounds
(``ops/binpack.place_rounds_batch``), given the shape tags of its
``device.dispatch`` span.  The least any implementation moves:

    read   capacity, reserved, usage     3 x n_pad x 6 float32, once
    read   the feasibility mask          one byte a node for every REAL
                                         slot of every real lane
    write  picks and scores              4 + 4 bytes for each of
                                         k_cap x rounds x slots a lane

The lanes' job counts (n_pad int32 a lane, all nought for a job with no
allocation yet) are left out: a window of fresh jobs need not move them.

``slots`` is the lanes' real slot count where the span states it and 1
a lane otherwise (the single-ask jobs of the closed-loop generator
dedupe to one).  Padded lanes (``b_pad`` - ``lanes``), padded slots
(``g_pad`` - slots) and every intermediate (the [lanes, n] score field,
the usage copies of a scan) earn no credit, so padding the kernel
cannot raise its share.  The floor is bytes, not operations: a score is
~20 flops for 25 bytes a node, far under the chip's 240 flops a byte.
"""
NDIMS = 6


def fused_rounds_window(tags: dict) -> int:
    n_pad = int(tags["n_pad"])
    slots = int(tags.get("slots", tags["lanes"]))
    picks = int(tags["k_cap"]) * int(tags["rounds"]) * slots
    return (3 * n_pad * NDIMS * 4     # capacity, reserved, usage
            + slots * n_pad           # the mask of the real slots
            + picks * 8)              # picks (int32) and scores (float32)
