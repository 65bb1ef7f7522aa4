"""Share of its roofline that the fused embedding lookup+pool kernel reaches.

The least time is the logical bytes the window's lookups must move
(``bench/bytes.py``: every id's row read, every bag's sum written) over the
chip's HBM bandwidth; the kernel's time is the summed device time of its
instruction (``embedding_bag_op``) in the trace. The kernel moves no FLOPs
worth bounding it, so bytes set the roofline."""
from bench import bytes as nbytes

KERNEL = "embedding_bag_op"


def read(ctx):
    secs = ctx.trace.op_seconds(KERNEL)
    if secs <= 0:
        return None
    moved = nbytes.total(ctx.window_ids(), ctx.shapes.dim, nbytes.lookup_bytes,
                         ctx.shapes.bags)
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / secs
