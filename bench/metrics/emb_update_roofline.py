"""Share of its roofline that the fused sparse-Adagrad update kernel reaches.

The least time is the logical bytes the window's updates must move
(``bench/bytes.py``: every occurrence's gradient row read, every distinct
row's table and accumulator entries read and written) over the chip's HBM
bandwidth; the kernel's time is the summed device time of its instruction
(``sparse_adagrad_op``) in the trace. The whole-shard copies around the
kernel are not its time: they are ``copy`` in the breakdown."""
from bench import bytes as nbytes

KERNEL = "sparse_adagrad_op"


def read(ctx):
    secs = ctx.trace.op_seconds(KERNEL)
    if secs <= 0:
        return None
    moved = nbytes.total(ctx.window_ids(), ctx.shapes.dim, nbytes.update_bytes,
                         ctx.shapes.bags)
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / secs
