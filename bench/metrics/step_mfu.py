"""Model FLOP utilization of the traced window: the dense forward and backward
operations per example (``bench/flops.py``) times the examples per second,
over the chip's bf16 peak."""
from bench.flops import train_flops_per_example


def read(ctx):
    return 100.0 * train_flops_per_example(ctx.shapes) * ctx.samples_per_s / ctx.peaks["bf16_flops"]
