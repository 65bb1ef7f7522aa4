"""Replica exchanges landed by the shadow thread per trainer iteration (runner counters)."""


def read(ctx):
    iters = sum(ctx.iter_count)
    return ctx.counters["sync_count"] / iters if iters else None
