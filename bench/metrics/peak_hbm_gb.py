"""Peak device memory of the run, ``memory_stats()["peak_bytes_in_use"]`` after the window."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes > 0 else None
