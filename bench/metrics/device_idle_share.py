"""Share of the traced window in which no operation ran on the cell's
chips, averaged over the chips: 100 * (1 - busy / window), busy being the
union of each chip's operation intervals (``bench/devtrace.py``)."""


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.busy_s()
    mean = sum(busy.get(p, 0.0) for p in ctx.planes) / len(ctx.planes)
    return 100.0 * (1.0 - mean / ctx.trace.window_s)
