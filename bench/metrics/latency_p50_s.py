"""Median latency over every request of the window, failed ones included:
from submit (closed loop) or from when it was due (open loop) to its
result."""
from bench.run import percentile


def read(ctx):
    return percentile([r.latency for r in ctx.requests], 50)
