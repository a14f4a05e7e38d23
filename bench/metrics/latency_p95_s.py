"""95th percentile of the same latencies as ``latency_p50_s``."""
from bench.run import percentile


def read(ctx):
    return percentile([r.latency for r in ctx.requests], 95)
