"""Mean ``ExecutionStats.merge_seconds`` per request of the window: result assembly,
on the host clock of the executor."""
import numpy as np


def read(ctx):
    vals = [r.stats.merge_seconds for r in ctx.requests if r.ok]
    return float(np.mean(vals)) if vals else None
