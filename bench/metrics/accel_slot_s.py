"""Mean ``ExecutionStats.time_a`` per request of the window: the accelerator class's makespan,
on the host clock of the executor."""
import numpy as np


def read(ctx):
    vals = [r.stats.time_a for r in ctx.requests if r.ok]
    return float(np.mean(vals)) if vals else None
