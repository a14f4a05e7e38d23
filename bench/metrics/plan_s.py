"""Mean ``ExecutionStats.plan_seconds`` per request of the window: decide and plan (or a plan-cache lookup),
on the host clock of the executor."""
import numpy as np


def read(ctx):
    vals = [r.stats.plan_seconds for r in ctx.requests if r.ok]
    return float(np.mean(vals)) if vals else None
