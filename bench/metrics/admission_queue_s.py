"""Mean ``ExecutionStats.queue_seconds`` per request of the window: the
wait in the Scheduler's admission queue (or a fusion window) between
``Scheduler.submit`` and the start of the request's graph, measured by
the program.  A program without the measurement gives nothing to
read."""
import numpy as np


def read(ctx):
    vals = [getattr(r.stats, "queue_seconds", None) for r in ctx.requests
            if r.ok]
    vals = [v for v in vals if v is not None]
    return float(np.mean(vals)) if vals else None
