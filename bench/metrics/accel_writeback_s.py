"""Mean ``ExecutionStats.writeback_a`` per request of the window: the
write-back phase (outputs read into the host buffers) of the accelerator
slot that set ``time_a``, on the executor's host clock.  A program
without the phase gives nothing to read."""
import numpy as np


def read(ctx):
    vals = [getattr(r.stats, "writeback_a", None) for r in ctx.requests
            if r.ok]
    vals = [v for v in vals if v is not None]
    return float(np.mean(vals)) if vals else None
