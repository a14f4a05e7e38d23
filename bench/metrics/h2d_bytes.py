"""Mean ``ExecutionStats.h2d_bytes`` per request of the window: bytes of
host-resident inputs handed to the accelerator slots, counted by the
executor.  A program without the count gives nothing to read."""
import numpy as np


def read(ctx):
    vals = [getattr(r.stats, "h2d_bytes", None) for r in ctx.requests
            if r.ok]
    vals = [v for v in vals if v is not None]
    return float(np.mean(vals)) if vals else None
