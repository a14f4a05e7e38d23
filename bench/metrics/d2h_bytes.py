"""Mean ``ExecutionStats.d2h_bytes`` per request of the window: bytes of
the accelerator slots' outputs read into host memory (direct writes and
merge copies), counted by the executor.  A program without the count
gives nothing to read."""
import numpy as np


def read(ctx):
    vals = [getattr(r.stats, "d2h_bytes", None) for r in ctx.requests
            if r.ok]
    vals = [v for v in vals if v is not None]
    return float(np.mean(vals)) if vals else None
