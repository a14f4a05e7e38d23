"""Mean ``ExecutionStats.compute_a`` per request of the window: the
compute phase (segment views, ``sct.apply`` with the implicit upload of
host inputs, ``block_until_ready``) of the accelerator slot that set
``time_a``, on the executor's host clock.  A program without the phase
gives nothing to read."""
import numpy as np


def read(ctx):
    vals = [getattr(r.stats, "compute_a", None) for r in ctx.requests
            if r.ok]
    vals = [v for v in vals if v is not None]
    return float(np.mean(vals)) if vals else None
