"""Mean time a request spends outside its node's execution span: from the
client's ``Session.submit`` call to its result, less the span that
``GraphHandle.spans()`` gives the node (admission queue, backpressure and
result delivery)."""
import numpy as np


def read(ctx):
    waits = [r.done - r.submit - r.span_s for r in ctx.requests if r.ok]
    return float(np.mean(waits)) if waits else None
