"""Share of an SCT's roofline reached by its accelerator slots in a traced
window, for the ``accel_roofline`` metric reader, whatever SCT the cell
runs.

The numerator is the least time the cell's chips could take for the work
they were given: each request's accelerator units times the least
operations and bytes of one unit (``bench/reference.py:unit_work``), over
the chip's peaks (``bench/peaks.json``), the larger of the two.  The
denominator is the chips' busy time in the same trace.  The work is counted
from shapes, so the share reads the same work whatever implements the SCT.
"""
from bench.reference import unit_work


def share(ctx):
    """Percent of the roofline, or None where there is nothing to read."""
    if ctx.trace is None:
        return None
    ops, nbytes = unit_work(ctx.sct, ctx.size)
    units = sum(sum(r.accel_units.values()) for r in ctx.requests if r.ok)
    busy = ctx.trace.busy_s()
    total = sum(busy.get(p, 0.0) for p in ctx.planes)
    if total <= 0 or units == 0:
        return None
    least = max(units * ops / ctx.peaks["flops_per_s"],
                units * nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / total
