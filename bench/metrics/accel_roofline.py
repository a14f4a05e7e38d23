"""Share of the roofline of the cell's SCT that its accelerator slots
reached in the traced window (``bench/roofline.py``): the least time for
the chips' domain units at the chip's peaks, over the chips' busy time."""
from bench.roofline import share


def read(ctx):
    return share(ctx)
