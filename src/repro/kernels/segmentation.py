"""Pallas Segmentation — the paper's 3-D Map benchmark.

Gray-scale volume -> {black, gray, white} by two thresholds.  The
elementary partitioning unit is one (D2 x D3) plane, and planes lie on
axis 0, as in the paper workload (``benchmarks/paper_suite.py``).  The
grid walks the planes, and each plane in bands of ``BLOCK_ROWS`` rows,
so a block is (1, rows, D3) with the plane's rows and columns last.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: rows per band: a 256 x 1024 f32 band is 1 MiB, well inside VMEM
BLOCK_ROWS = 256


def _seg_kernel(vol_ref, o_ref, *, lo: float, hi: float):
    v = vol_ref[...]
    out = jnp.where(v < lo, 0.0, jnp.where(v > hi, 255.0, 128.0))
    o_ref[...] = out.astype(o_ref.dtype)


def segmentation(vol: jax.Array, *, lo: float = 85.0, hi: float = 170.0,
                 interpret: bool = False) -> jax.Array:
    """vol (planes, D2, D3) f32 -> segmented volume (plane-partitioned)."""
    D1, D2, D3 = vol.shape
    br = min(BLOCK_ROWS, D2)
    kernel = functools.partial(_seg_kernel, lo=lo, hi=hi)
    block = pl.BlockSpec((1, br, D3), lambda p, i: (p, i, 0))
    return pl.pallas_call(
        kernel,
        grid=(D1, -(-D2 // br)),
        in_specs=[block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((D1, D2, D3), vol.dtype),
        interpret=interpret,
    )(vol)
