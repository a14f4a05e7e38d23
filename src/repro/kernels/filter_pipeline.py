"""Pallas fused Filter Pipeline — the paper's Pipeline benchmark.

Gaussian-noise -> Solarize -> Mirror over an image, fused into one kernel
(the paper composes them as three SCT stages; the locality-aware
decomposition keeps the intermediate images on-device, which on TPU
collapses to VMEM-resident fusion).  The elementary partitioning unit is
the image *line* (paper Sec. 4).

Mirror is split in two: the grid reads column block ``nc - 1 - j`` for
output block ``j`` (the coarse reversal), and each block is reversed in
its lanes with a gather (Mosaic has no lowering for ``lax.rev``).  Column
blocks are 128 lanes wide when the width allows it, else the whole row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _filter_kernel(img_ref, seed_ref, o_ref, *, noise_scale: float,
                   solarize_threshold: float, n_col_blocks: int):
    rows = img_ref[...]                               # (block_rows, bw) f32
    br, bw = rows.shape
    # gaussian-ish noise: 2 uniform hashes -> irwin-hall(2) approximation,
    # keyed on the *input* pixel's global (row, col)
    r, j = pl.program_id(0), pl.program_id(1)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) + r * br
    col_ids = (jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
               + (n_col_blocks - 1 - j) * bw)
    seed = seed_ref[0]

    def hash01(salt):
        h = (row_ids * -1640531535 + col_ids * 40503 + seed * 69069
             + salt * 1013904223)
        h ^= h >> 13
        h = h * 1274126177
        h ^= h >> 16
        return (h & 0xFFFF).astype(jnp.float32) / 65535.0

    noise = (hash01(1) + hash01(2) - 1.0) * noise_scale
    v = jnp.clip(rows + noise, 0.0, 255.0)
    # solarize
    v = jnp.where(v > solarize_threshold, 255.0 - v, v)
    # mirror within the block (the index maps reversed the blocks)
    lane_rev = bw - 1 - jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    o_ref[...] = jnp.take_along_axis(v, lane_rev, axis=1).astype(o_ref.dtype)


def filter_pipeline(img: jax.Array, seed: int = 0, *,
                    noise_scale: float = 8.0,
                    solarize_threshold: float = 128.0,
                    block_rows: int = 64,
                    interpret: bool = False) -> jax.Array:
    """img (H, W) float32 in [0, 255] -> filtered (H, W)."""
    H, W = img.shape
    br = min(block_rows, H)
    nb = -(-H // br)
    pad = nb * br - H
    if pad:
        img = jnp.pad(img, ((0, pad), (0, 0)))
    bw = LANES if W % LANES == 0 else W
    nc = W // bw
    kernel = functools.partial(_filter_kernel, noise_scale=noise_scale,
                               solarize_threshold=solarize_threshold,
                               n_col_blocks=nc)
    out = pl.pallas_call(
        kernel,
        grid=(nb, nc),
        in_specs=[pl.BlockSpec((br, bw), lambda i, j: (i, nc - 1 - j)),
                  pl.BlockSpec((1,), lambda i, j: (0,))],
        out_specs=pl.BlockSpec((br, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nb * br, W), img.dtype),
        interpret=interpret,
    )(img, jnp.asarray([seed], jnp.int32))
    return out[:H]
