"""Pallas N-Body direct-sum — the paper's Loop benchmark.

Each body interacts with every other: the dataset is COPY-mode (fully
replicated, paper Sec. 4), work is partitioned at *body* granularity.
Grid: (n_i_blocks, n_j_blocks), j innermost with an f32 VMEM accumulator;
i-bodies stay resident for a whole j sweep (the classic O(N²) tiling —
on TPU the j tile streams through the VPU at 8x128 lanes).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SOFTENING = 1e-3


def _nbody_kernel(pos_i_ref, mass_ref, pos_jt_ref, acc_out_ref, acc_ref):
    jb = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(jb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pi = pos_i_ref[...]                                  # (bi, 3)
    pjt = pos_jt_ref[...]                                # (3, bj)
    mj = mass_ref[...]                                   # (1, bj)
    # one (bi, bj) plane per component: bodies j on lanes, i on sublanes
    d = [pjt[c:c + 1, :] - pi[:, c:c + 1] for c in range(3)]
    r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2] + SOFTENING
    w = mj * (jax.lax.rsqrt(r2) / r2)
    acc_ref[...] += jnp.concatenate(
        [jnp.sum(w * dc, axis=1, keepdims=True) for dc in d], axis=1)

    @pl.when(jb == nj - 1)
    def _emit():
        acc_out_ref[...] = acc_ref[...].astype(acc_out_ref.dtype)


def nbody_accelerations(pos: jax.Array, mass: jax.Array, *,
                        block_i: int = 256, block_j: int = 1024,
                        interpret: bool = False) -> jax.Array:
    """pos (N, 3) f32, mass (N,) f32 -> accelerations (N, 3)."""
    N = pos.shape[0]
    bi, bj = min(block_i, N), min(block_j, N)
    ni, nj = -(-N // bi), -(-N // bj)
    pad_i, pad_j = ni * bi - N, nj * bj - N
    pos_i = jnp.pad(pos, ((0, pad_i), (0, 0))) if pad_i else pos
    pos_jt = (jnp.pad(pos, ((0, pad_j), (0, 0))) if pad_j else pos).T
    # padded m=0: no force
    mass_j = (jnp.pad(mass, (0, pad_j)) if pad_j else mass).reshape(1, -1)

    acc = pl.pallas_call(
        _nbody_kernel,
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((bi, 3), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bj), lambda i, j: (0, j)),
            pl.BlockSpec((3, bj), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bi, 3), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ni * bi, 3), pos.dtype),
        scratch_shapes=[pltpu.VMEM((bi, 3), jnp.float32)],
        interpret=interpret,
    )(pos_i, mass_j, pos_jt)
    return acc[:N]


def nbody_step(pos: jax.Array, vel: jax.Array, mass: jax.Array,
               dt: float = 0.01, *, interpret: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    """One leapfrog step (the paper's Loop body)."""
    acc = nbody_accelerations(pos, mass, interpret=interpret)
    vel = vel + acc * dt
    return pos + vel * dt, vel
