"""Public wrappers for the Pallas kernels.

Every wrapper compiles its kernel for the TPU unless the caller passes
``interpret=True``, which runs the kernel body in Python with real block
indexing (BlockSpecs, grids, scratch) on any backend — the CPU tests do
that.  Nothing here picks the mode from the backend: a kernel that cannot
compile fails, it does not fall back.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.filter_pipeline import filter_pipeline as _filter
from repro.kernels.moe_gemm import grouped_matmul as _gmm
from repro.kernels.nbody import nbody_accelerations as _nbody
from repro.kernels.nbody import nbody_step as _nbody_step
from repro.kernels.saxpy import saxpy as _saxpy
from repro.kernels.segmentation import segmentation as _seg
from repro.kernels.ssd_scan import ssd_scan as _ssd


def flash_attention(q, k, v, *, interpret: bool = False, **kw):
    """(B,H,S,hd) x (B,KV,S,hd) flash attention (GQA/causal/SWA/softcap)."""
    return _flash(q, k, v, interpret=interpret, **kw)


def flash_attention_bshd(q, k, v, *, interpret: bool = False, **kw):
    """Model-layout adapter: (B,S,H,hd)/(B,S,KV,hd) in and out."""
    o = _flash(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
               v.transpose(0, 2, 1, 3), interpret=interpret, **kw)
    return o.transpose(0, 2, 1, 3)


def ssd_scan(x, dt, B, C, A, *, chunk: int, h0=None,
             interpret: bool = False):
    return _ssd(x, dt, B, C, A, chunk=chunk, h0=h0, interpret=interpret)


def grouped_matmul(x, w, *, interpret: bool = False, **kw):
    return _gmm(x, w, interpret=interpret, **kw)


def saxpy(a, x, y, *, interpret: bool = False, **kw):
    return _saxpy(jnp.asarray(a, x.dtype), x, y, interpret=interpret, **kw)


def filter_pipeline(img, seed: int = 0, *, interpret: bool = False, **kw):
    return _filter(img, seed, interpret=interpret, **kw)


def segmentation(vol, *, interpret: bool = False, **kw):
    return _seg(vol, interpret=interpret, **kw)


def nbody_accelerations(pos, mass, *, interpret: bool = False, **kw):
    return _nbody(pos, mass, interpret=interpret, **kw)


def nbody_step(pos, vel, mass, dt: float = 0.01, *, interpret: bool = False):
    return _nbody_step(pos, vel, mass, dt, interpret=interpret)
