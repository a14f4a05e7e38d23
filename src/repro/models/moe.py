"""Mixture-of-Experts FFN: top-k router + sort-based capacity dispatch.

TPU-native dispatch (no per-expert ragged loops): tokens are argsorted by
expert assignment, gathered into an expert-contiguous (E, C, d) buffer,
processed by a *grouped* batched GEMM (the Pallas ``moe_gemm`` kernel on
TPU; jnp einsum oracle here), and scattered back with router weights.
Tokens beyond an expert's capacity C = ceil(cf * k * N / E) are dropped
(standard Switch/GShard semantics).

Sharding: expert weights are (E, d, f) with f over the *model* axis (TP
inside each expert) and optionally d over *data* (FSDP); the token
dispatch stays on the batch axes, so the only cross-device traffic the
layer adds is the f-contraction all-reduce — the SCT edge stays
sharding-stable per the locality rule.
"""
from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import Defs, ParamDef, activate, softcap

#: trace-time context selecting the distributed MoE path: (mesh, dp, tp)
_MOE_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "moe_mesh", default=None)


@contextlib.contextmanager
def moe_mesh(mesh: Mesh, dp_axes=("data",), tp_axis: str = "model"):
    """While active, ``moe_ffn`` dispatches tokens *locally* per data
    shard inside ``shard_map`` (per-shard capacity + sort — no global
    argsort collectives), all-gathers the FSDP-sharded expert weights per
    layer (ZeRO-3 style), and psums the f-contraction over the model
    axis.  This is the locality-aware decomposition applied to the MoE
    edge (DESIGN.md §Arch-applicability)."""
    tok = _MOE_MESH.set((mesh, tuple(dp_axes), tp_axis))
    try:
        yield
    finally:
        _MOE_MESH.reset(tok)


def moe_defs(cfg: ModelConfig) -> Defs:
    m = cfg.moe
    d = cfg.d_model
    defs: Defs = {
        "router": ParamDef((d, m.n_experts), ("embed", "experts")),
        "w_in": ParamDef((m.n_experts, d, m.d_ff),
                         ("experts", "embed", "expert_mlp")),
        "w_out": ParamDef((m.n_experts, m.d_ff, d),
                          ("experts", "expert_mlp", "embed")),
    }
    if cfg.gated_mlp:
        defs["w_gate"] = ParamDef((m.n_experts, d, m.d_ff),
                                  ("experts", "embed", "expert_mlp"))
    return defs


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    m = cfg.moe
    c = int(math.ceil(m.capacity_factor * m.top_k * n_tokens / m.n_experts))
    return max(8, -(-c // 8) * 8)      # pad to an 8-multiple (VPU sublane)


def route(x2d: jax.Array, p: Defs, cfg: ModelConfig
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router: (N,d) -> top-k (weights (N,k), experts (N,k), aux loss)."""
    m = cfg.moe
    logits = x2d.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    logits = softcap(logits, m.router_softcap)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (Switch): E * sum(f_e * p_e)
    me = probs.mean(0)
    one = jax.nn.one_hot(idx[:, 0], m.n_experts, dtype=jnp.float32)
    ce = one.mean(0)
    aux = m.n_experts * jnp.sum(me * ce)
    return w.astype(x2d.dtype), idx, aux


def moe_ffn(x: jax.Array, p: Defs, cfg: ModelConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,d) -> (y, aux_loss).

    Under an active :func:`moe_mesh` context the distributed
    (shard_map) path runs; otherwise the single-shard sort-based
    dispatch below."""
    ctx = _MOE_MESH.get()
    if ctx is not None:
        return _moe_ffn_sharded(x, p, cfg, *ctx)
    return _moe_ffn_local(x, p, cfg)


def _moe_ffn_local(x: jax.Array, p: Defs, cfg: ModelConfig
                   ) -> Tuple[jax.Array, jax.Array]:
    """Sort-based capacity dispatch over the tokens visible locally."""
    m = cfg.moe
    B, S, d = x.shape
    N = B * S
    C = capacity(cfg, N)
    x2 = x.reshape(N, d)
    w, idx, aux = route(x2, p, cfg)                     # (N,k)

    K = m.top_k
    flat_expert = idx.reshape(-1)                       # (N*K,)
    flat_token = jnp.repeat(jnp.arange(N), K)           # token of each slot
    flat_w = w.reshape(-1)

    order = jnp.argsort(flat_expert)                    # expert-contiguous
    tok_sorted = flat_token[order]
    exp_sorted = flat_expert[order]
    w_sorted = flat_w[order]
    # position of each slot within its expert group
    ones = jnp.ones_like(exp_sorted)
    pos_in_expert = jnp.cumsum(ones) - 1
    seg_start = jnp.searchsorted(exp_sorted, jnp.arange(m.n_experts))
    pos_in_expert = pos_in_expert - seg_start[exp_sorted]
    keep = pos_in_expert < C                            # capacity drop
    dest = exp_sorted * C + jnp.where(keep, pos_in_expert, 0)

    # gather tokens into (E*C, d); dropped slots contribute zeros
    xg = jnp.zeros((m.n_experts * C, d), x.dtype)
    src = x2[tok_sorted] * keep[:, None].astype(x.dtype)
    xg = xg.at[dest].add(src)                           # unique dests (<=1 add)
    xe = xg.reshape(m.n_experts, C, d)

    h = jnp.einsum("ecd,edf->ecf", xe, p["w_in"])
    if "w_gate" in p:
        h = activate(h, cfg.activation) * jnp.einsum(
            "ecd,edf->ecf", xe, p["w_gate"])
    else:
        h = activate(h, cfg.activation)
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_out"])      # (E,C,d)

    # scatter back, weighted
    y_slots = ye.reshape(m.n_experts * C, d)[dest]      # (N*K, d)
    y_slots = y_slots * (w_sorted * keep.astype(w_sorted.dtype))[:, None]
    y2 = jnp.zeros((N, d), x.dtype).at[tok_sorted].add(
        y_slots.astype(x.dtype))
    return y2.reshape(B, S, d), aux


def moe_ffn_dense(x: jax.Array, p: Defs, cfg: ModelConfig
                  ) -> Tuple[jax.Array, jax.Array]:
    """Dense (no-drop) oracle: every expert sees every token, masked combine.

    O(E/k) more FLOPs — used only as the correctness reference in tests.
    """
    m = cfg.moe
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    w, idx, aux = route(x2, p, cfg)
    comb = jnp.zeros((B * S, m.n_experts), x.dtype)
    for j in range(m.top_k):
        comb = comb + jax.nn.one_hot(idx[:, j], m.n_experts,
                                     dtype=x.dtype) * w[:, j:j + 1]
    h = jnp.einsum("nd,edf->enf", x2, p["w_in"])
    if "w_gate" in p:
        h = activate(h, cfg.activation) * jnp.einsum(
            "nd,edf->enf", x2, p["w_gate"])
    else:
        h = activate(h, cfg.activation)
    ye = jnp.einsum("enf,efd->end", h, p["w_out"])
    y = jnp.einsum("end,ne->nd", ye, comb)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Distributed MoE: per-shard dispatch + expert tensor parallelism
# ---------------------------------------------------------------------------

def _moe_ffn_sharded(x: jax.Array, p: Defs, cfg: ModelConfig,
                     mesh: Mesh, dp: Tuple[str, ...], tp: str
                     ) -> Tuple[jax.Array, jax.Array]:
    """shard_map MoE:

      * tokens stay on their data shard — routing, capacity and the
        dispatch sort are **local** (the global argsort of the GSPMD path
        costs an all-to-all of every activation; locality-aware
        decomposition says move the experts' weights instead);
      * expert weights arrive (E, d/dp, f/tp): the d (FSDP) dim is
        all-gathered per layer (backward = reduce-scatter), the f dim
        stays tensor-parallel;
      * the f-contraction partial sums psum over the model axis — the
        single collective the MoE edge fundamentally requires.
    """
    m = cfg.moe
    has_gate = "w_gate" in p
    dp = tuple(a for a in dp if a in mesh.shape)
    tp_in_mesh = tp in mesh.shape
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]

    n_batch = x.shape[0]
    batch_axes = dp if (dp and n_batch % max(n_dp, 1) == 0) else None
    xspec = P(batch_axes, None, None)    # decode B=1: tokens replicated
    d_model = x.shape[-1]
    E, f = m.n_experts, m.d_ff

    def wspec(*dims):
        # replicate any dim whose mesh axes do not divide it
        out = []
        for size, cand in dims:
            if cand is None:
                out.append(None)
                continue
            axes = (cand,) if isinstance(cand, str) else tuple(cand)
            sz = 1
            for a in axes:
                sz *= mesh.shape.get(a, 1)
            ok = all(a in mesh.shape for a in axes) and size % sz == 0
            out.append(cand if ok else None)
        return P(*out)

    in_spec = wspec((E, None), (d_model, dp or None),
                    (f, tp if tp_in_mesh else None))           # w_in/gate
    out_spec_w = wspec((E, None), (f, tp if tp_in_mesh else None),
                       (d_model, dp or None))                  # w_out
    rspec = P()                                                # router

    def body(xl, rw, wi, wg, wo):
        # gather the FSDP (d) dim of the expert weights for this layer
        if dp and in_spec[1] is not None:
            wi = jax.lax.all_gather(wi, dp, axis=1, tiled=True)
            if has_gate:
                wg = jax.lax.all_gather(wg, dp, axis=1, tiled=True)
        if dp and out_spec_w[2] is not None:
            wo = jax.lax.all_gather(wo, dp, axis=2, tiled=True)
        pl = {"router": rw, "w_in": wi, "w_out": wo}
        if has_gate:
            pl["w_gate"] = wg
        y, aux = _moe_ffn_local(xl, pl, cfg)
        if tp_in_mesh:
            y = jax.lax.psum(y, tp)
        if dp and batch_axes is not None:
            aux = jax.lax.pmean(aux, dp)
        return y, aux

    wg_arg = p.get("w_gate", p["w_in"])      # placeholder when ungated
    y, aux = shard_map(
        body, mesh=mesh,
        in_specs=(xspec, rspec, in_spec, in_spec, out_spec_w),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, p["router"], p["w_in"], wg_arg, p["w_out"])
    return y, aux
