"""Train-step builder: microbatching, remat, sharding, compression.

``make_train_step`` assembles the jitted step for any assigned
architecture from the runtime knobs the autotuner searches over
(EXPERIMENTS.md §Perf):

  * ``microbatches``  — gradient accumulation via ``lax.scan`` over batch
    slices.  This is the paper's GPU *overlap factor* mapped to TPU: with
    M in-flight microbatches XLA overlaps microbatch k's gradient
    collectives with microbatch k+1's compute (latency hiding), and the
    per-step activation footprint divides by M.
  * ``remat``         — activation-checkpoint policy on the scanned layer
    body ("none" | "dots" | "dots_no_batch" | "full").
  * ``loss_chunks``   — seq-chunked unembed+loss (never materialise B,S,V).

``make_dp_train_step_int8`` is the explicit-collective data-parallel
variant: the gradient sync runs inside ``shard_map`` with int8 + error
feedback on the wire (4x fewer collective bytes — the beyond-paper
collective-term reducer of §Perf).

Both steps are pure ``(state, batch) -> (state, metrics)`` and donate-safe
on ``state``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.lm import forward_backbone
from repro.optim.adamw import AdamW, OptState
from repro.optim.compress import (CompressionState, compress_gradients,
                                  decompress_sum, init_compression,
                                  shared_scale)
from repro.runtime.loss import chunked_xent

REMAT_POLICIES: Dict[Optional[str], Any] = {
    None: None,
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    compression: Optional[CompressionState] = None


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """The runtime knobs — one point of the §Perf search space."""

    microbatches: int = 1
    remat: Optional[str] = "dots_no_batch"
    remat_group: int = 1               # checkpoint every k layers
    remat_inner: Optional[str] = None  # per-layer policy inside a group
                                       # (None = same as ``remat``)
    loss_chunks: int = 1
    aux_weight: float = 0.01           # MoE load-balance loss weight
    data_axes: Tuple[str, ...] = ("data",)   # axes the batch is sharded over
    act_spec: Any = None               # PartitionSpec pinned on the residual
                                       # stream at every layer (see lm.py)


def init_state(params: Any, optimizer: AdamW, *,
               compress: bool = False) -> TrainState:
    return TrainState(params=params, opt=optimizer.init(params),
                      compression=init_compression(params) if compress
                      else None)


def make_loss_fn(cfg: ModelConfig, rt: RuntimeConfig):
    def loss_fn(params, tokens, labels, extras):
        x, aux = forward_backbone(
            params, cfg, tokens,
            remat_policy=REMAT_POLICIES[rt.remat],
            act_spec=rt.act_spec, remat_group=rt.remat_group,
            remat_inner_policy=REMAT_POLICIES[rt.remat_inner],
            **extras)
        tot, cnt = chunked_xent(x, params, cfg, labels,
                                chunks=rt.loss_chunks)
        loss = tot / jnp.maximum(cnt, 1.0)
        return loss + rt.aux_weight * aux, (loss, aux)

    return loss_fn


def _accumulate_grads(loss_fn, params, batch: Dict[str, jax.Array],
                      rt: RuntimeConfig):
    """Gradient accumulation over microbatches (scan => activations are
    per-microbatch; XLA pipelines collective/compute across iterations).

    The batch is *reshaped* to (M, B/M, ...) and consumed as the scan's
    xs — never dynamically sliced along the sharded batch dim, which
    would force an all-gather of the whole batch on every microbatch.
    The per-microbatch batch dim keeps the data-axis sharding via an
    explicit constraint (PartitionSpec-only form, mesh from context).
    """
    tokens, labels = batch["tokens"], batch["labels"]
    extras = {k: v for k, v in batch.items()
              if k not in ("tokens", "labels")}
    M = rt.microbatches
    B = tokens.shape[0]
    if M <= 1 or B % M:
        (_, (loss, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, tokens, labels, extras)
        return grads, loss, aux

    def to_mb(v):
        r = v.reshape((M, B // M) + v.shape[1:])
        spec = P(None, rt.data_axes) if rt.data_axes else P()
        try:
            return jax.lax.with_sharding_constraint(r, spec)
        except (ValueError, RuntimeError, TypeError):
            return r        # off-mesh (single-device tests)

    xs = (to_mb(tokens), to_mb(labels),
          {k: to_mb(v) for k, v in extras.items()})

    def step(carry, mb):
        g_acc, l_acc, a_acc = carry
        mb_tokens, mb_labels, mb_extras = mb
        (_, (loss, aux)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(params, mb_tokens, mb_labels, mb_extras)
        g_acc = jax.tree.map(
            lambda a, b: a + b.astype(jnp.float32), g_acc, g)
        return (g_acc, l_acc + loss, a_acc + aux), None

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (g, l, a), _ = jax.lax.scan(
        step, (g0, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        xs)
    inv = 1.0 / M
    return jax.tree.map(lambda x: x * inv, g), l * inv, a * inv


def make_train_step(cfg: ModelConfig, optimizer: AdamW,
                    rt: RuntimeConfig = RuntimeConfig()):
    """Build the (un-jitted) GSPMD train step; callers jit with shardings."""
    loss_fn = make_loss_fn(cfg, rt)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        grads, loss, aux = _accumulate_grads(loss_fn, state.params, batch, rt)
        params, opt, gnorm = optimizer.update(grads, state.opt, state.params)
        metrics = {"loss": loss, "aux_loss": aux, "grad_norm": gnorm,
                   "lr": optimizer.config.lr_at(opt.step)}
        return TrainState(params, opt, state.compression), metrics

    return train_step


# ---------------------------------------------------------------------------
# Explicit-DP step with int8 + error-feedback gradient sync
# ---------------------------------------------------------------------------

def make_dp_train_step_int8(cfg: ModelConfig, optimizer: AdamW,
                            rt: RuntimeConfig, mesh: Mesh,
                            axis: str = "data"):
    """Pure data-parallel step with the gradient sync under our control.

    Params/opt state replicated; batch sharded over ``axis``.  Each shard
    computes its local gradient, agrees on a per-tensor scale (pmax),
    quantises to int8, psums in int32, and decodes the exact mean of the
    quantised gradients — wire bytes/step drop from 4·P to ~1·P.  The
    per-shard quantisation error is carried in the error-feedback state so
    the accumulated update stays unbiased.
    """
    from jax import shard_map

    loss_fn = make_loss_fn(cfg, rt)
    n = mesh.shape[axis]

    def shard_fn(params, err, tokens, labels):
        grads, loss, aux = _accumulate_grads(
            loss_fn, params, {"tokens": tokens, "labels": labels}, rt)
        st = CompressionState(error=err)
        scales = shared_scale(grads, st, axis=axis)
        q, st = compress_gradients(grads, st, scales)
        q_sum = jax.tree.map(
            lambda x: jax.lax.psum(x.astype(jnp.int32), axis), q)
        mean_g = decompress_sum(q_sum, scales, n)
        return mean_g, st.error, jax.lax.pmean(loss, axis), \
            jax.lax.pmean(aux, axis)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        rep = jax.tree.map(lambda _: P(), state.params)
        data = P(axis)
        grads, err, loss, aux = shard_map(
            shard_fn, mesh=mesh,
            in_specs=(rep, rep, data, data),
            out_specs=(rep, rep, P(), P()),
            check_vma=False)(state.params, state.compression.error,
                             batch["tokens"], batch["labels"])
        params, opt, gnorm = optimizer.update(grads, state.opt, state.params)
        metrics = {"loss": loss, "aux_loss": aux, "grad_norm": gnorm,
                   "lr": optimizer.config.lr_at(opt.step)}
        return TrainState(params, opt, CompressionState(error=err)), metrics

    return train_step
