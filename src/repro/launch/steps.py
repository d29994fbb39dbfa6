"""Jit-able train / prefill / decode step builders.

These are the functions the dry-run lowers and the trainer/server drive.
All distribution is expressed through in/out shardings + the activation
constraints inside the model code; the steps themselves are mesh-agnostic.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.losses import soft_trimmed_token_loss
from repro.models import transformer as T
from repro.optim import adamw
from repro.optim.compression import ef_int8_roundtrip

Array = jax.Array


# The MoE counters a step returns beside its loss (``T.forward_train``).
MOE_METRICS = ("moe_assignments_held", "moe_busiest_share")


def loss_from_batch(cfg, params, batch) -> tuple[Array, dict[str, Array]]:
  with jax.named_scope("repro_forward_train"):
    token_losses, stats = T.forward_train(cfg, params, batch)
  if cfg.loss_trim_fraction > 0:
    # Paper §6.4 at LM scale: soft least-trimmed-squares over per-token
    # losses, applied per sequence (bounded PAV length; DESIGN.md §4).
    with jax.named_scope("repro_soft_lts_loss"):
      loss = jnp.mean(soft_trimmed_token_loss(
          token_losses.reshape(token_losses.shape[0], -1),
          cfg.loss_trim_fraction, cfg.loss_trim_eps))
  else:
    loss = jnp.mean(token_losses)
  aux = stats["aux_loss"]
  metrics = {"loss": loss, "aux_loss": aux}
  if cfg.num_experts:
    metrics.update({k: stats[k] for k in MOE_METRICS})
  return loss + 0.01 * aux, metrics


def make_train_step(cfg, opt_cfg: adamw.AdamWConfig, *,
                    lr_schedule=None, compress_grads: bool = False):
  """(params, opt_state, batch) -> (params, opt_state, metrics).

  Gradient accumulation: the global batch is split into ``cfg.grad_accum``
  microbatches scanned sequentially (activation memory / accum trade);
  grads are averaged in f32.
  """

  def grads_of(params, batch):
    return jax.value_and_grad(
        lambda p: loss_from_batch(cfg, p, batch), has_aux=True)(params)

  def train_step(params, opt_state, batch):
    accum = cfg.grad_accum
    if accum > 1:
      def micro(mb):
        return jax.tree.map(
            lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
            mb)

      mbatches = micro(batch)

      def body(carry, mb):
        gsum, msum = carry
        (_, metrics), g = grads_of(params, mb)
        gsum = jax.tree.map(
            lambda a, b: a + b.astype(a.dtype), gsum, g)
        msum = T.add_stats(msum, metrics)
        return (gsum, msum), None

      acc_dt = jnp.dtype(getattr(cfg, "grad_accum_dtype", "float32"))
      gzero = jax.tree.map(
          lambda p: jnp.zeros(p.shape, acc_dt), params)
      keys = ("loss",) + (MOE_METRICS if cfg.num_experts else ())
      mzero = {k: jnp.zeros((), jnp.float32) for k in keys}
      (gsum, metrics), _ = jax.lax.scan(body, (gzero, mzero), mbatches)
      grads = jax.tree.map(lambda g: (g / accum).astype(cfg.dtype), gsum)
      metrics = {**metrics, "loss": metrics["loss"] / accum,
                 "aux_loss": jnp.zeros((), jnp.float32)}
    else:
      (_, metrics), grads = grads_of(params, batch)

    if compress_grads:
      # int8 + error feedback wire format (cross-pod reduction model).
      grads, new_resid = ef_int8_roundtrip(grads, opt_state["ef_residual"])

    lr_scale = (lr_schedule(opt_state["adam"]["step"])
                if lr_schedule else 1.0)
    with jax.named_scope("repro_optimizer_update"):
      new_params, new_adam, opt_metrics = adamw.update(
          opt_cfg, grads, opt_state["adam"], params, lr_scale)
    new_opt = {"adam": new_adam}
    if compress_grads:
      new_opt["ef_residual"] = new_resid
    metrics = {**metrics, **opt_metrics}
    return new_params, new_opt, metrics

  return train_step


def init_opt_state(cfg, opt_cfg, params, *, compress_grads: bool = False):
  state = {"adam": adamw.init(opt_cfg, params)}
  if compress_grads:
    state["ef_residual"] = jax.tree.map(
        lambda p: jnp.zeros(p.shape, p.dtype), params)
  return state


def make_prefill_step(cfg, max_len: int | None = None):
  def prefill(params, batch):
    s = (batch["embeds"].shape[1] if cfg.frontend == "audio"
         else batch["tokens"].shape[1] + (
             cfg.num_patches if cfg.frontend == "vision" else 0))
    return T.forward_prefill(cfg, params, batch, max_len or s)
  return prefill


def make_decode_step(cfg):
  def decode(params, caches, inputs, pos):
    return T.forward_decode(cfg, params, caches, inputs, pos)
  return decode
