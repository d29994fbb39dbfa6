"""Mixture-of-Experts FFN: one chip's share of the experts, dropless, under
a differentiable soft-top-k router.

The router is the framework's flagship integration of the paper: gate
masses come from the projection of logits onto the k-subset permutahedron
(``core.soft_topk_mask``), giving *dense, nonzero gradients to every
expert's logit*, unlike softmax-top-k whose gradient is zero for
unselected experts.  The experts a token goes to are the hard top-k of the
gate weights (straight-through: the weights carry the gradient).

The layer is told which experts it holds (``cfg.experts_held`` of them,
from id ``cfg.first_held_expert``; all by default), as a chip of an
expert-parallel deployment is.  It routes every token over all
``num_experts`` and computes the part of the result that its held experts
give, for every assignment that lands on them: no token is dropped.  The
assignments are sorted by held expert into a buffer that can hold every
assignment that can reach the held experts (``tokens * min(k, held)``
rows) and computed with grouped matrix products (``lax.ragged_dot``) over
the held experts only.  Rows move between token order and expert order by
whole-row gathers in both directions (the backward pass of one direction
is the other), never by element-wise gathers or scatters.  Shared experts
are computed for every token.

Routers:
  softmax_topk  — standard baseline (softmax over chosen experts)
  soft_topk     — paper technique (projection gate mass, straight-through)

Named scopes (what the trace reduction attributes device time to):
``repro_moe_router`` (logits, softmax, soft top-k, the top-k choice),
``repro_moe_dispatch`` (sort, row gathers, combine), ``repro_moe_experts``
(the held experts' grouped products), ``repro_moe_shared``.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.operators import soft_topk_mask
from repro.sharding.specs import current_rules, param_spec

Array = jax.Array
Params = dict[str, Any]


def moe_init(key, cfg, dtype) -> Params:
  d, f, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.num_experts
  held = cfg.num_experts_held
  ks = jax.random.split(key, 5)
  si, so = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
  p = {
      "router": jax.random.normal(ks[0], (d, e)).astype(jnp.float32) * si,
      "we_in": (jax.random.normal(ks[1], (held, d, f)) * si).astype(dtype),
      "we_gate": (jax.random.normal(ks[2], (held, d, f)) * si).astype(dtype),
      "we_out": (jax.random.normal(ks[3], (held, f, d)) * so).astype(dtype),
  }
  if cfg.num_shared_experts:
    fs = f * cfg.num_shared_experts
    k1, k2, k3 = jax.random.split(ks[4], 3)
    p["shared"] = {
        "w_in": (jax.random.normal(k1, (d, fs)) * si).astype(dtype),
        "w_gate": (jax.random.normal(k2, (d, fs)) * si).astype(dtype),
        "w_out": (jax.random.normal(k3, (fs, d)) * so).astype(dtype),
    }
  return p


def route(p: Params, xt: Array, cfg) -> tuple[Array, Array, Array]:
  """xt: (T, d) -> (chosen expert ids (T, k), their gates (T, k), router
  probs (T, E)), over all ``num_experts``."""
  k, e = cfg.experts_per_token, cfg.num_experts
  logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
  probs = jax.nn.softmax(logits, axis=-1)
  if cfg.router == "soft_topk":
    # Paper technique: differentiable top-k mass (sums to k per token),
    # with dense gradients to every expert logit.
    w = soft_topk_mask(logits, k, cfg.router_eps) * probs
  else:
    topv = lax.top_k(probs, k)[0]
    w = jnp.where(probs >= topv[..., -1:], probs, 0.0)
  if cfg.norm_topk_prob:
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
  ids = lax.top_k(lax.stop_gradient(w), k)[1]                  # (T, k)
  # The k gates by a one-hot product, not an element-wise gather.
  gates = jnp.einsum("tke,te->tk", jax.nn.one_hot(ids, e, dtype=w.dtype), w)
  return ids, gates, probs


def load_balance_loss(probs: Array, ids: Array) -> Array:
  """Switch-style auxiliary loss over all experts: E * <assignments per
  token, mean prob>, from this chip's own tokens."""
  e = probs.shape[-1]
  frac = jnp.mean(jnp.sum(jax.nn.one_hot(ids, e, dtype=probs.dtype), 1), 0)
  return e * jnp.sum(frac * jnp.mean(probs, axis=0))


# The buffer's rows past the held assignments (``n`` of them) belong to
# no expert: a grouped product leaves whatever it likes there (zeros on
# the CPU, undefined on the TPU), in its output and in its gradient, so
# both crossings zero them.


@jax.custom_vjp
def _rows_to_experts(xt: Array, order: Array, inv: Array, n: Array) -> Array:
  """Buffer row r holds token ``order[r] // k`` (assignment order[r])."""
  k = inv.shape[0] // xt.shape[0]
  return jnp.take(xt, order // k, axis=0)


def _rows_to_experts_fwd(xt, order, inv, n):
  return _rows_to_experts(xt, order, inv, n), (order, inv, n, xt.shape[0])


def _rows_to_experts_bwd(res, g):
  order, inv, n, t = res
  g = _rows_from_experts(g, order, inv, n)               # (T * k, d)
  return g.reshape(t, -1, g.shape[-1]).sum(1), None, None, None


@jax.custom_vjp
def _rows_from_experts(ys: Array, order: Array, inv: Array,
                       n: Array) -> Array:
  """Assignment a's row of the buffer (``inv[a]``), zero past the held
  assignments."""
  rows = jnp.arange(ys.shape[0])[:, None]
  ys = jnp.where(rows < n, ys, jnp.zeros((), ys.dtype))
  pad = jnp.zeros((inv.shape[0] - ys.shape[0], ys.shape[1]), ys.dtype)
  return jnp.take(jnp.concatenate([ys, pad]), inv, axis=0)


def _rows_from_experts_fwd(ys, order, inv, n):
  return _rows_from_experts(ys, order, inv, n), (order, inv)


def _rows_from_experts_bwd(res, g):
  order, inv = res
  return jnp.take(g, order, axis=0), None, None, None


_rows_to_experts.defvjp(_rows_to_experts_fwd, _rows_to_experts_bwd)
_rows_from_experts.defvjp(_rows_from_experts_fwd, _rows_from_experts_bwd)


def refuse_expert_sharded_mesh(w_in: Array) -> None:
  """Every chip computes all of its held experts, so a mesh that shards
  their weights by expert would gather every expert's weights into each
  product: expert parallelism across chips is refused, not silently lost."""
  rules = current_rules()
  if (rules is not None and rules.axis_size(rules.model_axis) > 1
      and param_spec(rules, "ffn/we_in", w_in.shape)[0] is not None):
    raise NotImplementedError(
        "the held-experts MoE layer has no expert parallelism across "
        f"chips: its {w_in.shape[0]} experts would be sharded over the "
        f"{rules.axis_size(rules.model_axis)}-way '{rules.model_axis}' axis")


def held_experts(p: Params, xt: Array, ids: Array, gates: Array, cfg):
  """The part of the layer's output that the held experts give, for every
  assignment routed to them.  Returns (out (T, d), assignments per held
  expert (held,))."""
  refuse_expert_sharded_mesh(p["we_in"])
  t, k = ids.shape
  held = cfg.num_experts_held
  with jax.named_scope("repro_moe_dispatch"):
    local = ids - cfg.first_held_expert
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held).reshape(-1)          # (T * k,)
    order = jnp.argsort(key, stable=True)
    inv = jnp.argsort(order)
    order = order[:t * min(k, held)]
    sizes = jnp.sum(key[:, None] == jnp.arange(held), 0, dtype=jnp.int32)
    n = jnp.sum(sizes)
    xs = _rows_to_experts(xt, order, inv, n)
  with jax.named_scope("repro_moe_experts"):
    h = lax.ragged_dot(xs, p["we_in"], sizes)
    g = lax.ragged_dot(xs, p["we_gate"], sizes)
    ys = lax.ragged_dot(jax.nn.silu(g) * h, p["we_out"], sizes)
  with jax.named_scope("repro_moe_dispatch"):
    y = _rows_from_experts(ys, order, inv, n).reshape(t, k, -1)
    w = jnp.where(is_held, gates, 0.0)
    out = jnp.sum(w[..., None] * y.astype(w.dtype), axis=1)
  return out.astype(xt.dtype), sizes


def moe_apply(p: Params, x: Array, cfg) -> tuple[Array, dict[str, Array]]:
  """x: (B,S,d) or (B,d) -> (same shape, stats).

  ``stats``: ``aux_loss`` (load balance over all experts),
  ``moe_assignments_held`` (assignments computed here) and
  ``moe_busiest_share`` (the busiest held expert's share of them)."""
  orig_shape = x.shape
  xt = x.reshape(-1, x.shape[-1])
  with jax.named_scope("repro_moe_router"):
    ids, gates, probs = route(p, xt, cfg)
  out, sizes = held_experts(p, xt, ids, gates, cfg)
  if "shared" in p:
    with jax.named_scope("repro_moe_shared"):
      sh = p["shared"]
      hs = jax.nn.silu(xt @ sh["w_gate"]) * (xt @ sh["w_in"])
      out = out + hs @ sh["w_out"]
  held = jnp.sum(sizes).astype(jnp.float32)
  stats = {"aux_loss": load_balance_loss(probs, ids),
           "moe_assignments_held": held,
           "moe_busiest_share": jnp.max(sizes) / jnp.maximum(held, 1.0)}
  return out.reshape(orig_shape), stats
