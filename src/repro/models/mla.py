"""Multi-head Latent Attention (DeepSeek-V2) — compressed KV cache.

Train/prefill use the expanded form; decode uses the *absorbed* form: W_uk
is folded into the query so attention runs directly against the cached
latent c_kv (rank 512) + shared RoPE key (64), which is the whole point of
MLA (cache bytes ~ (r + rope) per token instead of 2*H*Dh).

As published: the latent is RMS-normalised (``kv_norm``, DeepSeek-V2's
``kv_a_layernorm``) before ``w_uk``/``w_uv`` and before it is cached; with
``cfg.rope_yarn`` the rope dimensions take YaRN's frequencies and the
scores YaRN's temperature, ``(qk_nope + qk_rope)^-0.5 * m^2`` with
``m = yarn_get_mscale(factor, mscale_all_dim)``.  The rope dimensions are
rotated as halves (feature i with i + 32); the published code rotates
interleaved pairs, which is the same model under a fixed permutation of
the rope columns of ``wq`` and ``w_dkv``.  The train/prefill path runs
under the named scope ``repro_mla``.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import (flash_attention, norm_apply, rope,
                                 yarn_get_mscale)
from repro.sharding.specs import shard_activation

Array = jax.Array
Params = dict[str, Any]

_NEG_INF = -1e30


def mla_init(key, cfg, dtype) -> Params:
  d, h = cfg.d_model, cfg.num_heads
  r, nd, rd, vd = (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                   cfg.v_head_dim)
  ks = jax.random.split(key, 5)
  si = 1.0 / math.sqrt(d)
  sr = 1.0 / math.sqrt(r)
  return {
      "wq": (jax.random.normal(ks[0], (d, h, nd + rd)) * si).astype(dtype),
      "w_dkv": (jax.random.normal(ks[1], (d, r + rd)) * si).astype(dtype),
      "w_uk": (jax.random.normal(ks[2], (r, h, nd)) * sr).astype(dtype),
      "w_uv": (jax.random.normal(ks[3], (r, h, vd)) * sr).astype(dtype),
      "wo": (jax.random.normal(ks[4], (h, vd, d)) /
             math.sqrt(h * vd)).astype(dtype),
      "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
  }


def softmax_scale(cfg) -> float:
  """The scores' scale: 1/sqrt(qk width), times m^2 under YaRN."""
  scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
  y = cfg.rope_yarn
  if y is not None and y.mscale_all_dim:
    scale *= yarn_get_mscale(y.factor, y.mscale_all_dim) ** 2
  return scale


def _latent(p: Params, x: Array, positions: Array, cfg):
  """x -> (normalised latent c_kv, rotated shared rope key)."""
  r = cfg.kv_lora_rank
  ckv_full = jnp.einsum("...d,dr->...r", x, p["w_dkv"])
  c_kv = norm_apply(p["kv_norm"], ckv_full[..., :r], "rmsnorm")
  k_rope = rope(ckv_full[..., None, r:], positions, cfg.rope_theta,
                cfg.rope_yarn)[..., 0, :]
  return c_kv, k_rope


def mla_apply_seq(p: Params, x: Array, positions: Array, cfg, *,
                  return_kv: bool = False):
  """Expanded MLA for train/prefill. x: (B,S,d)."""
  with jax.named_scope("repro_mla"):
    return _mla_seq(p, x, positions, cfg, return_kv)


def _mla_seq(p, x, positions, cfg, return_kv):
  nd, rd = cfg.qk_nope_dim, cfg.qk_rope_dim
  q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
  q_nope, q_rope = q[..., :nd], q[..., nd:]
  q_rope = rope(q_rope, positions, cfg.rope_theta, cfg.rope_yarn)

  c_kv, k_rope = _latent(p, x, positions, cfg)
  k_rope = k_rope[..., None, :]                                 # (B,S,1,rd)

  k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"])
  v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"])

  h = cfg.num_heads
  k_rope_b = jnp.broadcast_to(k_rope, k_rope.shape[:2] + (h, rd))
  q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
  k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
  q_full = shard_activation(q_full, "heads")
  k_full = shard_activation(k_full, "heads")

  # V stays at v_head_dim (128): padding it to the 192-wide qk dim cost
  # 50% extra attention-output traffic+flops (§Perf deepseek iter d5).
  # Under full remat the query blocks are recomputed too: the backward
  # pass of 8k tokens would otherwise keep every block's probabilities,
  # 6.4 GB a layer.
  o = flash_attention(q_full, k_full, v, causal=True,
                      q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                      scale=softmax_scale(cfg),
                      remat_q_blocks=cfg.remat == "full")
  out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
  if return_kv:
    return out, {"c_kv": c_kv, "k_rope": k_rope[..., 0, :]}
  return out


def mla_init_cache(cfg, batch: int, max_len: int, dtype) -> Params:
  return {
      "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
      "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
  }


def mla_apply_decode(p: Params, x: Array, cache: Params, pos: Array, cfg):
  """Absorbed-form decode. x: (B,d); cache latents (B,S,r),(B,S,rd)."""
  nd = cfg.qk_nope_dim
  q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
  q_nope, q_rope = q[..., :nd], q[..., nd:]
  q_rope = rope(q_rope, pos, cfg.rope_theta, cfg.rope_yarn)

  c_new, kr_new = _latent(p, x, pos, cfg)
  c_cache = lax.dynamic_update_slice_in_dim(
      cache["c_kv"], c_new[:, None].astype(cache["c_kv"].dtype), pos, 1)
  kr_cache = lax.dynamic_update_slice_in_dim(
      cache["k_rope"], kr_new[:, None].astype(cache["k_rope"].dtype), pos, 1)

  # Absorb W_uk into q: q_lat (B,H,r) attends directly to latents.
  q_lat = jnp.einsum("bhk,rhk->bhr", q_nope, p["w_uk"])
  s_lat = jnp.einsum("bhr,bsr->bhs", q_lat, c_cache)
  s_rope = jnp.einsum("bhk,bsk->bhs", q_rope, kr_cache)
  s = (s_lat + s_rope).astype(jnp.float32) * softmax_scale(cfg)
  spos = jnp.arange(c_cache.shape[1])
  s = jnp.where((spos < pos + 1)[None, None], s, _NEG_INF)
  pw = jax.nn.softmax(s, axis=-1)
  # Attend over latents, then decompress once: (B,H,r) @ W_uv.
  o_lat = jnp.einsum("bhs,bsr->bhr", pw.astype(c_cache.dtype), c_cache)
  o = jnp.einsum("bhr,rhk->bhk", o_lat, p["w_uv"])
  out = jnp.einsum("bhk,hkd->bd", o, p["wo"])
  return out, {"c_kv": c_cache, "k_rope": kr_cache}
