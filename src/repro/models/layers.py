"""Shared model layers: norms, RoPE, MLPs, chunked flash attention, LM head.

Everything is einsum-based with explicit parameter pytrees (plain dicts) so
sharding specs attach by path.  Attention never materializes the full
(S x S) score matrix: queries are processed in ``q_chunk`` blocks with an
inner scan over ``kv_chunk`` blocks carrying running (max, denom, acc) —
flash-attention restated in pure JAX so XLA:TPU can keep blocks in VMEM.
Sliding-window layers scan only the window's kv blocks via dynamic slices,
making them O(S * W) (this is what qualifies gemma3/recurrentgemma local
layers for the 500k-token cell).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.sharding.specs import shard_activation

Array = jax.Array
Params = dict[str, Any]

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_init(d: int, kind: str) -> Params:
  p = {"scale": jnp.ones((d,), jnp.float32)}
  if kind == "layernorm":
    p["bias"] = jnp.zeros((d,), jnp.float32)
  return p


def norm_apply(p: Params, x: Array, kind: str, eps: float = 1e-6) -> Array:
  xf = x.astype(jnp.float32)
  if kind == "layernorm":
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]
  else:
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(ms + eps) * p["scale"]
  return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: Array, positions: Array, theta: float, yarn=None) -> Array:
  """x: (..., S, H, D) or (..., H, D) w/ scalar positions; rotate pairs
  (feature i with i + D/2).  ``yarn`` (a ``configs.base.Yarn``) rescales
  the frequencies and the rotation as YaRN does."""
  d = x.shape[-1]
  half = d // 2
  if yarn is None:
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
  else:
    freq = jnp.asarray(yarn_inv_freq(d, theta, yarn))
  ang = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
  cos, sin = jnp.cos(ang), jnp.sin(ang)
  if yarn is not None:
    mscale = (yarn_get_mscale(yarn.factor, yarn.mscale)
              / yarn_get_mscale(yarn.factor, yarn.mscale_all_dim))
    if mscale != 1.0:
      cos, sin = cos * mscale, sin * mscale
  cos = cos[..., None, :]  # broadcast over heads
  sin = sin[..., None, :]
  x1, x2 = x[..., :half], x[..., half:]
  out = jnp.concatenate(
      [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
  return out.astype(x.dtype)


def yarn_get_mscale(factor: float, mscale: float) -> float:
  """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
  return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
  """YaRN inverse frequencies of a ``dim``-wide rotary embedding (float32,
  ``dim // 2`` of them): the original frequencies where a dimension turns
  more than ``beta_fast`` times over the original context, ``1/factor`` of
  them where it turns fewer than ``beta_slow`` times, a linear ramp
  between (DeepSeek-V2's ``yarn_find_correction_range``)."""
  def correction_dim(rotations):
    return (dim * math.log(yarn.original_max_position
                           / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))
  low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
  high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
  if low == high:
    high += 0.001
  extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
  inter = extra / yarn.factor
  ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                 / (high - low), 0, 1)
  return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------


def mlp_init(key, d: int, f: int, variant: str, dtype) -> Params:
  k1, k2, k3 = jax.random.split(key, 3)
  scale_in = 1.0 / math.sqrt(d)
  scale_out = 1.0 / math.sqrt(f)
  p = {
      "w_in": (jax.random.normal(k1, (d, f)) * scale_in).astype(dtype),
      "w_out": (jax.random.normal(k2, (f, d)) * scale_out).astype(dtype),
  }
  if variant in ("swiglu", "geglu"):
    p["w_gate"] = (jax.random.normal(k3, (d, f)) * scale_in).astype(dtype)
  return p


def mlp_apply(p: Params, x: Array, variant: str) -> Array:
  h = jnp.einsum("...d,df->...f", x, p["w_in"])
  if variant == "swiglu":
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    h = jax.nn.silu(g) * h
  elif variant == "geglu":
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    h = jax.nn.gelu(g, approximate=True) * h
  else:
    h = jax.nn.gelu(h, approximate=True)
  if h.ndim == 3:
    h = shard_activation(h, "ffn")
  return jnp.einsum("...f,fd->...d", h, p["w_out"])


# ---------------------------------------------------------------------------
# Chunked flash attention (GQA, causal, optional sliding window)
# ---------------------------------------------------------------------------


def _attend_block(q, k, v, mask, scale, softcap):
  """q: (B,cq,Hkv,G,D)  k/v: (B,ckv,Hkv,D)  mask: (cq,ckv) bool."""
  s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32) * scale
  if softcap > 0.0:
    s = jnp.tanh(s / softcap) * softcap
  s = jnp.where(mask[None, None, None], s, _NEG_INF)
  # The running max only keeps exp in range: the output does not depend on
  # it, so it carries no gradient.  Differentiating max instead divides by
  # the count of entries equal to it, which XLA:TPU can make 0 when the
  # backward pass recomputes s with different rounding (NaN grads).
  m = lax.stop_gradient(jnp.max(s, axis=-1))        # (B,Hkv,G,cq)
  p = jnp.exp(s - m[..., None])
  l = jnp.sum(p, axis=-1)
  o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v)
  return m, l, o


def _merge(m1, l1, o1, m2, l2, o2):
  m = jnp.maximum(m1, m2)
  a1 = jnp.exp(m1 - m)
  a2 = jnp.exp(m2 - m)
  l = l1 * a1 + l2 * a2
  o = o1 * a1[..., None].astype(o1.dtype) + o2 * a2[..., None].astype(o2.dtype)
  return m, l, o


def flash_attention(
    q: Array, k: Array, v: Array, *,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    softcap: float = 0.0,
    q_offset: int | Array = 0,
    scale: float | None = None,
    remat_q_blocks: bool = False,
) -> Array:
  """Chunked attention. q: (B,Sq,H,D); k,v: (B,Skv,Hkv,D) -> (B,Sq,H,D).

  `q_offset`: global position of q[0] relative to k[0] (prefill continuation
  / decode). With `window > 0` only kv blocks inside the window are visited
  (O(S*W)); otherwise all kv blocks are scanned with causal masking.
  `scale` multiplies the scores (default 1/sqrt(D)).  With
  `remat_q_blocks` each query block is recomputed in the backward pass,
  which then holds one block's probabilities instead of every block's.
  """
  b, sq, h, d = q.shape
  _, skv, hkv, _ = k.shape
  dv = v.shape[-1]          # may differ from d (MLA: un-padded values)
  g = h // hkv
  if scale is None:
    scale = 1.0 / math.sqrt(d)
  q_chunk = min(q_chunk, sq)
  kv_chunk = min(kv_chunk, skv)
  while sq % q_chunk:
    q_chunk -= 1
  while skv % kv_chunk:
    kv_chunk -= 1
  nq, nkv = sq // q_chunk, skv // kv_chunk
  qg = q.reshape(b, sq, hkv, g, d)

  def one_q_block(qi, q_blk):
    """q_blk: (B,cq,Hkv,G,D); returns (B,cq,Hkv,G,D)."""
    q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

    m0 = jnp.full((b, hkv, g, q_chunk), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, q_chunk), jnp.float32)
    o0 = jnp.zeros((b, hkv, g, q_chunk, dv), v.dtype)

    if window > 0:
      # visit only blocks overlapping [q_lo - window + 1, q_hi]
      w_blocks = window // kv_chunk + 2
      first = (q_offset + qi * q_chunk - window) // kv_chunk

      def body(carry, j):
        m, l, o = carry
        blk = jnp.clip(first + j, 0, nkv - 1)
        k_blk = lax.dynamic_slice_in_dim(k, blk * kv_chunk, kv_chunk, 1)
        v_blk = lax.dynamic_slice_in_dim(v, blk * kv_chunk, kv_chunk, 1)
        kv_pos = blk * kv_chunk + jnp.arange(kv_chunk)
        mask = (kv_pos[None, :] <= q_pos[:, None]) & (
            kv_pos[None, :] > q_pos[:, None] - window) & (
            (first + j) >= 0)
        mb, lb, ob = _attend_block(q_blk, k_blk, v_blk, mask, scale, softcap)
        return _merge(m, l, o, mb, lb, ob), None

      (m, l, o), _ = lax.scan(body, (m0, l0, o0), jnp.arange(w_blocks))
    else:
      def body(carry, j):
        m, l, o = carry
        k_blk = lax.dynamic_slice_in_dim(k, j * kv_chunk, kv_chunk, 1)
        v_blk = lax.dynamic_slice_in_dim(v, j * kv_chunk, kv_chunk, 1)
        kv_pos = j * kv_chunk + jnp.arange(kv_chunk)
        if causal:
          mask = kv_pos[None, :] <= q_pos[:, None]
        else:
          mask = jnp.ones((q_chunk, kv_chunk), bool)
        mb, lb, ob = _attend_block(q_blk, k_blk, v_blk, mask, scale, softcap)
        return _merge(m, l, o, mb, lb, ob), None

      (m, l, o), _ = lax.scan(body, (m0, l0, o0), jnp.arange(nkv))

    out = o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype)
    return out.transpose(0, 3, 1, 2, 4)  # (B,cq,Hkv,G,D)

  if nq == 1:
    out = one_q_block(0, qg)
  else:
    qs = qg.reshape(b, nq, q_chunk, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)
    block = jax.checkpoint(one_q_block) if remat_q_blocks else one_q_block
    out = lax.map(lambda args: block(args[0], args[1]),
                  (jnp.arange(nq), qs))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, hkv, g, dv)
  return out.reshape(b, sq, h, dv)


def decode_attention(
    q: Array, k_cache: Array, v_cache: Array, cache_len: Array, *,
    window: int = 0, softcap: float = 0.0) -> Array:
  """Single-token attention. q: (B,H,D); caches: (B,S,Hkv,D) -> (B,H,D)."""
  b, h, d = q.shape
  _, s, hkv, _ = k_cache.shape
  g = h // hkv
  scale = 1.0 / math.sqrt(d)
  qg = q.reshape(b, hkv, g, d)
  s_ = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache).astype(jnp.float32) * scale
  if softcap > 0.0:
    s_ = jnp.tanh(s_ / softcap) * softcap
  pos = jnp.arange(s)
  valid = pos < cache_len
  if window > 0:
    valid &= pos > cache_len - 1 - window
  s_ = jnp.where(valid[None, None, None], s_, _NEG_INF)
  p = jax.nn.softmax(s_, axis=-1)
  o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache)
  return o.reshape(b, h, d)


# ---------------------------------------------------------------------------
# GQA attention layer (params + train/prefill/decode)
# ---------------------------------------------------------------------------


def attn_init(key, cfg, dtype) -> Params:
  d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
  k1, k2, k3, k4 = jax.random.split(key, 4)
  si = 1.0 / math.sqrt(d)
  so = 1.0 / math.sqrt(h * dh)
  return {
      "wq": (jax.random.normal(k1, (d, h, dh)) * si).astype(dtype),
      "wk": (jax.random.normal(k2, (d, hkv, dh)) * si).astype(dtype),
      "wv": (jax.random.normal(k3, (d, hkv, dh)) * si).astype(dtype),
      "wo": (jax.random.normal(k4, (h, dh, d)) * so).astype(dtype),
  }


def attn_apply_seq(
    p: Params, x: Array, positions: Array, cfg, *,
    window: int = 0, return_kv: bool = False):
  """Full-sequence attention (train / prefill). x: (B,S,d)."""
  q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
  k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
  v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
  q = shard_activation(rope(q, positions, cfg.rope_theta), "heads")
  k = shard_activation(rope(k, positions, cfg.rope_theta), "heads")
  o = flash_attention(
      q, k, v, causal=True, window=window,
      q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
  out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
  if return_kv:
    return out, (k, v)
  return out


def attn_apply_decode(
    p: Params, x: Array, cache: Params, pos: Array, cfg, *,
    window: int = 0):
  """One-token step. x: (B,d); cache: {k,v}: (B,S,Hkv,Dh)."""
  q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
  k = jnp.einsum("bd,dhk->bhk", x, p["wk"])
  v = jnp.einsum("bd,dhk->bhk", x, p["wv"])
  q = rope(q, pos, cfg.rope_theta)
  k = rope(k, pos, cfg.rope_theta)
  k_cache = lax.dynamic_update_slice_in_dim(
      cache["k"], k[:, None].astype(cache["k"].dtype), pos, 1)
  v_cache = lax.dynamic_update_slice_in_dim(
      cache["v"], v[:, None].astype(cache["v"].dtype), pos, 1)
  o = decode_attention(q, k_cache, v_cache, pos + 1, window=window)
  out = jnp.einsum("bhk,hkd->bd", o, p["wo"])
  return out, {"k": k_cache, "v": v_cache}


def attn_init_cache(cfg, batch: int, max_len: int, dtype) -> Params:
  shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
  return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# Embedding + chunked LM loss
# ---------------------------------------------------------------------------


def embed_init(key, vocab: int, d: int, dtype) -> Params:
  return {"table": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def embed_apply(p: Params, tokens: Array, scale: bool = False) -> Array:
  out = jnp.take(p["table"], tokens, axis=0)
  if scale:
    out = out * math.sqrt(out.shape[-1])
  return out


def lm_head_logits(w: Array, x: Array, softcap: float = 0.0) -> Array:
  logits = jnp.einsum("...d,dv->...v", x, w).astype(jnp.float32)
  if softcap > 0.0:
    logits = jnp.tanh(logits / softcap) * softcap
  return logits


def lm_loss_chunked(
    w: Array, x: Array, targets: Array, *,
    chunk: int = 1024, softcap: float = 0.0) -> Array:
  """Per-token NLL (B,S) without materializing (B,S,V): scan over S chunks."""
  b, s, d = x.shape
  chunk = min(chunk, s)
  while s % chunk:          # largest divisor of s not exceeding `chunk`
    chunk -= 1
  n = s // chunk
  xs = x.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
  ts = targets.reshape(b, n, chunk).transpose(1, 0, 2)

  def body(_, inp):
    x_c, t_c = inp
    logits = lm_head_logits(w, x_c, softcap)
    logits = shard_activation(logits, "logits")
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
    return None, logz - gold

  _, losses = lax.scan(body, None, (xs, ts))
  return losses.transpose(1, 0, 2).reshape(b, s)
