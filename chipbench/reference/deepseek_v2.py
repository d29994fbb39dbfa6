"""Plain float32 JAX reference of a DeepSeek-V2 training step on one chip's
share of its experts.

Follows the configuration file (``chipbench/configs/deepseek-v2-lite-ep8
.json``) and DeepSeek-V2 (arXiv:2405.04434): RMSNorm pre-norm blocks;
multi-head latent attention with no query compression (the query from
``wq``; a latent of ``kv_lora_rank`` and one shared rope key from
``w_dkv``; the latent RMS-normalised, then expanded by ``w_uk`` and
``w_uv``), rotary embedding with YaRN's frequencies over the rope
dimensions (rotated as halves: the published code's interleaved pairs up
to a fixed permutation of the rope columns) and YaRN's score scale; then
``first_k_dense_replace`` layers with a SiLU-gated MLP of
``intermediate_size`` and the rest with a mixture of experts: a float32
router over all ``router_experts``, softmax scores, the paper's soft top-k
mask (the projection onto the k-subset permutahedron, with its VJP,
written plainly below) times the scores as the gates, no renormalisation,
the hard top-k of the gates choosing each token's experts, and the held
experts' part of the result for every token routed to them (dropless),
plus the shared experts on every token.  Per-token negative
log-likelihood, the mean over tokens plus 0.01 x the load-balance loss
over all experts, and AdamW with global-norm clipping.

Stored as the configuration states: bfloat16 matrices (held in float32
arrays that carry bfloat16 values), a float32 router and float32 norm
scales, bfloat16 moments; every product and sum is float32 at ``highest``
matmul precision.  The weights are drawn from the seed's key the way the
trainer draws them; nothing of the program is imported.  Memory: layer by
layer under ``jax.checkpoint``, attention in query blocks, the experts one
at a time, the output head in sequence chunks.

``quantize="int8"`` is the precision control: every matmul operand rounded
to int8 with one absmax scale per tensor (``transformer._mm``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference import transformer as dense_ref

F32 = jnp.float32
Q_BLOCK = 512
HEAD_CHUNK = 512
_bf16, _mm = dense_ref._bf16, dense_ref._mm


def dims(c: dict) -> dict:
  dep = c["deployment"]
  return dict(
      d=c["hidden_size"], h=c["num_attention_heads"],
      nd=c["qk_nope_head_dim"], rd=c["qk_rope_head_dim"],
      vd=c["v_head_dim"], r=c["kv_lora_rank"], f=c["intermediate_size"],
      fe=c["moe_intermediate_size"], e=dep["router_experts"],
      held=c["n_routed_experts"], h0=dep["first_held_expert"],
      k=c["num_experts_per_tok"], shared=c["n_shared_experts"],
      vocab=c["vocab_size"], dense=c["first_k_dense_replace"],
      moe=c["num_hidden_layers"] - c["first_k_dense_replace"],
      theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
      yarn=c["rope_scaling"], router_eps=float(c["train"]["router_eps"]),
      norm_topk=c["norm_topk_prob"])


# ---------------------------------------------------------------------------
# Weights, as the trainer draws them.
# ---------------------------------------------------------------------------


def _normal(key, shape, scale):
  return _bf16(jax.random.normal(key, shape) * scale)


def _mla(z, key):
  d, h, nd, rd, vd, r = z["d"], z["h"], z["nd"], z["rd"], z["vd"], z["r"]
  ks = jax.random.split(key, 5)
  return {
      "mla.wq": _normal(ks[0], (d, h, nd + rd), 1 / math.sqrt(d)),
      "mla.w_dkv": _normal(ks[1], (d, r + rd), 1 / math.sqrt(d)),
      "mla.w_uk": _normal(ks[2], (r, h, nd), 1 / math.sqrt(r)),
      "mla.w_uv": _normal(ks[3], (r, h, vd), 1 / math.sqrt(r)),
      "mla.wo": _normal(ks[4], (h, vd, d), 1 / math.sqrt(h * vd)),
      "mla.kv_norm.scale": jnp.ones((r,), F32),
  }


def _swiglu(key, d, f, prefix):
  k1, k2, k3 = jax.random.split(key, 3)
  return {f"{prefix}w_in": _normal(k1, (d, f), 1 / math.sqrt(d)),
          f"{prefix}w_out": _normal(k2, (f, d), 1 / math.sqrt(f)),
          f"{prefix}w_gate": _normal(k3, (d, f), 1 / math.sqrt(d))}


def _experts(z, key):
  d, fe, held = z["d"], z["fe"], z["held"]
  ks = jax.random.split(key, 5)
  si, so = 1 / math.sqrt(d), 1 / math.sqrt(fe)
  p = {"ffn.router": jax.random.normal(ks[0], (d, z["e"])).astype(F32) * si,
       "ffn.we_in": _normal(ks[1], (held, d, fe), si),
       "ffn.we_gate": _normal(ks[2], (held, d, fe), si),
       "ffn.we_out": _normal(ks[3], (held, fe, d), so)}
  if z["shared"]:
    fs = fe * z["shared"]
    k1, k2, k3 = jax.random.split(ks[4], 3)
    p.update({"ffn.shared.w_in": _normal(k1, (d, fs), si),
              "ffn.shared.w_gate": _normal(k2, (d, fs), si),
              "ffn.shared.w_out": _normal(k3, (fs, d), so)})
  return p


def _layer_init(z, kind, key):
  k1, k2 = jax.random.split(key)
  p = {"norm1.scale": jnp.ones((z["d"],), F32),
       "norm2.scale": jnp.ones((z["d"],), F32)}
  p.update(_mla(z, k1))
  if kind == "mla_dense":
    p.update(_swiglu(k2, z["d"], z["f"], "ffn."))
  else:
    p.update(_experts(z, k2))
  return p


def init(c: dict, key) -> dict:
  """Weights as the trainer draws them from ``key``: the leading dense
  layers are its first scanned segment, the expert layers its second."""
  z = dims(c)
  keys = jax.random.split(key, 8)
  out = {"embed": _normal(keys[0], (z["vocab"], z["d"]), 0.02),
         "head": _normal(keys[2], (z["d"], z["vocab"]), 1 / math.sqrt(z["d"])),
         "final_norm.scale": jnp.ones((z["d"],), F32)}
  for si, (kind, n) in enumerate((("mla_dense", z["dense"]),
                                  ("mla_moe", z["moe"]))):
    lkeys = jax.random.split(jax.random.fold_in(keys[3], si * 64), n)
    out[kind] = jax.vmap(functools.partial(_layer_init, z, kind))(lkeys)
  return out


# ---------------------------------------------------------------------------
# The soft top-k mask: the projection onto the k-subset permutahedron.
# ---------------------------------------------------------------------------


def _isotonic_dec(y):
  """Non-increasing least-squares isotonic fit of each row of y (..., n):
  v_i = min_{j <= i} max_{k >= i} mean(y_j..y_k)."""
  n = y.shape[-1]
  cs = jnp.concatenate([jnp.zeros(y.shape[:-1] + (1,), y.dtype),
                        jnp.cumsum(y, -1)], -1)
  j = jnp.arange(n)[:, None]
  k = jnp.arange(n)[None, :]
  mean = (cs[..., None, 1:] - cs[..., :-1, None]) / (k - j + 1)  # [.., j, k]
  mean = jnp.where(k >= j, mean, -jnp.inf)
  inner = lax.cummax(mean, axis=mean.ndim - 1, reverse=True)    # max k >= i
  inner = jnp.where(k >= j, inner, jnp.inf)                     # [.., j, i]
  return jnp.min(inner, axis=-2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def soft_topk_mask(z, k):
  """P(z, w) with w = (1 x k, 0 x (n - k)): z - v(sorted z - w), unsorted
  (Prop. 3 of the paper)."""
  return _soft_topk_fwd(z, k)[0]


def _soft_topk_fwd(z, k):
  n = z.shape[-1]
  w = (jnp.arange(n) < k).astype(z.dtype)
  sigma = jnp.argsort(-z, axis=-1, stable=True)
  s = jnp.take_along_axis(z, sigma, -1)
  v = _isotonic_dec(s - w)
  out = jnp.zeros_like(z)
  out = jnp.put_along_axis(out, sigma, s - v, -1, inplace=False)
  return out, (sigma, v)


def _soft_topk_bwd(k, res, g):
  """Lemma 2: the isotonic fit's Jacobian averages within its blocks, so
  d out / d z = I - (block mean) in sorted order."""
  sigma, v = res
  gs = jnp.take_along_axis(g, sigma, -1)
  starts = jnp.concatenate([jnp.ones(v.shape[:-1] + (1,), bool),
                            v[..., 1:] != v[..., :-1]], -1)
  block = jnp.cumsum(starts, -1)
  same = (block[..., :, None] == block[..., None, :]).astype(g.dtype)
  mean = jnp.sum(same * gs[..., None, :], -1) / jnp.sum(same, -1)
  out = jnp.zeros_like(g)
  return (jnp.put_along_axis(out, sigma, gs - mean, -1, inplace=False),)


soft_topk_mask.defvjp(_soft_topk_fwd, _soft_topk_bwd)


# ---------------------------------------------------------------------------
# Forward pass.
# ---------------------------------------------------------------------------


def _rms(x, scale, eps):
  return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _mscale(factor, m):
  return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, y: dict) -> np.ndarray:
  """YaRN frequencies (DeepseekV2YarnRotaryEmbedding): the original ones
  below the correction range, 1/factor of them above it, a ramp between."""
  def corr(rot):
    return (dim * math.log(y["original_max_position_embeddings"]
                           / (rot * 2 * math.pi)) / (2 * math.log(theta)))
  low = max(math.floor(corr(y["beta_fast"])), 0)
  high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
  if low == high:
    high += 0.001
  extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
  ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
  return (extra / y["factor"] * ramp + extra * (1 - ramp)).astype(np.float32)


def _rope(z, x, pos):
  """Rotate x (..., S, H, rd) as halves at YaRN's frequencies."""
  y = z["yarn"]
  freq = jnp.asarray(yarn_inv_freq(z["rd"], z["theta"], y))
  ms = _mscale(y["factor"], y["mscale"]) / _mscale(y["factor"],
                                                  y["mscale_all_dim"])
  ang = pos[:, None].astype(F32) * freq
  cos = (jnp.cos(ang) * ms)[:, None, :]
  sin = (jnp.sin(ang) * ms)[:, None, :]
  half = z["rd"] // 2
  x1, x2 = x[..., :half], x[..., half:]
  return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def score_scale(z) -> float:
  y = z["yarn"]
  return (_mscale(y["factor"], y["mscale_all_dim"]) ** 2
          / math.sqrt(z["nd"] + z["rd"]))


def _attention(z, q, k, v, quantize):
  """Causal softmax attention, one query block at a time."""
  b, s, h, _ = q.shape
  nb = max(1, s // Q_BLOCK)
  blk = s // nb
  scale = score_scale(z)
  kpos = jnp.arange(s)

  @jax.checkpoint
  def block(i, qb):
    scores = _mm("bqhd,bkhd->bhqk", qb, k, quantize) * scale
    qpos = i * blk + jnp.arange(blk)
    scores = jnp.where(kpos[None, :] <= qpos[:, None], scores, -jnp.inf)
    return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v, quantize)

  qs = q.reshape(b, nb, blk, h, -1).transpose(1, 0, 2, 3, 4)
  out = lax.map(lambda a: block(*a), (jnp.arange(nb), qs))
  return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, -1)


def mla(z, quantize, x, p):
  pos = jnp.arange(x.shape[1])
  nd, r = z["nd"], z["r"]
  q = _mm("bsd,dhk->bshk", x, p["mla.wq"], quantize)
  q = jnp.concatenate([q[..., :nd], _rope(z, q[..., nd:], pos)], -1)
  ckv = _mm("bsd,dr->bsr", x, p["mla.w_dkv"], quantize)
  c = _rms(ckv[..., :r], p["mla.kv_norm.scale"], z["eps"])
  k_rope = _rope(z, ckv[..., None, r:], pos)
  k_nope = _mm("bsr,rhk->bshk", c, p["mla.w_uk"], quantize)
  v = _mm("bsr,rhk->bshk", c, p["mla.w_uv"], quantize)
  k = jnp.concatenate([k_nope, jnp.broadcast_to(
      k_rope, k_nope.shape[:3] + (z["rd"],))], -1)
  o = _attention(z, q, k, v, quantize)
  return _mm("bshk,hkd->bsd", o, p["mla.wo"], quantize)


def _swiglu_apply(x, w_gate, w_in, w_out, quantize):
  h = jax.nn.silu(_mm("td,df->tf", x, w_gate, quantize)) * \
      _mm("td,df->tf", x, w_in, quantize)
  return _mm("tf,fd->td", h, w_out, quantize)


def route(z, quantize, x, router):
  """x (T, d) -> (gates (T, E), zero where not chosen; chosen (T, E); probs)."""
  logits = _mm("td,de->te", x, router, quantize)
  probs = jax.nn.softmax(logits, -1)
  w = soft_topk_mask(logits / z["router_eps"], z["k"]) * probs
  if z["norm_topk"]:
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
  top = lax.top_k(lax.stop_gradient(w), z["k"])[1]
  chosen = jnp.sum(jax.nn.one_hot(top, z["e"], dtype=F32), 1)
  return w * chosen, chosen, probs


def moe(z, quantize, x, p):
  """x (T, d) -> (held and shared experts' output, load-balance loss,
  assignments to held experts)."""
  gates, chosen, probs = route(z, quantize, x, p["ffn.router"])
  held = lax.dynamic_slice_in_dim(gates, z["h0"], z["held"], 1)

  @jax.checkpoint
  def expert(out, e):
    g, w_gate, w_in, w_out = e
    return out + g[:, None] * _swiglu_apply(x, w_gate, w_in, w_out,
                                            quantize), None

  out, _ = lax.scan(expert, jnp.zeros_like(x),
                    (held.T, p["ffn.we_gate"], p["ffn.we_in"],
                     p["ffn.we_out"]))
  if z["shared"]:
    out = out + _swiglu_apply(x, p["ffn.shared.w_gate"], p["ffn.shared.w_in"],
                              p["ffn.shared.w_out"], quantize)
  aux = z["e"] * jnp.sum(jnp.mean(chosen, 0) * jnp.mean(probs, 0))
  n_held = jnp.sum(lax.dynamic_slice_in_dim(chosen, z["h0"], z["held"], 1))
  return out, aux, n_held


def _layer(z, quantize, kind, carry, p):
  x, aux, n_held = carry
  x = x + mla(z, quantize, _rms(x, p["norm1.scale"], z["eps"]), p)
  h = _rms(x, p["norm2.scale"], z["eps"])
  b, s, d = h.shape
  if kind == "mla_dense":
    ff = _swiglu_apply(h.reshape(-1, d), p["ffn.w_gate"], p["ffn.w_in"],
                       p["ffn.w_out"], quantize)
  else:
    ff, a, n = moe(z, quantize, h.reshape(-1, d), p)
    aux, n_held = aux + a, n_held + n
  return (x + ff.reshape(b, s, d), aux, n_held), None


def final_hidden(c: dict, params: dict, tokens, quantize=None):
  """(the final norm's output (B, S, d), load-balance loss summed over the
  expert layers, assignments to held experts)."""
  z = dims(c)
  x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
  carry = (x, jnp.zeros((), F32), jnp.zeros((), F32))
  for kind in ("mla_dense", "mla_moe"):
    body = jax.checkpoint(functools.partial(_layer, z, quantize, kind))
    carry, _ = lax.scan(body, carry, params[kind])
  x, aux, n_held = carry
  return _rms(x, params["final_norm.scale"], z["eps"]), aux, n_held


def forward(c: dict, params: dict, tokens, targets, quantize=None):
  """(per-token NLL (B, S), load-balance loss summed over the expert
  layers, assignments to held experts)."""
  x, aux, n_held = final_hidden(c, params, tokens, quantize)
  return token_nll(params, x, targets, quantize), aux, n_held


def token_nll(params: dict, x, targets, quantize=None):
  """Per-token NLL (B, S) of the output head over the final hidden x."""
  b, s, d = x.shape
  nc = max(1, s // HEAD_CHUNK)

  @jax.checkpoint
  def chunk(xc, tc):
    logits = _mm("bsd,dv->bsv", xc, params["head"], quantize)
    gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - gold

  xs = x.reshape(b, nc, s // nc, d).transpose(1, 0, 2, 3)
  ts = targets.reshape(b, nc, s // nc).transpose(1, 0, 2)
  nll = lax.map(lambda a: chunk(*a), (xs, ts))
  return nll.transpose(1, 0, 2).reshape(b, s)


# ---------------------------------------------------------------------------
# The training step.
# ---------------------------------------------------------------------------


def _stored_bf16(name: str) -> bool:
  """bfloat16 matrices; the router and the norms' scales in float32."""
  return "norm" not in name and "router" not in name


@jax.jit
def leaf_norms(tree):
  """name -> norms: one per layer for the stacked layers, one for every
  other leaf; names as the benchmark's driver gives the program's."""
  out = {}
  for name, x in tree.items():
    if isinstance(x, dict):
      for k, a in x.items():
        out[f"{name}.{k}"] = jnp.sqrt(jnp.sum(
            jnp.square(a).reshape(a.shape[0], -1), -1))
    else:
      out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
  return out


class Reference(dense_ref.Reference):
  """Drives the reference through the first steps of a run."""

  def __init__(self, c: dict, quantize=None, keep_tokens: float = 1.0):
    self.c = c
    tr = c["train"]
    self.adam = c["adamw"]
    self.lr = tr["learning_rate"]
    self.sched = c["schedule"]
    self.keep = keep_tokens        # < 1 plants a fault: tokens left out
    fwd = functools.partial(forward, c, quantize=quantize)

    def loss(p, t, y):
      nll, aux, n_held = fwd(p, t, y)
      kept = nll[:, :int(round(self.keep * nll.shape[1]))]
      return jnp.mean(kept) + 0.01 * aux, (jnp.mean(kept), n_held)
    self._step_grads = jax.jit(jax.value_and_grad(loss, has_aux=True))
    self._update = jax.jit(self._adam, donate_argnums=(0, 1, 2))

  def _adam(self, params, m, v, grads, step, scale, lr):
    a = self.adam
    bc1 = 1.0 - a["b1"] ** (step + 1.0)
    bc2 = 1.0 - a["b2"] ** (step + 1.0)

    def upd(path, p, g, mm, vv):
      g = g * scale
      m32 = a["b1"] * mm.astype(F32) + (1 - a["b1"]) * g
      v32 = a["b2"] * vv.astype(F32) + (1 - a["b2"]) * g * g
      d = (m32 / bc1) / (jnp.sqrt(v32 / bc2) + a["eps"])
      if p.ndim >= 2:              # the trainer's decay rule, as stored
        d = d + a["weight_decay"] * p
      new = p - lr * d
      if _stored_bf16(jax.tree_util.keystr(path)):
        new = _bf16(new)
      return new, m32.astype(mm.dtype), v32.astype(vv.dtype)

    out = jax.tree_util.tree_map_with_path(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)

  def run(self, key, batches):
    """Steps over ``batches`` from the weights of ``key``.

    Returns (losses, norms of the first clipped gradient, norms of the
    weights' change after the last step), each norm per leaf and layer."""
    moment = jnp.dtype(self.c["adam_moment_dtype"])
    params = jax.jit(functools.partial(init, self.c))(key)
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, moment), params)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, moment), params)
    losses, first = [], None
    for step, batch in enumerate(batches):
      (_, (loss, _)), g = self._step_grads(params, batch["tokens"],
                                           batch["targets"])
      losses.append(float(loss))
      gnorm = float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                 for x in jax.tree.leaves(g))))
      scale = min(1.0, self.adam["clip_norm"] / max(gnorm, 1e-12))
      if first is None:
        first = {k: scale * v for k, v in
                 dense_ref._host(leaf_norms(g)).items()}
      params, m, v = self._update(params, m, v, g, float(step), scale,
                                  self.lr * self.lr_scale(step))
      del g
    del m, v
    start = jax.jit(functools.partial(init, self.c))(key)
    change = dense_ref._host(leaf_norms(jax.tree.map(jnp.subtract, params,
                                                     start)))
    return losses, first, change
