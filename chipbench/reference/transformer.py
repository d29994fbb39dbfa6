"""Plain float32 JAX reference of a StableLM-style decoder training step.

Follows the configuration file (``chipbench/configs/stablelm-3b-lts.json``):
pre-LayerNorm blocks with bias, multi-head causal attention with rotary
position embedding over ``rope_pct`` of each head (split into halves),
a SiLU-gated MLP, a final LayerNorm and an untied output head; per-token
negative log-likelihood, the soft least-trimmed-squares loss over all
tokens of the step (the float64 NumPy reference in ``soft_ops``), and
AdamW with global-norm clipping.  The weights and moments are stored in
the precision the configuration states (bfloat16 matrices, float32
norms, bfloat16 moments; the matrices are held in float32 arrays that
carry bfloat16 values, so that gradients come out in float32); every
product and sum is float32 at ``highest`` matmul precision.

The weights are drawn from the seed's key the way the trainer draws them
(normal over fan-in, embedding 0.02 x normal), so both start from the
same point; nothing of the program is imported.  Memory: the model runs
layer by layer under ``jax.checkpoint``, attention in query blocks and
the output head in sequence chunks, each also rematerialised, so one
step fits beside float32 gradients on a 16 GB chip.

``quantize="int8"`` is the precision control: every matmul operand is
rounded to int8 with one absmax scale per tensor (straight-through in
the backward pass), the step below the bfloat16 the configuration runs.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference import soft_ops

F32 = jnp.float32
Q_BLOCK = 512
HEAD_CHUNK = 512


def _bf16(x):
  """x rounded to bfloat16, kept in float32.  ``reduce_precision`` and not
  a round trip through ``astype``: XLA may drop a float32 -> bfloat16 ->
  float32 pair as excess precision, and then the weights would move by
  exact float32 steps where the configuration's bfloat16 weights move by
  whole units of their last place."""
  return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def dims(c: dict) -> dict:
  d, h = c["hidden_size"], c["num_attention_heads"]
  return dict(d=d, h=h, kv=c["num_key_value_heads"], dh=d // h,
              f=c["intermediate_size"], vocab=c["vocab_size"],
              layers=c["num_hidden_layers"],
              rot=int(round(c["rope_pct"] * (d // h))),
              theta=float(c["rope_theta"]), eps=float(c["norm_eps"]))


# ---------------------------------------------------------------------------
# Weights.
# ---------------------------------------------------------------------------


def init(c: dict, key) -> dict:
  """Weights as the trainer draws them from ``key``."""
  z = dims(c)
  d, h, kv, dh, f, v = z["d"], z["h"], z["kv"], z["dh"], z["f"], z["vocab"]
  keys = jax.random.split(key, 8)
  normal = lambda k, shape, s: _bf16(jax.random.normal(k, shape) * s)

  def layer(k):
    k1, k2 = jax.random.split(k)
    a, b, g = jax.random.split(k2, 3)
    q, kk, vv, o = jax.random.split(k1, 4)
    return {
        "ln1.scale": jnp.ones((d,), F32), "ln1.bias": jnp.zeros((d,), F32),
        "ln2.scale": jnp.ones((d,), F32), "ln2.bias": jnp.zeros((d,), F32),
        "w_in": normal(a, (d, f), 1 / math.sqrt(d)),
        "w_out": normal(b, (f, d), 1 / math.sqrt(f)),
        "w_gate": normal(g, (d, f), 1 / math.sqrt(d)),
        "wq": normal(q, (d, h, dh), 1 / math.sqrt(d)),
        "wk": normal(kk, (d, kv, dh), 1 / math.sqrt(d)),
        "wv": normal(vv, (d, kv, dh), 1 / math.sqrt(d)),
        "wo": normal(o, (h, dh, d), 1 / math.sqrt(h * dh)),
    }

  layers = jax.vmap(layer)(jax.random.split(jax.random.fold_in(keys[3], 0),
                                            z["layers"]))
  return {
      "embed": normal(keys[0], (v, d), 0.02),
      "head": normal(keys[2], (d, v), 1 / math.sqrt(d)),
      "final_norm.scale": jnp.ones((d,), F32),
      "final_norm.bias": jnp.zeros((d,), F32),
      "layers": layers,
  }


# ---------------------------------------------------------------------------
# Forward pass.
# ---------------------------------------------------------------------------


def _quant(x):
  """int8 with one absmax scale per tensor, straight-through."""
  s = lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0)
  q = jnp.clip(jnp.round(x / s), -127, 127) * s
  return x + lax.stop_gradient(q - x)


def _mm(spec, a, b, quantize):
  a, b = a.astype(F32), b.astype(F32)
  if quantize == "int8":
    a, b = _quant(a), _quant(b)
  return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)


def _norm(x, scale, bias, eps):
  mu = jnp.mean(x, -1, keepdims=True)
  var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
  return (x - mu) * lax.rsqrt(var + eps) * scale + bias


def _rope(x, pos, rot, theta):
  """Rotate the first ``rot`` features of each head, halves paired."""
  half = rot // 2
  freq = theta ** (-jnp.arange(half, dtype=F32) / half)
  ang = pos[:, None].astype(F32) * freq
  cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
  x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
  return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _attention(q, k, v, quantize):
  """Causal softmax attention, one query block at a time."""
  b, s, h, dh = q.shape
  nb = max(1, s // Q_BLOCK)
  blk = s // nb
  scale = 1.0 / math.sqrt(dh)
  kpos = jnp.arange(s)

  @jax.checkpoint
  def block(i, qb):
    scores = _mm("bqhd,bkhd->bhqk", qb, k, quantize) * scale
    qpos = i * blk + jnp.arange(blk)
    scores = jnp.where(kpos[None, :] <= qpos[:, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return _mm("bhqk,bkhd->bqhd", p, v, quantize)

  qs = q.reshape(b, nb, blk, h, dh).transpose(1, 0, 2, 3, 4)
  out = lax.map(lambda a: block(*a), (jnp.arange(nb), qs))
  return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dh)


def _layer(z, quantize, x, p):
  pos = jnp.arange(x.shape[1])
  hdn = _norm(x, p["ln1.scale"], p["ln1.bias"], z["eps"])
  q = _rope(_mm("bsd,dhk->bshk", hdn, p["wq"], quantize), pos, z["rot"],
            z["theta"])
  k = _rope(_mm("bsd,dhk->bshk", hdn, p["wk"], quantize), pos, z["rot"],
            z["theta"])
  v = _mm("bsd,dhk->bshk", hdn, p["wv"], quantize)
  if z["kv"] != z["h"]:
    rep = z["h"] // z["kv"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
  x = x + _mm("bshk,hkd->bsd", _attention(q, k, v, quantize), p["wo"],
              quantize)
  hdn = _norm(x, p["ln2.scale"], p["ln2.bias"], z["eps"])
  gate = jax.nn.silu(_mm("bsd,df->bsf", hdn, p["w_gate"], quantize))
  up = _mm("bsd,df->bsf", hdn, p["w_in"], quantize)
  return x + _mm("bsf,fd->bsd", gate * up, p["w_out"], quantize)


def token_nll(c: dict, params: dict, tokens, targets, quantize=None):
  """Per-token negative log-likelihood (B, S), float32."""
  z = dims(c)
  x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
  body = jax.checkpoint(functools.partial(_layer, z, quantize))
  x, _ = lax.scan(lambda x, p: (body(x, p), None), x, params["layers"])
  x = _norm(x, params["final_norm.scale"], params["final_norm.bias"],
            z["eps"])
  b, s, d = x.shape
  nc = max(1, s // HEAD_CHUNK)

  @jax.checkpoint
  def chunk(xc, tc):
    logits = _mm("bsd,dv->bsv", xc, params["head"], quantize)
    gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - gold

  xs = x.reshape(b, nc, s // nc, d).transpose(1, 0, 2, 3)
  ts = targets.reshape(b, nc, s // nc).transpose(1, 0, 2)
  out = lax.map(lambda a: chunk(*a), (xs, ts))
  return out.transpose(1, 0, 2).reshape(b, s)


# ---------------------------------------------------------------------------
# The training step.
# ---------------------------------------------------------------------------


def _is_matrix(path) -> bool:
  """Matrices are stored in bfloat16; the norms' scales and biases in
  float32."""
  return "norm" not in jax.tree_util.keystr(path) and \
      "ln" not in jax.tree_util.keystr(path)


def _decayed(leaf) -> bool:
  """The trainer's rule: weight decay on every leaf of two or more axes
  as it stores them (the per-layer norms are stacked, so they decay; the
  final norm does not)."""
  return leaf.ndim >= 2


@jax.jit
def leaf_norms(tree):
  """name -> norms: one per layer for the stacked layers, one for every
  other leaf."""
  out = {}
  for name, x in tree.items():
    if name == "layers":
      for k, a in x.items():
        out[k] = jnp.sqrt(jnp.sum(jnp.square(a).reshape(a.shape[0], -1), -1))
    else:
      out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
  return out


@jax.jit
def change_norms(new, old):
  return leaf_norms.__wrapped__(jax.tree.map(jnp.subtract, new, old))


def _host(norms) -> dict[str, np.ndarray]:
  return {k: np.asarray(v, np.float64) for k, v in norms.items()}


class Reference:
  """Drives the reference through the first steps of a run."""

  def __init__(self, c: dict, quantize=None, keep_tokens: float = 1.0):
    self.c = c
    tr = c["train"]
    self.adam = c["adamw"]
    self.lr = tr["learning_rate"]
    self.sched = c["schedule"]
    self.trim, self.trim_eps = tr["loss_trim_fraction"], tr["loss_trim_eps"]
    self.keep = keep_tokens        # < 1 plants a fault: tokens left out
    nll = functools.partial(token_nll, c, quantize=quantize)
    self._nll = jax.jit(nll)

    def grads(p, t, y, ct):
      _, vjp = jax.vjp(lambda q: nll(q, t, y), p)
      return vjp(ct)[0]
    self._grads = jax.jit(grads)
    self._update = jax.jit(self._adam, donate_argnums=(0, 1, 2))

  def lr_scale(self, step: int) -> float:
    """The trainer's cosine schedule with linear warm-up."""
    w, total, lo = self.sched["warmup"], self.sched["total"], \
        self.sched["min_frac"]
    warm = min(step / max(w, 1), 1.0)
    t = min(max((step - w) / max(total - w, 1), 0.0), 1.0)
    return warm * (lo + (1 - lo) * 0.5 * (1 + math.cos(math.pi * t)))

  def _adam(self, params, m, v, grads, step, scale, lr):
    a = self.adam
    bc1 = 1.0 - a["b1"] ** (step + 1.0)
    bc2 = 1.0 - a["b2"] ** (step + 1.0)

    def upd(path, p, g, mm, vv):
      g = g * scale
      m32 = a["b1"] * mm.astype(F32) + (1 - a["b1"]) * g
      v32 = a["b2"] * vv.astype(F32) + (1 - a["b2"]) * g * g
      d = (m32 / bc1) / (jnp.sqrt(v32 / bc2) + a["eps"])
      if _decayed(p):
        d = d + a["weight_decay"] * p
      new = p - lr * d
      if _is_matrix(path):
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
      nll = np.asarray(self._nll(params, batch["tokens"], batch["targets"]),
                       np.float64)
      kept = nll[:, :int(round(self.keep * nll.shape[1]))]
      loss, ct_kept = soft_ops.trimmed_loss(kept.reshape(-1), self.trim,
                                            self.trim_eps)
      ct = np.zeros_like(nll)
      ct[:, :kept.shape[1]] = ct_kept.reshape(kept.shape)
      losses.append(loss)
      g = self._grads(params, batch["tokens"], batch["targets"],
                      jnp.asarray(ct, F32))
      gnorm = float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                 for x in jax.tree.leaves(g))))
      scale = min(1.0, self.adam["clip_norm"] / max(gnorm, 1e-12))
      if first is None:
        first = {k: scale * v for k, v in _host(leaf_norms(g)).items()}
      params, m, v = self._update(params, m, v, g, float(step), scale,
                                  self.lr * self.lr_scale(step))
      del g
    del m, v
    start = jax.jit(functools.partial(init, self.c))(key)
    change = _host(change_norms(params, start))
    return losses, first, change
