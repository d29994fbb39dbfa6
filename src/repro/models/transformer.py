"""Model assembly: layer-kind registry, scanned segments, train/serve passes.

A config's ``block_cycle`` is expanded to per-layer kinds and grouped into
scannable segments (``ArchConfig.plan_segments``): parameters for each
segment are stacked with a leading ``repeats`` dim and the segment runs
under ``lax.scan`` (compact HLO, one compiled body per cycle) with a
configurable remat policy.  Decode threads per-layer caches through the
same scan.

Supported layer kinds:
  dense / global   GQA attention + MLP
  local            sliding-window GQA attention + MLP
  moe              GQA attention + MoE FFN (+ shared experts)
  mla_dense        Multi-head Latent Attention + MLP (deepseek's leading
                   dense layers)
  mla_moe          Multi-head Latent Attention + MoE FFN (deepseek)
  rg               RG-LRU recurrent block + MLP (recurrentgemma)
  mlstm / slstm    xLSTM blocks + MLP
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import recurrent as RG
from repro.models import xlstm as XL
from repro.sharding.specs import shard_activation

Array = jax.Array
Params = dict[str, Any]

_ATTN_KINDS = ("dense", "global", "local", "moe")
_MLA_KINDS = ("mla_dense", "mla_moe")
_MOE_KINDS = ("moe", "mla_moe")
# What every layer adds to the step's statistics (MoE layers fill them):
# summed over layers, except the busiest share, the largest of any layer.
_STAT_KEYS = ("aux_loss", "moe_assignments_held", "moe_busiest_share")


def _no_stats():
  return {k: jnp.zeros((), jnp.float32) for k in _STAT_KEYS}


def add_stats(a, b):
  """a's statistics merged with b's: summed, the busiest share maxed."""
  return {k: (jnp.maximum if k == "moe_busiest_share" else jnp.add)(
      a[k], b[k]) for k in a}


def _dtype(cfg):
  return jnp.dtype(cfg.dtype)


def _ffn_variant(cfg, kind) -> str:
  if kind == "mlstm":
    return "gelu"
  if kind == "slstm":
    return "geglu"
  return cfg.mlp_variant


def _ffn_init(key, cfg, kind, dtype):
  if kind in _MOE_KINDS:
    return MOE.moe_init(key, cfg, dtype)
  if kind == "mlstm":
    f = 2 * cfg.d_model
  elif kind == "slstm":
    f = max(64, int(round(cfg.d_model * 4 / 3 / 64)) * 64)
  else:
    f = cfg.d_ff
  return L.mlp_init(key, cfg.d_model, f, _ffn_variant(cfg, kind), dtype)


def _mixer_init(key, cfg, kind, dtype):
  if kind in _ATTN_KINDS:
    return {"attn": L.attn_init(key, cfg, dtype)}
  if kind in _MLA_KINDS:
    return {"mla": MLA.mla_init(key, cfg, dtype)}
  if kind == "rg":
    return {"rg": RG.rg_init(key, cfg, dtype)}
  if kind == "mlstm":
    return {"mlstm": XL.mlstm_init(key, cfg, dtype)}
  if kind == "slstm":
    return {"slstm": XL.slstm_init(key, cfg, dtype)}
  raise ValueError(kind)


def _layer_init(key, cfg, kind) -> Params:
  dtype = _dtype(cfg)
  k1, k2 = jax.random.split(key)
  p = {
      "norm1": L.norm_init(cfg.d_model, cfg.norm),
      "norm2": L.norm_init(cfg.d_model, cfg.norm),
      "ffn": _ffn_init(k2, cfg, kind, dtype),
  }
  p.update(_mixer_init(k1, cfg, kind, dtype))
  return p


def _window(cfg, kind) -> int:
  return cfg.window_size if kind == "local" else 0


def _layer_apply_seq(p, x, positions, cfg, kind, *, collect_cache=False):
  """Returns (x, stats, cache_or_None)."""
  h = L.norm_apply(p["norm1"], x, cfg.norm)
  cache = None
  if kind in _ATTN_KINDS:
    if collect_cache:
      mixed, (kc, vc) = L.attn_apply_seq(
          p["attn"], h, positions, cfg, window=_window(cfg, kind),
          return_kv=True)
      cache = {"k": kc, "v": vc}
    else:
      mixed = L.attn_apply_seq(
          p["attn"], h, positions, cfg, window=_window(cfg, kind))
  elif kind in _MLA_KINDS:
    if collect_cache:
      mixed, cache = MLA.mla_apply_seq(
          p["mla"], h, positions, cfg, return_kv=True)
    else:
      mixed = MLA.mla_apply_seq(p["mla"], h, positions, cfg)
  elif kind == "rg":
    if collect_cache:
      mixed, cache = RG.rg_apply_seq(p["rg"], h, cfg, return_state=True)
    else:
      mixed = RG.rg_apply_seq(p["rg"], h, cfg)
  elif kind == "mlstm":
    if collect_cache:
      mixed, cache = XL.mlstm_apply_seq(p["mlstm"], h, cfg, return_state=True)
    else:
      mixed = XL.mlstm_apply_seq(p["mlstm"], h, cfg)
  elif kind == "slstm":
    if collect_cache:
      mixed, cache = XL.slstm_apply_seq(p["slstm"], h, cfg, return_state=True)
    else:
      mixed = XL.slstm_apply_seq(p["slstm"], h, cfg)
  else:
    raise ValueError(kind)
  x = x + mixed.astype(x.dtype)
  x = shard_activation(x, "residual")

  h2 = L.norm_apply(p["norm2"], x, cfg.norm)
  stats = _no_stats()
  if kind in _MOE_KINDS:
    ff, stats = MOE.moe_apply(p["ffn"], h2, cfg)
  else:
    ff = L.mlp_apply(p["ffn"], h2, _ffn_variant(cfg, kind))
  x = x + ff.astype(x.dtype)
  x = shard_activation(x, "residual")
  return x, stats, cache


def _layer_apply_decode(p, x, cache, pos, cfg, kind):
  """x: (B, d). Returns (x, new_cache)."""
  h = L.norm_apply(p["norm1"], x, cfg.norm)
  if kind in _ATTN_KINDS:
    mixed, cache = L.attn_apply_decode(
        p["attn"], h, cache, pos, cfg, window=_window(cfg, kind))
  elif kind in _MLA_KINDS:
    mixed, cache = MLA.mla_apply_decode(p["mla"], h, cache, pos, cfg)
  elif kind == "rg":
    mixed, cache = RG.rg_apply_decode(p["rg"], h, cache, cfg)
  elif kind == "mlstm":
    mixed, cache = XL.mlstm_apply_decode(p["mlstm"], h, cache, cfg)
  elif kind == "slstm":
    mixed, cache = XL.slstm_apply_decode(p["slstm"], h, cache, cfg)
  else:
    raise ValueError(kind)
  x = x + mixed.astype(x.dtype)

  h2 = L.norm_apply(p["norm2"], x, cfg.norm)
  if kind in _MOE_KINDS:
    ff, _ = MOE.moe_apply(p["ffn"], h2, cfg)
  else:
    ff = L.mlp_apply(p["ffn"], h2, _ffn_variant(cfg, kind))
  x = x + ff.astype(x.dtype)
  x = shard_activation(x, "residual_decode")
  return x, cache


def _layer_init_cache(cfg, kind, batch, max_len, dtype):
  if kind in _ATTN_KINDS:
    # window caches could be ring buffers; keep full length for simplicity
    return L.attn_init_cache(cfg, batch, max_len, dtype)
  if kind in _MLA_KINDS:
    return MLA.mla_init_cache(cfg, batch, max_len, dtype)
  if kind == "rg":
    return RG.rg_init_state(cfg, batch, dtype)
  if kind == "mlstm":
    return XL.mlstm_init_state(cfg, batch)
  if kind == "slstm":
    return XL.slstm_init_state(cfg, batch)
  raise ValueError(kind)


# ---------------------------------------------------------------------------
# Whole-model init / apply
# ---------------------------------------------------------------------------


def init_params(cfg, key) -> Params:
  dtype = _dtype(cfg)
  keys = jax.random.split(key, 8)
  params: Params = {}
  if cfg.frontend != "audio":
    params["embed"] = L.embed_init(keys[0], cfg.vocab_size, cfg.d_model,
                                   dtype)
  if cfg.num_codebooks:
    for i in range(cfg.num_codebooks):
      params[f"codebook_head_{i}"] = {
          "w": (jax.random.normal(jax.random.fold_in(keys[1], i),
                                  (cfg.d_model, cfg.vocab_size)) *
                (1.0 / math.sqrt(cfg.d_model))).astype(dtype)}
  elif not cfg.tie_embeddings:
    params["lm_head"] = {
        "w": (jax.random.normal(keys[2], (cfg.d_model, cfg.vocab_size)) *
              (1.0 / math.sqrt(cfg.d_model))).astype(dtype)}
  params["final_norm"] = L.norm_init(cfg.d_model, cfg.norm)

  for si, (cycle, reps) in enumerate(cfg.plan_segments()):
    seg: Params = {}
    for j, kind in enumerate(cycle):
      lkeys = jax.random.split(
          jax.random.fold_in(keys[3], si * 64 + j), reps)
      seg[f"l{j}_{kind}"] = jax.vmap(
          lambda k: _layer_init(k, cfg, kind))(lkeys)
    params[f"seg{si}"] = seg
  return params


def _head_weight(cfg, params):
  if cfg.tie_embeddings:
    return params["embed"]["table"].T
  return params["lm_head"]["w"]


def _embed_inputs(cfg, params, batch) -> tuple[Array, Array]:
  """Returns (x (B,S,d), positions (S,))."""
  if cfg.frontend == "audio":
    x = batch["embeds"].astype(_dtype(cfg))     # stub: precomputed frames
  elif cfg.frontend == "vision":
    tok = L.embed_apply(params["embed"], batch["tokens"],
                        scale=cfg.norm == "rmsnorm" and cfg.tie_embeddings)
    img = batch["image_embeds"].astype(tok.dtype)
    x = jnp.concatenate([img, tok], axis=1)
  else:
    x = L.embed_apply(params["embed"], batch["tokens"],
                      scale=cfg.tie_embeddings)
  positions = jnp.arange(x.shape[1])
  return x, positions


def _run_segments(cfg, params, x, positions, *, collect_caches=False):
  """Scan all segments. Returns (x, stats, caches|None)."""
  stats = _no_stats()
  caches: list[Any] = []

  for si, (cycle, reps) in enumerate(cfg.plan_segments()):
    seg_params = params[f"seg{si}"]

    def seg_body(carry, layer_params, cycle=cycle):
      x, stats = carry
      cache_out = {}
      for j, kind in enumerate(cycle):
        x, st, c = _layer_apply_seq(
            layer_params[f"l{j}_{kind}"], x, positions, cfg, kind,
            collect_cache=collect_caches)
        stats = add_stats(stats, st)
        if collect_caches:
          cache_out[f"l{j}_{kind}"] = c
      return (x, stats), cache_out if collect_caches else None

    if cfg.remat == "full":
      seg_body = jax.checkpoint(
          seg_body, policy=jax.checkpoint_policies.nothing_saveable,
          static_argnums=())
    elif cfg.remat == "dots":
      seg_body = jax.checkpoint(
          seg_body, policy=jax.checkpoint_policies.checkpoint_dots)

    (x, stats), seg_caches = lax.scan(seg_body, (x, stats), seg_params)
    caches.append(seg_caches)

  return x, stats, caches if collect_caches else None


def forward_train(cfg, params, batch) -> tuple[Array, dict[str, Array]]:
  """Per-token NLL (B, S_target) + the step's statistics: ``aux_loss``
  (the MoE load-balance loss, summed over layers),
  ``moe_assignments_held`` (summed over layers) and ``moe_busiest_share``
  (the largest of any layer); all zero without MoE layers."""
  x, positions = _embed_inputs(cfg, params, batch)
  x, aux, _ = _run_segments(cfg, params, x, positions)
  x = L.norm_apply(params["final_norm"], x, cfg.norm)

  if cfg.num_codebooks:
    losses = []
    for i in range(cfg.num_codebooks):
      w = params[f"codebook_head_{i}"]["w"]
      losses.append(L.lm_loss_chunked(
          w, x, batch["targets"][..., i], chunk=cfg.xent_chunk,
          softcap=cfg.logit_softcap))
    return jnp.mean(jnp.stack(losses), axis=0), aux
  if cfg.frontend == "vision":
    x = x[:, -batch["tokens"].shape[1]:]        # loss on text region only
  w = _head_weight(cfg, params)
  loss = L.lm_loss_chunked(w, x, batch["targets"], chunk=cfg.xent_chunk,
                           softcap=cfg.logit_softcap)
  return loss, aux


def init_cache(cfg, batch: int, max_len: int) -> list[Any]:
  dtype = _dtype(cfg)
  caches = []
  for cycle, reps in cfg.plan_segments():
    seg = {}
    for j, kind in enumerate(cycle):
      one = _layer_init_cache(cfg, kind, batch, max_len, dtype)
      seg[f"l{j}_{kind}"] = jax.tree.map(
          lambda a: jnp.broadcast_to(a, (reps,) + a.shape), one)
    caches.append(seg)
  return caches


def forward_prefill(cfg, params, batch, max_len: int):
  """Prefill: returns (last-position logits (B, V), caches).

  Attention caches are written at positions [0, S); the returned cache
  tensors are padded to `max_len` so decode can continue in place.
  """
  x, positions = _embed_inputs(cfg, params, batch)
  s = x.shape[1]
  x, _, caches = _run_segments(cfg, params, x, positions,
                               collect_caches=True)
  x = L.norm_apply(params["final_norm"], x, cfg.norm)
  last = x[:, -1]
  if cfg.num_codebooks:
    logits = jnp.stack([
        L.lm_head_logits(params[f"codebook_head_{i}"]["w"], last,
                         cfg.logit_softcap)
        for i in range(cfg.num_codebooks)], axis=1)
  else:
    logits = L.lm_head_logits(_head_weight(cfg, params), last,
                              cfg.logit_softcap)

  def pad_cache(c):
    def pad_leaf(a, proto):
      if a is None:
        return proto
      if a.ndim >= 3 and a.shape[2] == s and proto.shape[2] == max_len:
        pad = [(0, 0)] * a.ndim
        pad[2] = (0, max_len - s)
        return jnp.pad(a, pad).astype(proto.dtype)
      return a.astype(proto.dtype)
    return pad_leaf

  protos = init_cache(cfg, x.shape[0], max_len)
  padded = []
  for got, proto in zip(caches, protos):
    padded.append(jax.tree.map(pad_cache(None), got, proto))
  return logits, padded


def forward_decode(cfg, params, caches, inputs, pos: Array):
  """One decode step.

  inputs: token ids (B,) — or for the audio frontend, a precomputed frame
  embedding (B, d).  pos: scalar int32 current position (cache fill level).
  Returns (logits (B, V) [or (B, K, V)], new caches).
  """
  if cfg.frontend == "audio":
    x = inputs.astype(_dtype(cfg))
  else:
    x = L.embed_apply(params["embed"], inputs, scale=cfg.tie_embeddings)
  x = shard_activation(x, "residual_decode")

  new_caches = []
  for si, (cycle, reps) in enumerate(cfg.plan_segments()):
    seg_params = params[f"seg{si}"]
    seg_cache = caches[si]

    # The stacked cache rides the scan CARRY with indexed in-place updates
    # (not xs->ys, which would allocate a second cache-sized buffer): the
    # donated input then aliases straight through to the output.
    def seg_body(carry, inp, cycle=cycle):
      x, cache_stacked = carry
      i, lp = inp
      new_slices = {}
      for j, kind in enumerate(cycle):
        key = f"l{j}_{kind}"
        lc = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            cache_stacked[key])
        x, c = _layer_apply_decode(lp[key], x, lc, pos, cfg, kind)
        new_slices[key] = c
      cache_stacked = jax.tree.map(
          lambda a, u: lax.dynamic_update_index_in_dim(
              a, u.astype(a.dtype), i, 0),
          cache_stacked, new_slices)
      return (x, cache_stacked), None

    reps_idx = jnp.arange(reps)
    (x, new_seg), _ = lax.scan(
        seg_body, (x, seg_cache), (reps_idx, seg_params))
    new_caches.append(new_seg)

  x = L.norm_apply(params["final_norm"], x, cfg.norm)
  if cfg.num_codebooks:
    logits = jnp.stack([
        L.lm_head_logits(params[f"codebook_head_{i}"]["w"], x,
                         cfg.logit_softcap)
        for i in range(cfg.num_codebooks)], axis=1)
  else:
    logits = L.lm_head_logits(_head_weight(cfg, params), x,
                              cfg.logit_softcap)
    logits = shard_activation(logits, "logits_decode")
  return logits, new_caches


def count_params(params) -> int:
  return sum(x.size for x in jax.tree.leaves(params))
