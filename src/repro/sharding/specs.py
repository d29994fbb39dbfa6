"""Sharding rules: parameter specs and activation constraints.

Divisibility-aware: a rule names the *preferred* mesh axes per tensor dim;
axes that do not divide the dim fall back to replication (e.g.
recurrentgemma's 10 attention heads or xlstm's 4 cannot shard over a
16-way model axis, so those archs shard head_dim / features instead).

Activation constraints are applied through a context so the same model code
runs un-annotated on CPU tests and fully annotated under the production
mesh (`use_rules(...)`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array

_STATE = threading.local()


@dataclasses.dataclass
class ShardingRules:
  mesh: Mesh
  data_axes: tuple[str, ...] = ("data",)    # ("pod","data") multi-pod
  model_axis: str = "model"
  seq_shard_activations: bool = False
  fsdp: bool = False

  # ----- helpers -----

  def axis_size(self, name: str) -> int:
    return self.mesh.shape[name]

  def _fit(self, dim: int, axes: tuple[str, ...] | str | None,
           used: set[str] | None = None):
    """Return axes (or prefix) whose product divides dim, else None.

    Axes already consumed by earlier dims of the same spec are skipped."""
    if axes is None:
      return None
    if isinstance(axes, str):
      axes = (axes,)
    if used is not None:
      axes = tuple(a for a in axes if a not in used)
    if not axes:
      return None
    for cut in range(len(axes), 0, -1):
      sub = axes[:cut]
      t = 1
      for a in sub:
        t *= self.axis_size(a)
      if dim % t == 0:
        return sub if len(sub) > 1 else sub[0]
    return None

  def spec(self, shape: tuple[int, ...], wanted: tuple[Any, ...]) -> P:
    assert len(shape) == len(wanted), (shape, wanted)
    used: set[str] = set()
    parts = []
    for d, a in zip(shape, wanted):
      fit = self._fit(d, a, used)
      parts.append(fit)
      if fit is not None:
        used.update((fit,) if isinstance(fit, str) else fit)
    return P(*parts)

  @property
  def dp(self):
    return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]

  @property
  def tp(self):
    return self.model_axis


# Parameter rules: (path regex, wanted axes per dim). First match wins.
# `DP` and `TP` are placeholders resolved against the live rules;
# `FSDP` resolves to DP when rules.fsdp else None.
DP, TP, FSDP = "__DP__", "__TP__", "__FSDP__"
# __ALL__: every mesh axis (data axes + model), for embarrassingly
# parallel dims like MoE routing groups or long-context KV sequence dims.
ALL = "__ALL__"

PARAM_RULES: list[tuple[str, tuple[Any, ...]]] = [
    (r".*embed/table$", (TP, FSDP)),               # (vocab, d)
    (r".*lm_head/w$", (FSDP, TP)),                 # (d, vocab)
    (r".*codebook_head_\d+/w$", (FSDP, TP)),       # (d, codebook_vocab)
    (r".*attn/wq$", (FSDP, TP, None)),             # (d, H, Dh)
    (r".*attn/wk$", (FSDP, TP, None)),
    (r".*attn/wv$", (FSDP, TP, None)),
    (r".*attn/wo$", (TP, None, FSDP)),             # (H, Dh, d)
    (r".*mla/wq$", (FSDP, TP, None)),              # (d, H, nope+rope)
    (r".*mla/w_dkv$", (FSDP, None)),               # (d, r+rope)
    (r".*mla/w_uk$", (None, TP, None)),            # (r, H, nope)
    (r".*mla/w_uv$", (None, TP, None)),            # (r, H, v)
    (r".*mla/wo$", (TP, None, FSDP)),              # (H, v, d)
    (r".*ffn/router$", None),                      # (d, E) replicated
    (r".*ffn/we_in$", (TP, FSDP, "__MOE_FF__")),   # (E, d, f): E over model
                                                   # (refused by moe.py)
    (r".*ffn/we_gate$", (TP, FSDP, "__MOE_FF__")),
    (r".*ffn/we_out$", (TP, "__MOE_FF__", FSDP)),  # (E, f, d)
    (r".*ffn/(shared/)?w_in$", (FSDP, TP)),        # (d, f) dense/shared MLP
    (r".*ffn/(shared/)?w_gate$", (FSDP, TP)),
    (r".*ffn/(shared/)?w_out$", (TP, FSDP)),       # (f, d)
    (r".*rg/(w_x|w_gate)$", (FSDP, TP)),           # (d, lru)
    (r".*rg/w_out$", (TP, FSDP)),                  # (lru, d)
    (r".*rg/(a_param|conv_w.*|gate_w.*|gate_b.*)", None),  # small, replicate
    (r".*lstm/w_(q|k|v)$", (FSDP, None, TP)),      # (d, H, dh): shard dh
    (r".*lstm/.*", None),
    (r".*(norm|scale|bias).*", None),
]


def _resolve(rules: ShardingRules, wanted):
  out = []
  for a in wanted:
    if a == DP:
      out.append(rules.data_axes)
    elif a == TP:
      out.append(rules.model_axis)
    elif a == FSDP:
      out.append(rules.data_axes if rules.fsdp else None)
    elif a == "__ALL__":
      out.append(rules.data_axes + (rules.model_axis,))
    elif a == "__MOE_FF__":
      # expert-ffn dim: use model axis only if expert dim could not take it
      out.append(rules.model_axis)
    else:
      out.append(a)
  return tuple(out)


def param_spec(rules: ShardingRules, path: str, shape: tuple[int, ...]) -> P:
  for pat, wanted in PARAM_RULES:
    if re.match(pat, path):
      if wanted is None:
        return P()
      resolved = _resolve(rules, wanted)
      # Scanned segments stack params with a leading repeats dim (never
      # sharded): left-pad the rule to the actual rank.
      if len(shape) > len(resolved):
        resolved = (None,) * (len(shape) - len(resolved)) + resolved
      elif len(shape) < len(resolved):
        return P()
      spec = rules.spec(shape, resolved)
      # MoE: prefer sharding the expert dim; if it took the model axis,
      # drop model from the ffn dim to avoid double use.
      parts = list(spec)
      seen: set[str] = set()
      for i, s in enumerate(parts):
        names = (s,) if isinstance(s, str) else tuple(s or ())
        if any(n in seen for n in names):
          parts[i] = None
        seen.update(names)
      return P(*parts)
  return P()


def param_specs_tree(rules: ShardingRules, params: Any) -> Any:
  def one(path, leaf):
    pstr = "/".join(str(getattr(k, "key", k)) for k in path)
    return param_spec(rules, pstr, leaf.shape)
  return jax.tree_util.tree_map_with_path(one, params)


# Decode-cache rules: (leaf-name regex, ndim) -> wanted axes. Cache leaves
# are segment-stacked: leading dim = scan repeats (never sharded).
CACHE_RULES: list[tuple[str, int, tuple[Any, ...]]] = [
    # attn KV (r,B,S,H,D): batch over data, sequence over whatever is left
    # (for long_500k's global_batch=1, S takes ALL 512 ways).
    (r"(k|v)$", 5, (None, DP, ALL, None, None)),
    (r"c_kv$", 4, (None, DP, ALL, None)),         # MLA latent (r,B,S,r)
    (r"k_rope$", 4, (None, DP, ALL, None)),
    (r"h$", 3, (None, DP, TP)),                   # rg-lru state (r,B,L)
    (r"conv$", 4, (None, DP, None, TP)),          # rg conv hist (r,B,W,L)
    (r"c$", 5, (None, DP, None, None, TP)),       # mlstm C (r,B,H,dk,dv)
    (r"(c|n|m|h)$", 4, (None, DP, None, TP)),     # per-head vec states
    (r"m$", 3, (None, DP, None)),                 # mlstm stabilizer (r,B,H)
]


def cache_spec(rules: ShardingRules, path: str, shape: tuple[int, ...]) -> P:
  leaf = path.rsplit("/", 1)[-1]
  # Attention KV (reps, B, S, H, D): prefer head sharding (attention stays
  # fully local per device); fall back to sequence sharding (flash-decode
  # combine territory) when the kv-head count cannot take the model axis.
  if len(shape) == 5 and re.search(r"(k|v)$", leaf):
    heads = shape[3]
    if heads % rules.axis_size(rules.model_axis) == 0:
      return rules.spec(shape, _resolve(rules, (None, DP, None, TP, None)))
    return rules.spec(shape, _resolve(rules, (None, DP, ALL, None, None)))
  for pat, ndim, wanted in CACHE_RULES:
    if len(shape) == ndim and re.search(pat, leaf):
      return rules.spec(shape, _resolve(rules, wanted))
  return P()


def cache_specs_tree(rules: ShardingRules, cache: Any) -> Any:
  def one(path, leaf):
    pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)
    return cache_spec(rules, pstr, leaf.shape)
  return jax.tree_util.tree_map_with_path(one, cache)


def batch_specs_tree(rules: ShardingRules, batch: Any) -> Any:
  def one(path, leaf):
    spec = [rules.data_axes] + [None] * (len(leaf.shape) - 1)
    return rules.spec(leaf.shape, tuple(spec))
  return jax.tree_util.tree_map_with_path(one, batch)


def opt_state_specs_tree(rules: ShardingRules, opt_state: Any,
                         param_specs: Any) -> Any:
  """Adam moments mirror the param specs; scalars/history replicated."""

  def adam_specs(adam):
    out = dict(adam)
    out["step"] = P()
    out["m"] = param_specs
    out["v"] = param_specs
    if "norm_history" in adam:
      out["norm_history"] = P()
    return out

  out = {}
  for k, v in opt_state.items():
    if k == "adam":
      out[k] = adam_specs(v)
    elif k == "ef_residual":
      out[k] = param_specs
    else:
      out[k] = jax.tree.map(lambda _: P(), v)
  return out


# ---------------------------------------------------------------------------
# Activation constraints (context-scoped).
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def use_rules(rules: ShardingRules | None):
  prev = getattr(_STATE, "rules", None)
  _STATE.rules = rules
  try:
    yield
  finally:
    _STATE.rules = prev


def current_rules() -> ShardingRules | None:
  return getattr(_STATE, "rules", None)


# Activation kinds -> wanted axes (resolved lazily, divisibility-checked).
_ACT_RULES: dict[str, tuple[Any, ...]] = {
    "residual": (DP, "__SEQ__", None),         # (B, S, d)
    "residual_decode": (DP, None),             # (B, d)
    "heads": (DP, None, TP, None),             # (B, S, H, Dh)
    "heads_decode": (DP, TP, None),            # (B, H, Dh)
    "kv_cache": (DP, TP, None, None),          # (B, S, Hkv, Dh): seq-shard
    "kv_cache_batch": (DP, None, None, None),  # alt: batch-only
    "logits": (DP, None, TP),                  # (B, S, V)
    "logits_decode": (DP, TP),                 # (B, V)
    "ffn": (DP, None, TP),                     # (B, S, f)
    "rg_state": (DP, TP),                      # (B, lru)
    "mlstm_state": (DP, None, None, TP),       # (B, H, dk, dv)
    "tokens": (DP, None),                      # (B, S)
}


def shard_activation(x: Array, kind: str) -> Array:
  """Apply a named sharding constraint if rules are active, else no-op."""
  rules = current_rules()
  if rules is None:
    return x
  wanted = list(_resolve(rules, _ACT_RULES[kind]))
  # __SEQ__: shard sequence over model axis only when enabled.
  for i, a in enumerate(wanted):
    if a == "__SEQ__":
      wanted[i] = rules.model_axis if rules.seq_shard_activations else None
  if len(wanted) != x.ndim:
    return x
  spec = rules.spec(x.shape, tuple(wanted))
  return jax.lax.with_sharding_constraint(
      x, NamedSharding(rules.mesh, spec))
