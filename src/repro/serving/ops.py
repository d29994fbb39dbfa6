"""Padded batched op family with *exact* (bitwise) padding semantics.

The batcher pads every request up to its shape bucket.  Naive padding
(zeros, repeats of the last element) changes the isotonic problem: pads
can pool with real entries and perturb every output lane.  This module
constructs pads so that, per row with true length ``n`` inside a bucket
of width ``N``:

1. **Pads sort strictly below every real entry** — so after the
   descending sort the real entries occupy positions ``0..n-1`` in the
   same order as the unpadded call, and all prefix arithmetic (the lax
   sequential PAV, the pow2-aligned d&c merge tree of the ``scan``
   backend, and the index-0-aligned ``associative_scan`` of ``minimax``)
   is performed on bitwise-identical operands.
2. **No isotonic block ever pools across the real/pad boundary** — the
   first pad sits below the smallest achievable real block value by a
   margin ``M(N) = 132 + 2*log(N+1)``, and successive pads keep
   descending by at least that margin, so PAV never merges across the
   boundary and minimax's crossing intervals always lose the inner max.
3. **KL stays bitwise too** — the 132 in the margin exceeds the float32
   ``exp`` underflow threshold (~104), so every log-sum-exp that crosses
   into the pad region adds ``exp(pad - acc) == 0.0`` *exactly* and
   ``logaddexp`` returns the real-prefix accumulator bit-for-bit.

The result: ``padded_op(values_padded)[..., :n]`` is bitwise equal to
the unpadded operator per backend for soft_sort / soft_rank / soft_topk
/ projection (property-tested in tests/test_padding_invariance.py).
Scalar losses (Spearman, LTS) are masked reductions over those exact
vectors; their reduce tree differs between ``n`` and ``N`` so they are
allclose, not bitwise.

Every op takes the uniform traced signature

    fn(values (B, N) f32, true_n (B,) i32, eps (B,) f32, *extras)

with per-request parameters (``eps``, ``k``, ``trim``) as *traced*
per-row arrays — so one compiled executable serves any mix of request
parameters and the AOT cache key stays ``(op, variant, rows, bucket)``.
Static variant choices (regularization, direction) are baked into
module-level ``functools.partial`` objects, giving each variant a
process-stable callable identity (jit trace caches and
``dispatch.stable_entry`` rely on this).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.losses import soft_spearman_loss  # noqa: F401  (doc x-ref)
from repro.core.projection import projection_permutahedron

Array = jax.Array

#: Extra argument kinds: a scalar per row, or a full (B, N) row vector.
EXTRA_SCALAR = "scalar_per_row"
EXTRA_VECTOR = "row_vector"


def margin(bucket_n: int) -> float:
  """Separation margin between consecutive pad entries.

  128 clears the float32 ``exp`` underflow threshold (exp(x) == 0.0 for
  x < -103.98) with slack; ``2*log(N+1)`` absorbs log-sum-exp
  accumulation over up to N terms on both sides of a KL block value;
  +4 is headroom for the last-ulp of masked min/max reductions.
  """
  return 128.0 + 2.0 * math.log(bucket_n + 1.0) + 4.0


def _row_geometry(values: Array, true_n: Array):
  """(idx, mask, tail_k) for a (B, N) batch.

  ``mask`` is True on real lanes; ``tail_k`` counts pad positions
  1, 2, ... within the pad region (arbitrary <= 0 on real lanes).
  """
  n_bucket = values.shape[-1]
  idx = jnp.arange(n_bucket, dtype=jnp.int32)[None, :]
  nn = true_n[:, None]
  mask = idx < nn
  tail_k = (idx - nn + 1).astype(values.dtype)
  return idx, nn, mask, tail_k


def _masked_min(x: Array, mask: Array) -> Array:
  return jnp.min(jnp.where(mask, x, jnp.inf), axis=-1, keepdims=True)


def _masked_max(x: Array, mask: Array) -> Array:
  return jnp.max(jnp.where(mask, x, -jnp.inf), axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# The padded operators.
#
# Shared shape of the argument: real prefix reproduces the unpadded
# operator's (z, w) bit-for-bit; the pad tail extends z strictly
# descending with per-step drop D >= margin(N) (plus the weight range
# where the weights vary), and extends w weakly descending strictly
# below (or equal-at-the-bottom to) the real weights.
# ---------------------------------------------------------------------------


def _padded_soft_sort(values: Array, true_n: Array, eps: Array, *,
                      regularization: str, direction: str,
                      impl=None, plan=None) -> Array:
  """Bucket-padded soft_sort; out[:, :n] bitwise == unpadded soft_sort."""
  descending = direction == "DESCENDING"
  vv = values if descending else -values
  idx, nn, mask, tail_k = _row_geometry(values, true_n)
  e = eps[:, None]
  # Real prefix: z = rho_n / eps exactly ((n - idx) is integer-exact in
  # f32).  Pads keep descending by 1/eps + D per step.
  z_ladder = (nn - idx).astype(values.dtype) / e
  mn_v = _masked_min(vv, mask)
  d_step = (_masked_max(vv, mask) - mn_v) + margin(values.shape[-1])
  z = jnp.where(mask, z_ladder, z_ladder - tail_k * d_step)
  w = jnp.where(mask, vv, mn_v - 1.0)
  out = projection_permutahedron(
      z, w, regularization, impl, plan=plan, z_is_sorted=True)
  out = out if descending else -out
  return jnp.where(mask, out, 0.0)


def _padded_soft_rank(values: Array, true_n: Array, eps: Array, *,
                      regularization: str, direction: str,
                      impl=None, plan=None) -> Array:
  """Bucket-padded soft_rank; out[:, :n] bitwise == unpadded soft_rank."""
  descending = direction == "DESCENDING"
  idx, nn, mask, tail_k = _row_geometry(values, true_n)
  e = eps[:, None]
  z_real = (-values if descending else values) / e
  # Whole-row weight ladder (n, n-1, ..., 1, 0, -1, ...): the real
  # prefix is exactly rho_n and the tail keeps strictly descending, so
  # w_is_sorted holds for the full bucket row.
  w = (nn - idx).astype(values.dtype)
  mn_z = _masked_min(z_real, mask)
  d_step = values.shape[-1] + margin(values.shape[-1])
  z = jnp.where(mask, z_real, mn_z - tail_k * d_step)
  out = projection_permutahedron(
      z, w, regularization, impl, plan=plan, w_is_sorted=True)
  return jnp.where(mask, out, 0.0)


def _padded_soft_topk(values: Array, true_n: Array, eps: Array, k: Array, *,
                      regularization: str, impl=None, plan=None) -> Array:
  """Bucket-padded soft_topk_mask with per-row traced k."""
  idx, nn, mask, tail_k = _row_geometry(values, true_n)
  e = eps[:, None]
  z_real = values / e
  # k ones then zeros — pads fall in the zero region, so the whole-row
  # indicator is the real weight vector extended by (exact) zeros.
  w = (idx < k[:, None]).astype(values.dtype)
  mn_z = _masked_min(z_real, mask)
  z = jnp.where(mask, z_real, mn_z - tail_k * margin(values.shape[-1]))
  out = projection_permutahedron(
      z, w, regularization, impl, plan=plan, w_is_sorted=True)
  return jnp.where(mask, out, 0.0)


def _padded_projection(values: Array, true_n: Array, eps: Array, w: Array, *,
                       regularization: str, impl=None, plan=None) -> Array:
  """Bucket-padded generic P_Psi(z, w); ``values`` is z, ``eps`` unused
  (kept for the uniform serving signature)."""
  del eps
  idx, nn, mask, tail_k = _row_geometry(values, true_n)
  mn_z = _masked_min(values, mask)
  mn_w = _masked_min(w, mask)
  d_step = (_masked_max(w, mask) - mn_w) + margin(values.shape[-1])
  z_pad = jnp.where(mask, values, mn_z - tail_k * d_step)
  w_pad = jnp.where(mask, w, mn_w - 1.0)
  out = projection_permutahedron(z_pad, w_pad, regularization, impl, plan=plan)
  return jnp.where(mask, out, 0.0)


def _padded_spearman(values: Array, true_n: Array, eps: Array,
                     target: Array, *, regularization: str, direction: str,
                     impl=None, plan=None) -> Array:
  """Per-row soft Spearman loss over bucket-padded rows.

  Masked reduction over the exact padded soft_rank — allclose to the
  unpadded loss (the sum's reduce tree differs between n and N).
  """
  ranks = _padded_soft_rank(values, true_n, eps,
                            regularization=regularization,
                            direction=direction, impl=impl, plan=plan)
  _, _, mask, _ = _row_geometry(values, true_n)
  sq = jnp.where(mask, (ranks - target) ** 2, 0.0)
  return 0.5 * jnp.sum(sq, axis=-1)


def _padded_lts(values: Array, true_n: Array, eps: Array, trim: Array, *,
                regularization: str, impl=None, plan=None) -> Array:
  """Per-row soft least-trimmed-squares loss over bucket-padded rows."""
  s = _padded_soft_sort(values, true_n, eps, regularization=regularization,
                        direction="DESCENDING", impl=impl, plan=plan)
  idx, nn, mask, _ = _row_geometry(values, true_n)
  kept = mask & (idx >= trim[:, None])
  total = jnp.sum(jnp.where(kept, s, 0.0), axis=-1)
  denom = (true_n - trim).astype(values.dtype)
  return total / denom


# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OpSpec:
  """One servable (op, regularization, direction) variant.

  ``fn`` has the uniform traced signature
  ``fn(values, true_n, eps, *extras, impl=..., plan=...)`` and is a
  module-level ``functools.partial`` (stable identity per process).
  """

  op: str
  regularization: str
  direction: str                       # "" when the op has no direction
  extras: tuple[tuple[str, str, str], ...]  # (name, dtype, kind)
  output: str                          # "vector" | "scalar"
  exact: bool                          # bitwise padding contract holds
  fn: Callable

  @property
  def key(self) -> str:
    parts = [self.op, self.regularization]
    if self.direction:
      parts.append("desc" if self.direction == "DESCENDING" else "asc")
    return "/".join(parts)


def _specs() -> dict[str, OpSpec]:
  out: dict[str, OpSpec] = {}

  def add(spec: OpSpec):
    out[spec.key] = spec

  for reg in ("l2", "kl"):
    for direction in ("DESCENDING", "ASCENDING"):
      add(OpSpec("soft_sort", reg, direction, (), "vector", True,
                 functools.partial(_padded_soft_sort, regularization=reg,
                                   direction=direction)))
      add(OpSpec("soft_rank", reg, direction, (), "vector", True,
                 functools.partial(_padded_soft_rank, regularization=reg,
                                   direction=direction)))
      add(OpSpec("spearman", reg, direction,
                 (("target", "float32", EXTRA_VECTOR),), "scalar", False,
                 functools.partial(_padded_spearman, regularization=reg,
                                   direction=direction)))
    add(OpSpec("soft_topk", reg, "",
               (("k", "int32", EXTRA_SCALAR),), "vector", True,
               functools.partial(_padded_soft_topk, regularization=reg)))
    add(OpSpec("projection", reg, "",
               (("w", "float32", EXTRA_VECTOR),), "vector", True,
               functools.partial(_padded_projection, regularization=reg)))
    add(OpSpec("lts", reg, "",
               (("trim", "int32", EXTRA_SCALAR),), "scalar", False,
               functools.partial(_padded_lts, regularization=reg)))
  return out


#: key ("soft_sort/l2/desc", "lts/kl", ...) -> OpSpec
SERVING_OPS: dict[str, OpSpec] = _specs()


def padded_op(key: str) -> OpSpec:
  """Look up an OpSpec by its key, with a helpful error."""
  try:
    return SERVING_OPS[key]
  except KeyError:
    raise KeyError(
        f"unknown serving op {key!r}; expected one of "
        f"{sorted(SERVING_OPS)}") from None


@functools.lru_cache(maxsize=None)
def bound_op(key: str, impl: str | None = None, plan=None) -> Callable:
  """``spec.fn`` with backend/plan pinned, with stable identity.

  Same (key, impl, plan) -> same callable object, so ``jax.jit`` trace
  caches and the serving AOT cache see one function per configuration
  (``ExecutionPlan`` is hashable by design).  The companion for raw
  dispatch entries is ``repro.kernels.dispatch.stable_entry``.
  """
  spec = padded_op(key)
  fn = functools.partial(spec.fn, impl=impl, plan=plan)
  # JAX lowers an unnamed partial as ``jit(<unknown>)``; named, its build
  # records (repro.obs.tracing) name the function it wraps.
  fn.__name__ = fn.func.__name__
  return fn
