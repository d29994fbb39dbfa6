"""Sorting helpers that are safe to differentiate in this environment.

This jax build carries an old-style ``GatherDimensionNumbers`` (no
``operand_batching_dims``) while ``_sort_jvp`` passes the new kwargs, so any
attempt to differentiate through ``lax.sort`` / ``jnp.sort`` / ``argsort``
raises.  The a.e.-correct gradient of sorting is "apply the (locally
constant) permutation to the cotangent", so we compute permutations under
``stop_gradient`` and apply them with plain gathers — mathematically
identical to sort's own JVP rule, and robust here.  (Documented in
DESIGN.md §10.)

Fast path
---------
XLA:CPU (and GPU) have a radix-style fast path for *single-operand integer*
sorts, while any variadic/comparator sort (``argsort``, value+index pair
sorts, float sorts) falls back to a ~4-6x slower comparison sort.
``argsort_descending_fast`` exploits this: f32 keys are bitcast to u32,
mapped through the order-preserving total order on float bits, packed with
the position index into one u64 word (``bitcast_convert_type`` of a
trailing ``(..., 2)`` u32 axis — no 64-bit constants, so it lowers cleanly
whatever the x64 mode), and sorted as a single integer key.  The low word
of the result is a stable argsort permutation and the high word unpacks
*bit-exactly* to the sorted values.  ``invert_permutation_fast`` applies
the same trick to invert a permutation without a scatter.  The only
divergence from ``jnp.argsort`` semantics: ``-0.0`` and ``+0.0`` are
ordered by their (distinct) bit patterns rather than treated as equal keys
— numerically irrelevant downstream, where equal values merge into one
isotonic block anyway.

Permuting by sorting (TPU)
--------------------------
TPU has no 64-bit integers, and an element-wise gather there costs about
10 ns an element — far more than a sort of the same row.  Sorting the
pairs ``(p_k, x_k)`` by the distinct int32 keys ``p`` leaves ``x_k`` at
position ``p_k``, i.e. it computes ``x[p^{-1}]`` as exact data movement:
``permute_by_sort`` does that for any number of payloads, and
``sort_descending_with_perm`` yields the sorted values and sigma from one
stable key-value sort.  The TPU projection route (``"sortperm"`` in
``repro.core.projection``) applies every permutation this way, and gets
sigma^{-1} as a payload of its un-permute, so it never inverts a
permutation; ``invert_permutation_fast`` (for ``SortContext``) inverts
past the u32 pack limit with one such sort.

Staging caveat: the packed fast path must NOT be traced inside a
``jax.custom_vjp`` body.  Lowering a custom_vjp sub-jaxpr with global x64
off re-canonicalizes the size-changing u32 -> u64 bitcast into a
shape-preserving u32 no-op, which splits the packed sort into independent
word sorts (the permutation payload silently becomes identity).  Callers
that wrap a pipeline in custom_vjp (the fused projection) compute these
sorts in the surrounding trace context and pass the permutations in as
residual arguments instead.

All permutations produced by this module are int32 end-to-end (an n that
overflows int32 would OOM long before the index dtype matters).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

_INT = jnp.int32
_SIGN_BIT = 0x80000000
# u32 inverse packing needs sigma * n + iota < 2**32.
_U32_INVERT_MAX_N = 65535


def argsort_descending(x: Array, axis: int = -1) -> Array:
  """Non-differentiable descending argsort (stable, int32)."""
  return jnp.argsort(-lax.stop_gradient(x), axis=axis,
                     stable=True).astype(_INT)


def argsort_ascending(x: Array, axis: int = -1) -> Array:
  return jnp.argsort(lax.stop_gradient(x), axis=axis,
                     stable=True).astype(_INT)


def sort_descending_with_perm(x: Array) -> tuple[Array, Array]:
  """(x sorted descending, sigma int32) along the last axis, detached.

  One stable key-value sort of ``(-x, iota)`` — exactly the sort
  ``argsort_descending`` builds, with the same tie order — whose keys are
  the sorted values (negation is exact), so no gather reads them back.
  """
  x = lax.stop_gradient(x)
  iota = lax.broadcasted_iota(_INT, x.shape, x.ndim - 1)
  neg, sigma = lax.sort((-x, iota), dimension=-1, is_stable=True,
                        num_keys=1)
  return -neg, sigma


def permute_by_sort(keys: Array, *operands: Array) -> tuple[Array, ...]:
  """Each operand moved so that ``out[..., keys[k]] = x[..., k]``.

  ``keys`` is a permutation along the last axis (int32, the operands'
  shape), so one unstable key-value sort does it exactly:
  ``x[..., p]`` is ``permute_by_sort(p^{-1}, x)``, ``x[..., p^{-1}]`` is
  ``permute_by_sort(p, x)``, and an iota payload returns ``p^{-1}``.
  """
  return tuple(lax.sort((keys, *operands), dimension=-1, is_stable=False,
                        num_keys=1)[1:])


def sort_descending(x: Array) -> tuple[Array, Array]:
  """Differentiable descending sort along the last axis.

  Returns (sorted values, permutation sigma) with gradient flowing through
  the gather (the exact a.e. Jacobian of sorting: the permutation matrix).
  """
  sigma = argsort_descending(x)
  return jnp.take_along_axis(x, sigma, axis=-1), sigma


def inverse_permutation(sigma: Array) -> Array:
  """sigma^{-1} along the last axis (int32)."""
  sigma = sigma.astype(_INT)
  n = sigma.shape[-1]
  iota = jnp.broadcast_to(jnp.arange(n, dtype=_INT), sigma.shape)
  out = jnp.zeros_like(sigma)
  return jnp.put_along_axis(out, sigma, iota, axis=-1, inplace=False)


def apply_inverse_permutation(v: Array, sigma: Array) -> Array:
  """Compute v_{sigma^{-1}} (paper notation) differentiably.

  out[sigma_k] = v_k — a scatter whose transpose is the matching gather.
  """
  out = jnp.zeros_like(v)
  return jnp.put_along_axis(out, sigma, v, axis=-1, inplace=False)


# ---------------------------------------------------------------------------
# Packed single-key sorts (the integer-sort fast path).
# ---------------------------------------------------------------------------


def _packed_sort_u64(hi: Array, lo: Array) -> tuple[Array, Array]:
  """Ascending sort of the u64 keys (hi << 32) | lo; returns (hi, lo) sorted.

  Packing is a size-changing ``bitcast_convert_type`` of a trailing
  ``(..., 2)`` u32 axis (little-endian: element 0 is the low word), which
  avoids 64-bit *constants* entirely: jaxpr constants are re-canonicalized
  to 32 bits at lowering time when global x64 is off, so a
  ``jnp.uint64(32)`` shift amount would miscompile even inside an
  ``enable_x64`` trace scope.
  """
  with jax.enable_x64(True):
    packed = lax.bitcast_convert_type(jnp.stack([lo, hi], axis=-1),
                                      jnp.uint64)
    skeys = lax.sort(packed, dimension=-1, is_stable=False)
    unpacked = lax.bitcast_convert_type(skeys, jnp.uint32)
  return unpacked[..., 1], unpacked[..., 0]


def _f32_total_order_keys(x: Array, descending: bool) -> Array:
  """u32 keys whose unsigned order is the total order on f32 bit patterns."""
  b = lax.bitcast_convert_type(x, jnp.uint32)
  sign = jnp.uint32(_SIGN_BIT)
  asc = jnp.where((b & sign) != 0, ~b, b | sign)
  return ~asc if descending else asc


def _keys_to_f32(keys: Array, descending: bool) -> Array:
  """Invert ``_f32_total_order_keys`` — bit-exact value recovery."""
  sign = jnp.uint32(_SIGN_BIT)
  asc = ~keys if descending else keys
  b = jnp.where((asc & sign) != 0, asc & ~sign, ~asc)
  return lax.bitcast_convert_type(b, jnp.float32)


def _fast_sort_ok(x: Array) -> bool:
  """Packed u64 path: f32 keys only, and not on TPU (no 64-bit integers)."""
  return (x.dtype == jnp.float32 and x.ndim >= 1
          and jax.default_backend() != "tpu")


def argsort_descending_fast(x: Array) -> tuple[Array, Array]:
  """(sorted values, sigma int32) descending along the last axis.

  Single u64 integer sort on f32/CPU/GPU (~4x faster than the comparator
  argsort at n=1024); falls back to ``sort_descending_with_perm`` for
  other dtypes/platforms.  Non-differentiable: both
  outputs are detached — callers on the fused projection path own their
  gradients.
  """
  x = lax.stop_gradient(x)
  if not _fast_sort_ok(x):
    return sort_descending_with_perm(x)
  n = x.shape[-1]
  keys = _f32_total_order_keys(x, descending=True)
  iota = jnp.broadcast_to(jnp.arange(n, dtype=jnp.uint32), x.shape)
  skeys, sigma = _packed_sort_u64(keys, iota)
  return _keys_to_f32(skeys, descending=True), sigma.astype(_INT)


def invert_permutation_fast(sigma: Array) -> Array:
  """sigma^{-1} (int32) without a scatter: one packed integer sort.

  For n <= 65535 the (position-in-sorted-order, original-index) pair packs
  into a single u32 key (``sigma * n + iota``); larger n uses the u64
  pack, or on TPU (no u64) a key-value sort keyed by sigma.
  """
  n = sigma.shape[-1]
  if jax.default_backend() == "tpu" and n > _U32_INVERT_MAX_N:
    sigma = sigma.astype(_INT)
    return permute_by_sort(
        sigma, lax.broadcasted_iota(_INT, sigma.shape, sigma.ndim - 1))[0]
  iota = jnp.broadcast_to(jnp.arange(n, dtype=jnp.uint32), sigma.shape)
  sig_u = sigma.astype(jnp.uint32)
  if n <= _U32_INVERT_MAX_N:
    packed = sig_u * jnp.uint32(n) + iota
    inv = lax.sort(packed, dimension=-1, is_stable=False) % jnp.uint32(n)
  else:
    _, inv = _packed_sort_u64(sig_u, iota)
  return inv.astype(_INT)


# ---------------------------------------------------------------------------
# Sort reuse across operators.
# ---------------------------------------------------------------------------


class SortContext:
  """Caches the argsort of one tensor so several operators share one sort.

  Build it once on the raw values and pass it to every soft operator that
  sees the *same* tensor (``soft_rank`` twice in a Spearman loss, the
  ``soft_sort``/``soft_quantile`` pair, an eps sweep over identical
  scores): each direction's (sorted values, sigma, sigma^{-1}) triple is
  computed on first use and served from cache afterwards, recorded as
  ``sort_reuse_hit`` in ``repro.obs.metrics``.

  Trace-time caveat: the cache holds *traced* arrays, so a context is only
  valid within the jit trace (or eager region) whose ``values`` it was
  built from — build it inside the jitted function, next to the operator
  calls that share it.
  """

  def __init__(self, values: Array):
    self.values = jnp.asarray(values)
    self._cache: dict[bool, tuple[Array, Array, Array]] = {}

  def _get(self, descending: bool) -> tuple[Array, Array, Array]:
    hit = descending in self._cache
    if not hit:
      x = self.values if descending else -self.values
      s, sigma = argsort_descending_fast(x)
      self._cache[descending] = (s if descending else -s, sigma,
                                 invert_permutation_fast(sigma))
    from repro.obs import metrics as _metrics  # lazy: keep import light
    _metrics.counter_inc("sort_reuse_hit" if hit else "sort_reuse_miss",
                         source="sort_context")
    return self._cache[descending]

  def descending(self) -> tuple[Array, Array, Array]:
    """(values sorted descending, sigma, sigma^{-1}), all detached."""
    return self._get(True)

  def ascending(self) -> tuple[Array, Array, Array]:
    """(values sorted ascending, sigma, sigma^{-1}), all detached."""
    return self._get(False)
