"""Projections onto the permutahedron (paper §4-§5, Prop. 3).

  P_Psi(z, w) = z - v_Psi(z_sigma(z), sort_desc(w))_{sigma^{-1}(z)}

`P(w)` is permutation-invariant in `w`, so `w` need not be sorted by the
caller.  Three registered pipelines compute it (dispatch registry keys
``("projection", regularization, path)``, selected by
``repro.kernels.dispatch.resolve_projection`` through the unified chain —
explicit ``path=`` > env ``REPRO_PROJECTION`` > execution plan; the
built-in plan resolves to ``"sortperm"`` on TPU and ``"fused"``
elsewhere):

``"fused"`` (CPU, GPU)
    The whole pipeline is ONE ``jax.custom_vjp``: packed single-key
    integer sorts (``repro.core.permutations.argsort_descending_fast`` /
    ``invert_permutation_fast`` — the XLA integer-sort fast path, ~4x
    faster than comparator argsorts at n=1024), an explicitly-computed
    inverse permutation so the un-permute is a *gather* instead of the
    ``apply_inverse_permutation`` scatter, and a backward pass that reuses
    the residuals saved by the forward (sigma, sigma^{-1}, the solver's
    segment structure) — gather -> segmented scan -> gather, with no
    re-sort and no scatter.  Static ``z_is_sorted`` / ``w_is_sorted``
    flags skip sorts the caller guarantees (every built-in operator
    passes a by-construction-sorted argument on one side), and
    precomputed ``z_perm`` / ``w_perm`` permutations (from
    ``repro.core.permutations.SortContext``) let multi-operator callers
    pay for one argsort.  Unbatched *concrete* weights hit a small
    process-level sorted-``w`` cache, so eager eps sweeps never re-sort
    the same weight vector.

``"sortperm"`` (TPU)
    The same custom VJP (``by_sort``), with every permutation a key-value
    ``lax.sort`` that carries the data instead of an element-wise gather
    (about 10 ns an element on TPU, where a sort of the row costs far
    less): one stable sort yields the sorted values and sigma, the
    un-permute is a sort keyed by sigma that also returns sigma^{-1} as
    its iota payload, and the backward's two permutations and the weight
    gradient's are sorts keyed by sigma^{-1}, sigma and tau.  The keys
    are distinct, so the values are bitwise those a gather would read;
    no permutation is ever inverted, so nothing scatters at any n.

``"composed"``
    The reference chain of four differentiable primitives — descending
    sorts, isotonic solve, inverse-permutation scatter — kept reachable
    (``REPRO_PROJECTION=composed``) for differential testing of the fused
    path; its backward is whatever JAX derives by composition.

Batched-first in both paths: `z` may carry arbitrary leading batch
dimensions, there is ONE isotonic dispatch per call and no per-row Python
loop or vmap anywhere.  When `w` is unbatched (shape (n,)) it is sorted
exactly once and broadcast into the solver; its gradient still accumulates
correctly over the batch.
"""

from __future__ import annotations

import functools
import hashlib
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.isotonic import isotonic_kl, isotonic_l2
from repro.core.permutations import (
    apply_inverse_permutation,
    argsort_descending_fast,
    inverse_permutation,
    invert_permutation_fast,
    permute_by_sort,
    sort_descending,
    sort_descending_with_perm,
)
from repro.kernels import dispatch as _dispatch
from repro.kernels import segment_vjp as _svjp
from repro.obs import metrics as _metrics

Array = jax.Array

_REGS = ("l2", "kl")
_HALF_DTYPES = (jnp.bfloat16, jnp.float16)


# ---------------------------------------------------------------------------
# Composed reference pipeline (the pre-fusion implementation, unchanged).
# ---------------------------------------------------------------------------


def _composed_projection(regularization: str, z: Array, w: Array,
                         impl: str | None, plan=None, *,
                         z_is_sorted: bool = False,
                         w_is_sorted: bool = False, z_perm=None,
                         w_perm=None) -> Array:
  """z: (..., n); w: (n,) or broadcastable to z.shape.

  The reference path deliberately ignores the sortedness hints and
  re-derives everything through composed differentiable primitives —
  that is exactly what the fused path is differentially tested against.
  """
  del z_is_sorted, w_is_sorted, z_perm, w_perm
  if w.ndim == 1:
    # Unbatched weights: one sort, shared across every row of the batch.
    w_sorted, _ = sort_descending(w)
  else:
    w_sorted, _ = sort_descending(jnp.broadcast_to(w, z.shape))
  s, sigma = sort_descending(z)
  if regularization == "l2":
    v = isotonic_l2(s - w_sorted, impl, plan)
  else:
    v = isotonic_kl(s, w_sorted, impl, plan)
  # out = z - v_{sigma^{-1}}, i.e. out[sigma_k] = z[sigma_k] - v[k].
  return z - apply_inverse_permutation(v, sigma)


# ---------------------------------------------------------------------------
# Sorted-weight cache for concrete unbatched weights (eager fast path).
# ---------------------------------------------------------------------------

_W_CACHE_CAP = 64
_w_sorted_cache: OrderedDict[tuple, tuple] = OrderedDict()


def _sorted_w_unbatched(ws: Array) -> tuple[Array, Array, Array]:
  """(w sorted desc, tau, tau^{-1}) for an unbatched weight row.

  Concrete (non-tracer) weights are sorted once per distinct vector in a
  small bounded process cache — an eager eps sweep re-projecting onto the
  same permutahedron pays for exactly one weight sort.  Tracers (under
  jit the weights are abstract) go through the packed fast sort.
  """
  if isinstance(ws, jax.core.Tracer):
    w_sorted, tau = argsort_descending_fast(ws)
    return w_sorted, tau, invert_permutation_fast(tau)
  host = np.asarray(ws)
  key = (host.shape, str(host.dtype),
         hashlib.sha1(host.tobytes()).hexdigest())
  hit = key in _w_sorted_cache
  _metrics.counter_inc("sort_reuse_hit" if hit else "sort_reuse_miss",
                       source="w_cache")
  if hit:
    _w_sorted_cache.move_to_end(key)
  else:
    tau = np.argsort(-host, kind="stable").astype(np.int32)
    inv = np.argsort(tau, kind="stable").astype(np.int32)
    while len(_w_sorted_cache) >= _W_CACHE_CAP:
      _w_sorted_cache.popitem(last=False)
    _w_sorted_cache[key] = (host[tau], tau, inv)
  w_sorted, tau, inv = _w_sorted_cache[key]
  return jnp.asarray(w_sorted), jnp.asarray(tau), jnp.asarray(inv)


# ---------------------------------------------------------------------------
# Fused pipeline: one custom VJP around sort + solve + gather.
# ---------------------------------------------------------------------------


def _permute(x, perm, perm_inv, by_sort):
  """``x[..., perm]``: a gather reading ``perm``, or (``by_sort``) one
  key-value sort keyed by ``perm^{-1}`` — the same values, bitwise."""
  if by_sort:
    return permute_by_sort(perm_inv, x)[0]
  return jnp.take_along_axis(x, perm, axis=-1)


def _fused_forward(regularization, impl, plan, by_sort, z_is_sorted,
                   w_is_sorted, z, w, z_perm, w_perm):
  """Shared primal: returns (out, residuals).

  This function is staged *inside* the custom_vjp, where the packed u64
  sort fast path miscompiles (see ``_fused_entry``), so the sorts below
  are comparator key-value sorts.  ``fused`` dispatch callers never hit
  them: ``_fused_entry`` precomputes ``z_perm`` / ``w_perm`` with the
  fast path in the surrounding trace context.  ``sortperm`` sorts here,
  and with ``by_sort`` every permutation is a sort that carries the data
  (``repro.core.permutations.permute_by_sort``), never a gather.
  """
  n = z.shape[-1]
  zs = lax.stop_gradient(z)
  if z_is_sorted:
    s, sigma, sigma_inv = zs, None, None
  elif z_perm is not None:
    sigma, sigma_inv = z_perm
    s = _permute(zs, sigma, sigma_inv, by_sort)
  else:
    s, sigma = sort_descending_with_perm(zs)
    # by_sort: sigma^{-1} comes out of the un-permute below.
    sigma_inv = None if by_sort else inverse_permutation(sigma)

  ws = lax.stop_gradient(w)
  if ws.ndim > 1 and ws.shape != z.shape:
    ws = jnp.broadcast_to(ws, z.shape)
  tau = tau_inv = None
  if w_is_sorted:
    w_sorted = ws
  elif w_perm is not None:
    tau, tau_inv = w_perm
    w_sorted = _permute(ws, tau, tau_inv, by_sort)
  else:
    w_sorted, tau = sort_descending_with_perm(ws)
    if not by_sort:
      tau_inv = inverse_permutation(tau)

  if regularization == "l2":
    y = s - w_sorted                       # broadcasts unbatched w_sorted
    v = _dispatch.dispatch("isotonic", "l2", impl, y, plan=plan)
    w_b = None
  else:
    w_b = jnp.broadcast_to(w_sorted, s.shape)
    v = _dispatch.dispatch("isotonic", "kl", impl, s, w_b, plan=plan)

  vd = lax.stop_gradient(v)
  starts = _svjp.block_starts(vd.reshape(-1, n)).reshape(v.shape)
  start_idx, end_idx = _svjp.start_end_indices(starts.reshape(-1, n))
  start_idx = start_idx.reshape(v.shape)
  end_idx = end_idx.reshape(v.shape)

  if sigma is None:
    v_out = v
  elif sigma_inv is None:
    # One sort keyed by sigma returns v_{sigma^{-1}} and, from an iota
    # payload, sigma^{-1} itself for the backward pass.
    v_out, sigma_inv = permute_by_sort(
        sigma, v, lax.broadcasted_iota(sigma.dtype, sigma.shape,
                                       sigma.ndim - 1))
  else:
    v_out = _permute(v, sigma_inv, sigma, by_sort)
  out = z - v_out
  res = (sigma, sigma_inv, tau, tau_inv, starts, start_idx, end_idx,
         s if regularization == "kl" else None, w_b, lax.stop_gradient(w))
  return out, res


def _unbroadcast(g: Array, shape: tuple[int, ...]) -> Array:
  """Sum a full-batch cotangent down to a broadcast-origin shape."""
  if g.shape == tuple(shape):
    return g
  extra = g.ndim - len(shape)
  if extra:
    g = g.sum(axis=tuple(range(extra)))
  axes = tuple(i for i, (a, b) in enumerate(zip(g.shape, shape))
               if b == 1 and a != 1)
  if axes:
    g = g.sum(axis=axes, keepdims=True)
  return g.reshape(shape)


def _perm_cotangent(perm):
  """Symbolic-zero (float0) cotangents for integer permutation inputs."""
  return jax.tree_util.tree_map(
      lambda a: np.zeros(np.shape(a), jax.dtypes.float0), perm)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _fused_projection(regularization, impl, plan, by_sort, z_is_sorted,
                      w_is_sorted, z, w, z_perm, w_perm):
  return _fused_forward(regularization, impl, plan, by_sort, z_is_sorted,
                        w_is_sorted, z, w, z_perm, w_perm)[0]


def _fused_fwd(regularization, impl, plan, by_sort, z_is_sorted,
               w_is_sorted, z, w, z_perm, w_perm):
  out, res = _fused_forward(regularization, impl, plan, by_sort,
                            z_is_sorted, w_is_sorted, z, w, z_perm, w_perm)
  return out, res + (z_perm, w_perm)


def _fused_bwd(regularization, impl, plan, by_sort, z_is_sorted,
               w_is_sorted, res, g):
  """Whole-pipeline VJP from saved residuals: permute -> segmented
  reduction (Lemma 2, dispatched backward table) -> permute.  No re-sort
  of the data, no scatter; each permutation is a gather, or with
  ``by_sort`` a sort keyed by the other of (sigma, sigma^{-1})."""
  del impl, z_is_sorted
  (sigma, sigma_inv, tau, tau_inv, starts, start_idx, end_idx, s, w_b,
   w_orig, z_perm, w_perm) = res

  # d out / d v is -I composed with the sigma^{-1} un-permute: permute
  # the cotangent into sorted order.
  g_v = -(g if sigma is None else _permute(g, sigma, sigma_inv, by_sort))
  if regularization == "l2":
    g_y = _dispatch.dispatch_backward("projection", "l2", None,
                                      g_v, starts, start_idx, end_idx,
                                      plan=plan)
    g_s, g_ws = g_y, -g_y
  else:
    g_s, g_ws = _dispatch.dispatch_backward("projection", "kl", None,
                                            s, w_b, g_v, starts,
                                            start_idx, end_idx, plan=plan)

  # z cotangent: identity term plus the solve term mapped back through
  # sigma^{-1} (both permutations are already residuals).
  g_z = g + (g_s if sigma is None else
             _permute(g_s, sigma_inv, sigma, by_sort))

  # w cotangent: back from sorted order through tau^{-1}, then
  # un-broadcast (sum) onto the original weight shape.
  if w_orig.ndim == 1:
    g_w = _unbroadcast(g_ws, w_orig.shape)
    if not w_is_sorted:
      g_w = _permute(g_w, tau_inv, tau, by_sort)
  else:
    if not w_is_sorted:
      g_ws = _permute(g_ws, tau_inv, tau, by_sort)
    g_w = _unbroadcast(g_ws, w_orig.shape)
  return g_z, g_w, _perm_cotangent(z_perm), _perm_cotangent(w_perm)


_fused_projection.defvjp(_fused_fwd, _fused_bwd)


def _fused_entry(regularization: str, z: Array, w: Array, impl: str | None,
                 plan=None, *, z_is_sorted: bool = False,
                 w_is_sorted: bool = False, z_perm=None,
                 w_perm=None) -> Array:
  """Precompute the sort permutations OUTSIDE the custom_vjp, then project.

  The packed u64 argsort (``argsort_descending_fast``) must not be staged
  inside a custom_vjp body: when the custom_vjp sub-jaxpr is lowered with
  global x64 off, the size-changing u32(..., 2) -> u64 bitcast is
  re-canonicalized to a shape-preserving u32 -> u32 no-op, and the single
  packed sort silently splits into two *independent* word sorts — the
  sorted values (high word) still come out right, but the permutation
  payload (low word) degenerates to identity.  Plain jit and eager lower
  the bitcast correctly.  The sorts are nondifferentiable residuals
  (``stop_gradient``) in any case, so they run here, in the surrounding
  trace context, and enter the custom_vjp as ``z_perm`` / ``w_perm``
  (tests/test_projection_fused.py::test_fused_matches_eager_under_jit is
  the regression guard).
  """
  z = jnp.asarray(z)
  if not z_is_sorted and z_perm is None:
    _, sigma = argsort_descending_fast(lax.stop_gradient(z))
    z_perm = (sigma, invert_permutation_fast(sigma))
  if not w_is_sorted and w_perm is None:
    ws = lax.stop_gradient(jnp.asarray(w, z.dtype))
    if ws.ndim > 1 and ws.shape != z.shape:
      ws = jnp.broadcast_to(ws, z.shape)
    if ws.ndim == 1:
      _, tau, tau_inv = _sorted_w_unbatched(ws)
    else:
      _, tau = argsort_descending_fast(ws)
      tau_inv = invert_permutation_fast(tau)
    w_perm = (tau, tau_inv)
  return _fused_projection(regularization, impl, plan, False,
                           bool(z_is_sorted), bool(w_is_sorted), z, w,
                           z_perm, w_perm)


def _sortperm_entry(regularization: str, z: Array, w: Array,
                    impl: str | None, plan=None, *,
                    z_is_sorted: bool = False, w_is_sorted: bool = False,
                    z_perm=None, w_perm=None) -> Array:
  """The fused pipeline with every permutation a sort carrying the data.

  Nothing is precomputed: the comparator sorts are safe inside the
  custom_vjp, and each yields the values it permutes (sorted z and sigma
  from one sort, v_{sigma^{-1}} and sigma^{-1} from another), so no
  gather reads them back and no permutation is inverted.
  """
  return _fused_projection(regularization, impl, plan, True,
                           bool(z_is_sorted), bool(w_is_sorted),
                           jnp.asarray(z), w, z_perm, w_perm)


for _reg in _REGS:
  _dispatch.register("projection", _reg, "fused")(
      functools.partial(_fused_entry, _reg))
  _dispatch.register("projection", _reg, "sortperm")(
      functools.partial(_sortperm_entry, _reg))
  _dispatch.register("projection", _reg, "composed")(
      functools.partial(_composed_projection, _reg))


# ---------------------------------------------------------------------------
# Public API.
# ---------------------------------------------------------------------------


def projection_permutahedron(
    z: Array, w: Array, regularization: str = "l2",
    impl: str | None = None, *, path: str | None = None, plan=None,
    z_is_sorted: bool = False, w_is_sorted: bool = False,
    z_perm=None, w_perm=None) -> Array:
  """Project `z` onto the permutahedron generated by `w` (paper §4).

  Computes P_Psi(z, w) = z - v_Psi(z_sigma(z), sort_desc(w))_{sigma^{-1}}
  (Prop. 3): one descending sort, one isotonic solve, one un-permute.

  Parameters
  ----------
  z : Array, shape (..., n)
      Point(s) to project (last axis; arbitrary leading batch dims).
  w : Array, shape (n,) or broadcastable to z.shape
      Permutahedron generator. P(w) is permutation-invariant in w, so w
      need not be sorted. An unbatched (n,) w is sorted once and
      broadcast into the solver (no per-row re-sort); its gradient
      still accumulates correctly through the broadcast.
  regularization : {"l2", "kl"}
      "l2": Euclidean projection onto P(w). "kl": the paper's log-KL
      projection of e^z onto P(e^w), returned in log space (P_E).
  impl : {"auto", "lax", "scan", "dense", "pallas", "minimax"} or None
      Isotonic backend (``repro.kernels.dispatch``); pass explicitly
      under jit/grad (see ``isotonic_l2`` for why).
  path : {"auto", "fused", "sortperm", "composed"} or None
      Pipeline selection; None defers to env ``REPRO_PROJECTION`` then
      the execution-plan chain (the built-in plan resolves to
      ``"sortperm"`` on TPU, ``"fused"`` elsewhere).
  plan : repro.plan.ExecutionPlan or None
      Pin an execution plan for every decision this call makes (forward
      backend, backward backend, projection path).  Rides the fused
      custom VJP as a static argument, so — unlike ``use_plan`` — it
      survives jit and governs the lazily-traced backward too.
  z_is_sorted, w_is_sorted : bool
      Caller guarantees the argument is already descending along the
      last axis — the fused path skips that sort entirely.  (The
      composed reference path ignores the hints and always re-sorts.)
  z_perm, w_perm : (sigma, sigma^{-1}) int32 pairs or None
      Precomputed descending-argsort permutations for the respective
      argument (e.g. from ``repro.core.permutations.SortContext``) —
      the fused path replaces its packed sorts with two gathers
      (``sortperm``: with two sorts keyed by the inverses).

  Returns
  -------
  Array, shape broadcast(z, w)
      The projection, same shape as the broadcast inputs.

  Notes
  -----
  O(n log n) per row — the sort dominates; the PAV solve is O(n) after
  sorting (§5) versus O(n^2) for all-pairs relaxations. The fused
  default carries a whole-pipeline custom VJP (residuals: sigma,
  sigma^{-1}, solver segment structure) whose backward is
  gather -> segmented scan -> gather — exact (Lemma 2), O(n), no
  re-sort, no scatter, never differentiation through solver iterates.
  """
  if regularization not in _REGS:
    raise ValueError(f"regularization must be one of {_REGS}")
  z = jnp.asarray(z)
  w = jnp.asarray(w, z.dtype)
  dtype = z.dtype
  if dtype in _HALF_DTYPES:
    # Promote before the pipeline (not just the solve): the fused path's
    # packed integer sort keys assume f32, so the whole projection runs
    # promoted and only the result is demoted.
    out = _dispatch.dispatch_projection(
        _shift_row_max_to_zero(z.astype(jnp.float32)), w.astype(jnp.float32),
        regularization, impl, path, plan=plan, z_is_sorted=z_is_sorted,
        w_is_sorted=w_is_sorted, z_perm=z_perm, w_perm=w_perm)
    return out.astype(dtype)
  return _dispatch.dispatch_projection(
      _shift_row_max_to_zero(z), w, regularization, impl, path, plan=plan,
      z_is_sorted=z_is_sorted, w_is_sorted=w_is_sorted, z_perm=z_perm,
      w_perm=w_perm)


def _shift_row_max_to_zero(z: Array) -> Array:
  """z minus its row max, held out of the gradient.

  Exact: every point of a permutahedron has the same coordinate sum, so
  both projections are unchanged when one constant is added to a whole
  row of z (and so is their Jacobian).  It keeps ``out = z - v`` from
  cancelling in float32 when |z| is large, as ``values / eps`` is at
  small eps.  Padding below a row's real entries leaves its max alone,
  so the serving padding stays exact.
  """
  return z - lax.stop_gradient(jnp.max(z, axis=-1, keepdims=True))
