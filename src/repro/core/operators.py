"""Soft sorting and ranking operators (paper Eq. 5-6) and derived top-k.

Conventions follow the paper: the *descending* direction is primitive;
`rho = (n, n-1, ..., 1)`; rank 1 is assigned to the largest entry under the
descending direction.  All operators act on the last axis and accept
arbitrary leading batch dimensions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.permutations import SortContext
from repro.core.projection import projection_permutahedron

Array = jax.Array

_DIRECTIONS = ("ASCENDING", "DESCENDING")


def _rho(n: int, dtype) -> Array:
  return jnp.arange(n, 0, -1, dtype=dtype)


def _ctx_perm(sort_context: SortContext | None, descending: bool):
  """(sigma, sigma^{-1}) of the context's values in the given direction.

  Tie order may differ from a fresh argsort of the transformed argument
  (operators negate/scale their input before projecting), which is
  harmless: equal values merge into one isotonic block either way.
  """
  if sort_context is None:
    return None
  _, sigma, sigma_inv = (sort_context.descending() if descending
                         else sort_context.ascending())
  return sigma, sigma_inv


def soft_sort(
    values: Array,
    regularization_strength: float = 1.0,
    regularization: str = "l2",
    direction: str = "DESCENDING",
    impl: str | None = None,
    plan=None,
    sort_context: SortContext | None = None,
) -> Array:
  """Soft sort: s_{eps*Psi}(theta) = P_Psi(rho/eps, theta) (paper Eq. 5).

  Parameters
  ----------
  values : Array, shape (..., n)
      Input scores; the operator acts on the last axis, arbitrary leading
      batch dimensions are supported.
  regularization_strength : float
      eps > 0. As eps -> 0 the output approaches the hard sort (exactly
      hard for eps <= eps_min, Lemma 3); as eps -> inf it collapses
      toward a constant vector (l2) / rescaling (kl).
  regularization : {"l2", "kl"}
      Psi. "l2" is the paper's quadratic Q; "kl" the entropic E
      (projection carried out in log space).
  direction : {"DESCENDING", "ASCENDING"}
      "DESCENDING" (paper primitive) returns values softly sorted from
      largest to smallest; "ASCENDING" is -soft_sort(-values).
  impl : {"auto", "lax", "scan", "dense", "pallas", "minimax"} or None
      Isotonic backend; None defers to the unified precedence chain
      (``repro.kernels.dispatch``). Pass explicitly under jit/grad.
  plan : repro.plan.ExecutionPlan or None
      Pin an execution plan for all of this call's dispatch decisions;
      rides the custom VJP as a static argument, so it survives jit.
  sort_context : SortContext or None
      A ``SortContext`` built on ``values``; supplies the argsort
      permutation so several operators over the same tensor share one
      sort (trace-local — see the class docstring for the jit caveat).

  Returns
  -------
  Array, shape (..., n)
      The soft-sorted vector(s).

  Notes
  -----
  Cost is O(n log n) per row — one descending sort plus a linear-time
  PAV isotonic solve (paper §5) — versus O(n^2) for All-pairs and
  O(T n^2) for OT/Sinkhorn relaxations. The backward pass is the exact
  O(n) segment-algebra VJP of Lemma 2, never unrolled solver iterates.
  The projection's z argument (rho/eps) is descending by construction,
  so the fused pipeline (``repro.core.projection``) skips that sort
  entirely via ``z_is_sorted``.
  """
  if direction not in _DIRECTIONS:
    raise ValueError(f"direction must be one of {_DIRECTIONS}")
  values = jnp.asarray(values)
  eps = regularization_strength
  n = values.shape[-1]
  descending = direction == "DESCENDING"
  # ASCENDING is -P(rho/eps, -theta): same sorted z, negated weights.
  w = values if descending else -values
  z = jnp.broadcast_to(_rho(n, values.dtype) / eps, values.shape)
  out = projection_permutahedron(
      z, w, regularization, impl, plan=plan, z_is_sorted=True,
      w_perm=_ctx_perm(sort_context, descending=descending))
  return out if descending else -out


def soft_rank(
    values: Array,
    regularization_strength: float = 1.0,
    regularization: str = "l2",
    direction: str = "DESCENDING",
    impl: str | None = None,
    plan=None,
    sort_context: SortContext | None = None,
) -> Array:
  """Soft rank: r_{eps*Psi}(theta) = P_Psi(-theta/eps, rho) (paper Eq. 6).

  Parameters
  ----------
  values : Array, shape (..., n)
      Input scores (last axis; arbitrary leading batch dimensions).
  regularization_strength : float
      eps > 0; eps -> 0 recovers the hard ranks exactly (Lemma 3),
      larger eps trades fidelity for smoother gradients.
  regularization : {"l2", "kl"}
      Psi: quadratic Q or entropic E (paper §3).
  direction : {"DESCENDING", "ASCENDING"}
      "DESCENDING" (paper default): rank 1 for the largest value.
      "ASCENDING": rank 1 for the smallest ( = descending rank of
      -theta ).
  impl : {"auto", "lax", "scan", "dense", "pallas", "minimax"} or None
      Isotonic backend; see ``repro.kernels.dispatch``. Pass explicitly
      under jit/grad.
  plan : repro.plan.ExecutionPlan or None
      Pin an execution plan for all of this call's dispatch decisions;
      rides the custom VJP as a static argument, so it survives jit.
  sort_context : SortContext or None
      A ``SortContext`` built on ``values``; supplies the argsort
      permutation so several operators over the same tensor share one
      sort (trace-local — see the class docstring for the jit caveat).

  Returns
  -------
  Array, shape (..., n)
      Soft ranks in [1, n]; differentiable everywhere in theta.

  Notes
  -----
  O(n log n) per row (sort + linear PAV, §5) with the exact O(n) VJP of
  Lemma 2 — the differentiability does not cost an O(n^2) Jacobian.
  The projection's weight rho is descending by construction, so the
  fused pipeline never sorts it (``w_is_sorted``).
  """
  if direction not in _DIRECTIONS:
    raise ValueError(f"direction must be one of {_DIRECTIONS}")
  values = jnp.asarray(values)
  eps = regularization_strength
  n = values.shape[-1]
  descending = direction == "DESCENDING"
  # DESCENDING projects -theta/eps; ASCENDING is the descending rank of
  # -theta, i.e. projects +theta/eps.  Sorting z descending is sorting
  # theta ascending (resp. descending), which a SortContext serves.
  z = (-values if descending else values) / eps
  w = _rho(n, values.dtype)
  return projection_permutahedron(
      z, w, regularization, impl, plan=plan, w_is_sorted=True,
      z_perm=_ctx_perm(sort_context, descending=not descending))


def soft_rank_kl_direct(
    values: Array, regularization_strength: float = 1.0,
    direction: str = "DESCENDING",
    impl: str | None = None,
    plan=None,
    sort_context: SortContext | None = None) -> Array:
  """Appendix variant r~_E: KL projection directly onto P(rho), not P(e^rho).

  r~_{eps E}(theta) = exp(P_E(-theta/eps, log rho)).

  Parameters
  ----------
  values : Array, shape (..., n)
      Input scores (last axis).
  regularization_strength : float
      eps > 0.
  direction : {"DESCENDING", "ASCENDING"}
      "DESCENDING" (paper default): rank 1 for the largest value;
      "ASCENDING" is the descending variant of -theta.
  impl : {"auto", "lax", "scan", "dense", "pallas", "minimax"} or None
      Isotonic backend (``repro.kernels.dispatch``).
  plan : repro.plan.ExecutionPlan or None
      Pin an execution plan for all of this call's dispatch decisions.
  sort_context : SortContext or None
      A ``SortContext`` built on ``values`` (shares the argsort with
      other operators over the same tensor; trace-local under jit).

  Returns
  -------
  Array, shape (..., n)
      Strictly positive soft ranks (the exp of a log-space projection).

  Notes
  -----
  Same O(n log n) forward / O(n) backward as ``soft_rank``; only the
  target polytope differs (paper appendix discussion of r~_E).  The
  weight log(rho) is descending by construction (log is monotone), so
  the fused pipeline never sorts it.
  """
  if direction not in _DIRECTIONS:
    raise ValueError(f"direction must be one of {_DIRECTIONS}")
  values = jnp.asarray(values)
  eps = regularization_strength
  n = values.shape[-1]
  descending = direction == "DESCENDING"
  z = (-values if descending else values) / eps
  w = jnp.log(_rho(n, values.dtype))
  return jnp.exp(projection_permutahedron(
      z, w, "kl", impl, plan=plan, w_is_sorted=True,
      z_perm=_ctx_perm(sort_context, descending=not descending)))


def soft_topk_mask(
    values: Array,
    k: int,
    regularization_strength: float = 1.0,
    regularization: str = "l2",
    impl: str | None = None,
    plan=None,
    sort_context: SortContext | None = None,
) -> Array:
  """Differentiable top-k indicator in [0, 1]^n summing to k.

  Projection of theta/eps onto P(w) with w = (1,...,1,0,...,0) (k ones):
  the vertices of that permutahedron are exactly the 0/1 indicators of
  k-subsets, so the projection is the canonical soft top-k selector
  built from the paper's machinery (cf. §6.1's O(n log k) remark).

  Parameters
  ----------
  values : Array, shape (..., n)
      Selection scores (last axis).
  k : int
      Number of entries softly selected, 1 <= k <= n.
  regularization_strength : float
      eps > 0; small eps approaches the hard 0/1 top-k mask.
  regularization : {"l2", "kl"}
      Psi for the projection.
  impl : {"auto", "lax", "scan", "dense", "pallas", "minimax"} or None
      Isotonic backend (``repro.kernels.dispatch``).
  plan : repro.plan.ExecutionPlan or None
      Pin an execution plan for all of this call's dispatch decisions.

  Returns
  -------
  Array, shape (..., n)
      Mask in [0, 1]^n with sum k (exactly, by the projection's
      marginals); gradients flow to every entry, unlike hard top-k.

  Notes
  -----
  O(n log n) per row via the generic reduction (a specialized
  O(n log k) variant is possible, §6.1, but the generic path is what
  the MoE router benchmarks exercise — see
  ``repro.kernels.ops.soft_topk_gates`` for the fused kernel).
  """
  values = jnp.asarray(values)
  eps = regularization_strength
  n = values.shape[-1]
  # The k-ones mask is descending by construction: never sorted.
  w = jnp.concatenate([
      jnp.ones((k,), values.dtype),
      jnp.zeros((n - k,), values.dtype),
  ])
  return projection_permutahedron(
      values / eps, w, regularization, impl, plan=plan, w_is_sorted=True,
      z_perm=_ctx_perm(sort_context, descending=True))


def soft_quantile(
    values: Array,
    q: float,
    regularization_strength: float = 0.1,
    regularization: str = "l2",
    impl: str | None = None,
    plan=None,
    sort_context: SortContext | None = None,
) -> Array:
  """Differentiable q-quantile via the soft sort (ascending).

  Parameters
  ----------
  values : Array, shape (..., n)
      Samples (last axis).
  q : float
      Quantile in [0, 1]; the index round(q * (n-1)) of the ascending
      soft sort is returned (q=0.5 is a soft median).
  regularization_strength : float
      eps > 0 for the underlying soft sort (Eq. 5).
  regularization : {"l2", "kl"}
      Psi for the projection.
  impl : {"auto", "lax", "scan", "dense", "pallas", "minimax"} or None
      Isotonic backend (``repro.kernels.dispatch``).
  plan : repro.plan.ExecutionPlan or None
      Pin an execution plan for all of this call's dispatch decisions.
  sort_context : SortContext or None
      A ``SortContext`` built on ``values``: the underlying ascending
      soft sort reuses the caller's argsort instead of re-sorting.

  Returns
  -------
  Array, shape (...)
      The soft q-quantile per batch row (one scalar per row).

  Notes
  -----
  O(n log n) per row — inherited from ``soft_sort``; gradients spread
  over neighboring order statistics instead of the single hard sample.
  """
  values = jnp.asarray(values)
  n = values.shape[-1]
  s = soft_sort(values, regularization_strength, regularization,
                direction="ASCENDING", impl=impl, plan=plan,
                sort_context=sort_context)
  idx = jnp.clip(jnp.asarray(round(q * (n - 1)), jnp.int32), 0, n - 1)
  return s[..., idx]


# ---------------------------------------------------------------------------
# Exact-regime thresholds (paper Lemma 3) -- used by tests and EXPERIMENTS.md
# to validate the asymptotic claims *exactly* rather than approximately.
# ---------------------------------------------------------------------------


def eps_min(s: Array, w: Array) -> Array:
  """Largest eps at which P_Psi(z/eps, w) equals the hard operator.

  Parameters
  ----------
  s : Array, shape (..., n)
      Sorted-descending inputs, s = z_sigma(z).
  w : Array, shape (..., n)
      Sorted-descending target weights.

  Returns
  -------
  Array, shape (...)
      eps_min = min_i (s_i - s_{i+1}) / (w_i - w_{i+1}); for
      eps <= eps_min the soft operator is *exactly* the hard one
      (paper Lemma 3) — used by tests to validate asymptotics exactly.

  Notes
  -----
  O(n) per row (one pass over adjacent differences).
  """
  ds = s[..., :-1] - s[..., 1:]
  dw = w[..., :-1] - w[..., 1:]
  return jnp.min(ds / dw, axis=-1)


def eps_max(s: Array, w: Array) -> Array:
  """Smallest eps beyond which the solution is the closed-form constant.

  Parameters
  ----------
  s : Array, shape (..., n)
      Sorted-descending inputs.
  w : Array, shape (..., n)
      Sorted-descending target weights.

  Returns
  -------
  Array, shape (...)
      eps_max = max_{i<j} (s_i - s_j) / (w_i - w_j) (paper Lemma 3's
      other endpoint): beyond it every PAV block has merged and the
      projection is the fully-pooled closed form.

  Notes
  -----
  O(n^2) per row (all pairs) — a diagnostic for tests/analysis, not a
  production path.
  """
  n = s.shape[-1]
  i, j = jnp.triu_indices(n, k=1)
  num = s[..., i] - s[..., j]
  den = w[..., i] - w[..., j]
  return jnp.max(num / den, axis=-1)
