"""Soft sort/rank operator semantics vs the paper's claims (Prop. 2, Lemma 3)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    eps_max, eps_min, hard_rank, soft_quantile, soft_rank,
    soft_rank_kl_direct, soft_sort, soft_topk_mask)

rng = np.random.default_rng(1)


def test_paper_figure1_example():
  theta = jnp.array([2.9, 0.1, 1.2])
  # Paper Fig. 1: r(theta) = (1, 3, 2); with eps=1 (Q) the soft rank is
  # exactly the hard rank.
  np.testing.assert_allclose(soft_rank(theta, 1.0, "l2"), [1., 3., 2.],
                             atol=1e-6)


@pytest.mark.parametrize("reg", ["l2", "kl"])
def test_exact_hard_regime_below_eps_min(reg):
  """Lemma 3: for eps <= eps_min the soft operators are EXACTLY hard."""
  n = 6
  local = np.random.default_rng(42)   # deterministic: eps_min is data-dep
  theta = jnp.array(local.normal(size=n).astype(np.float32)) * 2
  rho = jnp.arange(n, 0, -1).astype(jnp.float32)
  # soft rank: z = -theta/eps, w = rho
  s_sorted = jnp.flip(jnp.sort(-theta))
  emin = float(eps_min(s_sorted, rho))
  eps = emin * 0.5
  ranks = soft_rank(theta, eps, reg)
  np.testing.assert_allclose(ranks, hard_rank(theta, "DESCENDING"),
                             atol=1e-3)
  # sort: z = rho/eps, w = sort(theta); exact for eps <= eps_min(rho, w).
  # Too-small eps costs f32 precision (z ~ rho/eps cancellation), so use
  # the largest eps inside the exact regime.
  w_sorted = jnp.flip(jnp.sort(theta))
  emin_s = float(eps_min(rho, w_sorted))
  eps_s = min(emin_s * 0.5, 0.5)
  sorted_vals = soft_sort(theta, eps_s, reg)
  np.testing.assert_allclose(
      sorted_vals, w_sorted, atol=1e-3)


def test_constant_regime_above_eps_max():
  """Lemma 3: for eps > eps_max the solution is the closed-form constant."""
  n = 5
  theta = jnp.array(rng.normal(size=n).astype(np.float32))
  rho = jnp.arange(n, 0, -1).astype(jnp.float32)
  z = -theta
  s_sorted = jnp.flip(jnp.sort(z))
  emax = float(eps_max(s_sorted, rho))
  eps = emax * 2 + 1.0
  r = soft_rank(theta, eps, "l2")
  # P_Q(z/eps, w) = z/eps - mean(z/eps - w) 1
  want = z / eps - jnp.mean(z / eps - rho)
  np.testing.assert_allclose(r, want, atol=1e-5)


@pytest.mark.parametrize("reg", ["l2", "kl"])
def test_order_preservation(reg):
  """Prop. 2.2: soft sort non-increasing; soft ranks ordered like -theta."""
  theta = jnp.array(rng.normal(size=(8, 12)).astype(np.float32))
  s = soft_sort(theta, 0.7, reg)
  assert bool(jnp.all(s[:, :-1] >= s[:, 1:] - 1e-5))
  r = soft_rank(theta, 0.7, reg)
  sigma = jnp.argsort(-theta, axis=-1)
  r_sig = jnp.take_along_axis(r, sigma, axis=-1)
  assert bool(jnp.all(r_sig[:, :-1] <= r_sig[:, 1:] + 1e-5))


def test_asymptote_large_eps():
  theta = jnp.array([0.0, 3.0, 1.0, 2.0])
  np.testing.assert_allclose(
      soft_sort(theta, 1e7), jnp.full(4, jnp.mean(theta)), atol=1e-3)
  np.testing.assert_allclose(
      soft_rank(theta, 1e7), jnp.full(4, 2.5), atol=1e-3)


def test_sum_conservation():
  """Projection lands on the permutahedron: coordinate sums are invariant."""
  theta = jnp.array(rng.normal(size=(3, 9)).astype(np.float32))
  np.testing.assert_allclose(
      jnp.sum(soft_sort(theta, 0.3), -1), jnp.sum(theta, -1), rtol=1e-4)
  np.testing.assert_allclose(
      jnp.sum(soft_rank(theta, 0.3), -1),
      jnp.full(3, 9 * 10 / 2), rtol=1e-5)


def test_directions():
  theta = jnp.array([0.0, 3.0, 1.0, 2.0])
  np.testing.assert_allclose(
      soft_rank(theta, 1e-4, direction="ASCENDING"), [1., 4., 2., 3.],
      atol=1e-3)
  np.testing.assert_allclose(
      soft_sort(theta, 1e-4, direction="ASCENDING"), [0., 1., 2., 3.],
      atol=1e-3)


def test_kl_direct_variant_hard_limit():
  theta = jnp.array([0.0, 3.0, 1.0, 2.0])
  # f32 LSE precision at theta/eps ~ 3e5 leaves ~1% residue.
  np.testing.assert_allclose(
      soft_rank_kl_direct(theta, 1e-5), [4., 1., 3., 2.], atol=5e-2)


def test_topk_mask_hard_limit_and_sum():
  theta = jnp.array([3., 1., 2., 0., -1.])
  m = soft_topk_mask(theta, 2, 1e-4)
  np.testing.assert_allclose(m, [1., 0., 1., 0., 0.], atol=1e-3)
  m2 = soft_topk_mask(theta, 2, 5.0)
  np.testing.assert_allclose(jnp.sum(m2), 2.0, rtol=1e-5)
  assert bool(jnp.all(m2 >= -1e-6)) and bool(jnp.all(m2 <= 1 + 1e-6))


@pytest.mark.parametrize("reg", ["l2", "kl"])
@pytest.mark.parametrize("op", ["soft_topk_mask", "soft_rank"])
def test_ties_far_from_zero_at_small_eps(op, reg):
  """x / eps is about 1.7e4 here, where float32 cancellation in z - v
  would cost the answer its third digit.  Tied inputs have the same
  result wherever they sit."""
  fn = {"soft_topk_mask": lambda t: soft_topk_mask(t, 1, 1e-3, reg),
        "soft_rank": lambda t: soft_rank(t, 1e-3, reg)}[op]
  far = fn(jnp.full((3,), 17.349, jnp.float32))
  near = fn(jnp.zeros((3,), jnp.float32))
  np.testing.assert_allclose(far, near, rtol=0, atol=1e-5)


def test_soft_quantile():
  x = jnp.array(rng.normal(size=101).astype(np.float32))
  q = soft_quantile(x, 0.5, 1e-3)
  np.testing.assert_allclose(q, np.median(np.array(x)), atol=1e-2)


def test_jit_vmap_grad_compose():
  theta = jnp.array(rng.normal(size=(4, 7)).astype(np.float32))

  @jax.jit
  def f(t):
    return jax.vmap(lambda row: jnp.sum(soft_rank(row, 0.5) ** 2))(t)

  g = jax.jit(jax.grad(lambda t: jnp.sum(f(t))))(theta)
  assert g.shape == theta.shape
  assert bool(jnp.all(jnp.isfinite(g)))


def test_gradients_match_fd_all_ops():
  theta = jnp.array(rng.normal(size=6).astype(np.float32))
  u = jnp.array(rng.normal(size=6).astype(np.float32))
  for fn in (lambda t: jnp.sum(soft_rank(t, 0.4) * u),
             lambda t: jnp.sum(soft_sort(t, 0.4) * u),
             lambda t: jnp.sum(soft_rank(t, 0.4, "kl") * u),
             lambda t: jnp.sum(soft_topk_mask(t, 2, 0.4) * u)):
    g = jax.grad(fn)(theta)
    eps = 1e-3
    fd = np.array([
        (fn(theta.at[i].add(eps)) - fn(theta.at[i].add(-eps))) / (2 * eps)
        for i in range(6)])
    np.testing.assert_allclose(g, fd, atol=2e-2)


def test_permutation_indices_are_int32():
  """All permutation plumbing is pinned to int32 (ISSUE 8 satellite).

  int64 indices double gather/scatter bandwidth for nothing at the sizes
  this repo targets; the fused projection residuals assume int32, so the
  hard-sort primitives must never silently widen.
  """
  from repro.core.permutations import (
      argsort_ascending, argsort_descending, inverse_permutation,
      sort_descending)
  x = jnp.array(rng.normal(size=(2, 11)).astype(np.float32))
  sigma_d = argsort_descending(x)
  sigma_a = argsort_ascending(x)
  assert sigma_d.dtype == jnp.int32
  assert sigma_a.dtype == jnp.int32
  assert inverse_permutation(sigma_d).dtype == jnp.int32
  s, sigma = sort_descending(x)
  assert sigma.dtype == jnp.int32
  np.testing.assert_array_equal(
      np.take_along_axis(np.asarray(x), np.asarray(sigma), axis=-1),
      np.asarray(s))
