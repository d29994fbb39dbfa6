"""Property-based tests (hypothesis) for the paper's invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed (pip install -e .[dev])")
from hypothesis import given, settings, strategies as st

from repro.core import soft_rank, soft_sort, soft_topk_mask

SETTINGS = dict(max_examples=40, deadline=None)

floats = st.floats(min_value=-50, max_value=50, allow_nan=False,
                   allow_infinity=False)
vectors = st.lists(floats, min_size=1, max_size=24)
eps_strat = st.floats(min_value=1e-3, max_value=1e3, allow_nan=False)


def _arr(v):
  return jnp.array(np.asarray(v, np.float32))


@given(vectors, eps_strat)
@settings(**SETTINGS)
def test_sort_output_monotone(v, eps):
  s = soft_sort(_arr(v), eps)
  assert bool(jnp.all(s[:-1] >= s[1:] - 1e-4 * (1 + jnp.abs(s[:-1]))))


@given(vectors, eps_strat)
@settings(**SETTINGS)
def test_sort_sum_conserved(v, eps):
  x = _arr(v)
  np.testing.assert_allclose(
      float(jnp.sum(soft_sort(x, eps))), float(jnp.sum(x)),
      rtol=1e-3, atol=1e-3)


@given(vectors, eps_strat)
@settings(**SETTINGS)
def test_rank_in_permutahedron(v, eps):
  """Majorization check: soft ranks lie in P((n,...,1)).

  y in P(w) iff sum(y) == sum(w) and for all k, the sum of the k largest
  entries of y is <= sum of k largest of w.
  """
  x = _arr(v)
  n = x.shape[0]
  r = np.sort(np.asarray(soft_rank(x, eps)))[::-1]
  w = np.arange(n, 0, -1, dtype=np.float64)
  np.testing.assert_allclose(r.sum(), w.sum(), rtol=1e-3, atol=1e-3)
  tol = 1e-3 * n * n
  assert np.all(np.cumsum(r) <= np.cumsum(w) + tol)


@given(vectors, eps_strat)
@settings(**SETTINGS)
def test_rank_translation_invariance(v, eps):
  x = _arr(v)
  r1 = soft_rank(x, eps)
  r2 = soft_rank(x + 7.5, eps)
  np.testing.assert_allclose(np.asarray(r1), np.asarray(r2),
                             rtol=1e-3, atol=1e-3)


@given(vectors, eps_strat)
@settings(**SETTINGS)
def test_rank_permutation_equivariance(v, eps):
  x = np.asarray(v, np.float32)
  perm = np.random.default_rng(0).permutation(len(x))
  r = np.asarray(soft_rank(_arr(x), eps))
  rp = np.asarray(soft_rank(_arr(x[perm]), eps))
  # ties can resolve differently across permutations; only check when the
  # input has no near-ties
  sx = np.sort(x)
  if len(x) > 1 and np.min(np.diff(sx)) < 1e-3:
    return
  np.testing.assert_allclose(rp, r[perm], rtol=1e-3, atol=2e-3)


@given(vectors, eps_strat)
@settings(**SETTINGS)
def test_scaling_relation(v, eps):
  """r_{eps,Q}(c * theta) == r_{eps/c,Q}(theta) for c > 0."""
  x = _arr(v)
  c = 3.0
  r1 = soft_rank(c * x, eps)
  r2 = soft_rank(x, eps / c)
  np.testing.assert_allclose(np.asarray(r1), np.asarray(r2),
                             rtol=1e-3, atol=2e-3)


@given(vectors, st.integers(min_value=1, max_value=5), eps_strat)
@settings(**SETTINGS)
def test_topk_mask_bounds_and_sum(v, k, eps):
  x = _arr(v)
  n = x.shape[0]
  k = min(k, n)
  m = np.asarray(soft_topk_mask(x, k, eps))
  assert np.all(m >= -1e-4) and np.all(m <= 1 + 1e-4)
  np.testing.assert_allclose(m.sum(), k, rtol=1e-3, atol=1e-3)


@given(vectors)
@settings(**SETTINGS)
def test_gradients_finite(v):
  x = _arr(v)
  g = jax.grad(lambda t: jnp.sum(jnp.sin(soft_rank(t, 0.3))))(x)
  assert bool(jnp.all(jnp.isfinite(g)))
  g2 = jax.grad(lambda t: jnp.sum(jnp.sin(soft_sort(t, 0.3, "kl"))))(x)
  assert bool(jnp.all(jnp.isfinite(g2)))


# ---------------------------------------------------------------------------
# "scan" (divide-and-conquer PAV) backend vs the "lax" reference.
# ---------------------------------------------------------------------------

# Sizes straddle power-of-two boundaries on purpose: the scan backend pads
# rows to the next power of two with sentinel blocks, and an off-by-one
# there only shows up at non-power-of-two n.
scan_ns = st.integers(min_value=1, max_value=67)
rows_strat = st.integers(min_value=1, max_value=4)


def _row_batch(data, rows, n, kind):
  rng = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1),
                                        label="seed"))
  x = rng.normal(scale=10.0, size=(rows, n))
  if kind == "all_equal":
    x = np.broadcast_to(x[:, :1], (rows, n)).copy()
  elif kind == "descending":
    x = -np.sort(x, axis=-1)
  elif kind == "ascending":  # worst case: everything pools into one block
    x = np.sort(x, axis=-1)
  return x


@given(st.data(), scan_ns, rows_strat,
       st.sampled_from(["random", "all_equal", "descending", "ascending"]),
       st.sampled_from([np.float32, np.float64]))
@settings(**SETTINGS)
def test_scan_backend_matches_lax_l2(data, n, rows, kind, dtype):
  from repro.core.isotonic import isotonic_l2
  x = _row_batch(data, rows, n, kind).astype(dtype)
  with jax.enable_x64(dtype == np.float64):
    a = np.asarray(isotonic_l2(jnp.asarray(x), "scan"))
    b = np.asarray(isotonic_l2(jnp.asarray(x), "lax"))
  np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


@given(st.data(), scan_ns, rows_strat,
       st.sampled_from(["random", "all_equal", "descending", "ascending"]),
       st.sampled_from([np.float32, np.float64]))
@settings(**SETTINGS)
def test_scan_backend_matches_lax_kl(data, n, rows, kind, dtype):
  from repro.core.isotonic import isotonic_kl
  s = _row_batch(data, rows, n, kind).astype(dtype)
  w = _row_batch(data, rows, n, "random").astype(dtype)
  with jax.enable_x64(dtype == np.float64):
    a = np.asarray(isotonic_kl(jnp.asarray(s), jnp.asarray(w), "scan"))
    b = np.asarray(isotonic_kl(jnp.asarray(s), jnp.asarray(w), "lax"))
  np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


@given(st.data(), scan_ns, rows_strat)
@settings(**SETTINGS)
def test_scan_backend_vjp_matches_lax(data, n, rows):
  from repro.core.isotonic import isotonic_l2
  x = jnp.asarray(_row_batch(data, rows, n, "random").astype(np.float32))
  u = jnp.asarray(_row_batch(data, rows, n, "random").astype(np.float32))
  g_scan = jax.grad(lambda t: jnp.sum(isotonic_l2(t, "scan") * u))(x)
  g_lax = jax.grad(lambda t: jnp.sum(isotonic_l2(t, "lax") * u))(x)
  np.testing.assert_allclose(np.asarray(g_scan), np.asarray(g_lax),
                             rtol=1e-5, atol=1e-4)
