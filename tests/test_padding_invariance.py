"""The serving pad contract: bucket-padded ops match unpadded, bitwise.

`repro.serving.ops` pads every request up to its shape bucket with a
construction that (a) sorts strictly below all real entries and (b)
never pools across the real/pad boundary, so the sliced-back result is
*bitwise* equal to the unpadded operator — per backend.  The backend
must be pinned explicitly in these tests: the precedence chain is free
to route the padded shape (B, bucket) and the unpadded shape (n,) to
different isotonic backends, and cross-backend results are only
allclose, not bit-identical.

Scalar losses (Spearman, LTS) are masked reductions over those exact
vectors; their reduce tree differs between n and bucket, so they are
checked allclose.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import soft_rank, soft_sort, soft_topk_mask
from repro.core.losses import soft_lts_loss, soft_spearman_loss
from repro.core.projection import projection_permutahedron
from repro.plan import ExecutionPlan, PlanRule
from repro.serving.ops import bound_op

try:
  from hypothesis import given, settings, strategies as st
  _HAS_HYPOTHESIS = True
except ImportError:
  _HAS_HYPOTHESIS = False

rng = np.random.default_rng(17)

BACKENDS = ["lax", "scan", "dense", "minimax"]
REGS = ["l2", "kl"]
BUCKET = 16


def _padded(values, bucket=BUCKET, fill=0.0):
  """(1, bucket) row with the real entries in the prefix.

  The pad lanes are *inputs* the construction must ignore — `fill`
  defaults to 0.0 but tests also pass garbage to prove independence.
  """
  n = values.shape[-1]
  row = np.full((1, bucket), fill, np.float32)
  row[0, :n] = values
  return jnp.asarray(row)


def _run(key, impl, values, eps, extra=None):
  """Call the padded op on one padded row; return the real prefix."""
  n = values.shape[-1]
  args = [_padded(values), jnp.array([n], jnp.int32),
          jnp.array([eps], jnp.float32)]
  if extra is not None:
    args.append(extra)
  out = bound_op(key, impl=impl)(*args)
  return np.asarray(out)[0, :n] if out.ndim == 2 else np.asarray(out)[0]


def _pin(impl):
  return ExecutionPlan(name=f"pin-{impl}", rules=(PlanRule("forward", impl),))


# ---------------------------------------------------------------------------
# Deterministic sweep: every backend x reg x op, several n, bitwise.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", BACKENDS)
@pytest.mark.parametrize("reg", REGS)
@pytest.mark.parametrize("direction", ["desc", "asc"])
@pytest.mark.parametrize("n", [1, 5, 11, BUCKET])
def test_padded_soft_sort_bitwise(impl, reg, direction, n):
  v = rng.standard_normal(n).astype(np.float32) * 3
  eps = 0.7
  got = _run(f"soft_sort/{reg}/{direction}", impl, v, eps)
  dirn = "DESCENDING" if direction == "desc" else "ASCENDING"
  want = np.asarray(soft_sort(jnp.asarray(v), eps, reg, dirn, impl=impl))
  np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("impl", BACKENDS)
@pytest.mark.parametrize("reg", REGS)
@pytest.mark.parametrize("direction", ["desc", "asc"])
@pytest.mark.parametrize("n", [1, 5, 11, BUCKET])
def test_padded_soft_rank_bitwise(impl, reg, direction, n):
  v = rng.standard_normal(n).astype(np.float32) * 3
  eps = 0.7
  got = _run(f"soft_rank/{reg}/{direction}", impl, v, eps)
  dirn = "DESCENDING" if direction == "desc" else "ASCENDING"
  want = np.asarray(soft_rank(jnp.asarray(v), eps, reg, dirn, impl=impl))
  np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("impl", BACKENDS)
@pytest.mark.parametrize("reg", REGS)
@pytest.mark.parametrize("n,k", [(5, 2), (11, 1), (11, 10), (BUCKET, 4)])
def test_padded_soft_topk_bitwise(impl, reg, n, k):
  v = rng.standard_normal(n).astype(np.float32)
  eps = 0.5
  got = _run(f"soft_topk/{reg}", impl, v, eps,
             extra=jnp.array([k], jnp.int32))
  want = np.asarray(soft_topk_mask(jnp.asarray(v), k, eps, reg, impl=impl))
  np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("impl", BACKENDS)
@pytest.mark.parametrize("reg", REGS)
@pytest.mark.parametrize("n", [3, 9, BUCKET])
def test_padded_projection_bitwise(impl, reg, n):
  z = rng.standard_normal(n).astype(np.float32) * 2
  w = rng.standard_normal(n).astype(np.float32)
  if reg == "kl":
    w = np.abs(w) + 0.1
  got = _run(f"projection/{reg}", impl, z, 1.0, extra=_padded(w))
  want = np.asarray(projection_permutahedron(
      jnp.asarray(z), jnp.asarray(w), reg, impl))
  np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Scalar losses: masked reductions over exact vectors -> allclose.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", BACKENDS)
@pytest.mark.parametrize("reg", REGS)
def test_padded_lts_matches_loss(impl, reg):
  v = (rng.standard_normal(9).astype(np.float32)) ** 2
  trim, eps = 3, 0.8
  got = _run(f"lts/{reg}", impl, v, eps, extra=jnp.array([trim], jnp.int32))
  want = float(soft_lts_loss(jnp.asarray(v), trim, eps, reg, plan=_pin(impl)))
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("impl", BACKENDS)
@pytest.mark.parametrize("reg", REGS)
def test_padded_spearman_matches_loss(impl, reg):
  v = rng.standard_normal(7).astype(np.float32)
  target = rng.permutation(7).astype(np.float32) + 1.0
  eps = 0.6
  got = _run(f"spearman/{reg}/asc", impl, v, eps, extra=_padded(target))
  want = float(soft_spearman_loss(jnp.asarray(v), jnp.asarray(target), eps,
                                  reg, direction="ASCENDING",
                                  plan=_pin(impl)))
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Edge cases.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", BACKENDS)
def test_padded_full_bucket_is_identity_case(impl):
  """n == bucket: no pads at all, trivially bitwise."""
  v = rng.standard_normal(BUCKET).astype(np.float32)
  got = _run("soft_rank/l2/desc", impl, v, 1.0)
  want = np.asarray(soft_rank(jnp.asarray(v), 1.0, impl=impl))
  np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("impl", BACKENDS)
@pytest.mark.parametrize("reg", REGS)
def test_padded_ties_bitwise(impl, reg):
  """Ties pool into isotonic blocks; pads must not join those blocks."""
  v = np.array([1.5, 1.5, -2.0, 1.5, -2.0], np.float32)
  for op in ("soft_sort", "soft_rank"):
    got = _run(f"{op}/{reg}/desc", impl, v, 0.9)
    ref = soft_sort if op == "soft_sort" else soft_rank
    want = np.asarray(ref(jnp.asarray(v), 0.9, reg, impl=impl))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("impl", BACKENDS)
@pytest.mark.parametrize("eps", [1e-3, 1.0, 1e3])
def test_padded_extreme_eps_bitwise(impl, eps):
  """eps near the hard-sort and constant-collapse limits."""
  v = rng.standard_normal(6).astype(np.float32)
  for reg in REGS:
    got = _run(f"soft_sort/{reg}/desc", impl, v, eps)
    want = np.asarray(soft_sort(jnp.asarray(v), eps, reg, impl=impl))
    np.testing.assert_array_equal(got, want)


def test_pad_lane_inputs_are_ignored():
  """The construction must never read the pad lanes of the input row."""
  v = rng.standard_normal(5).astype(np.float32)
  outs = []
  for fill in (0.0, 1e30, -1e30, np.nan):
    row = _padded(v, fill=fill)
    out = bound_op("soft_rank/l2/desc", impl="lax")(
        row, jnp.array([5], jnp.int32), jnp.array([0.5], jnp.float32))
    outs.append(np.asarray(out)[0, :5])
  for o in outs[1:]:
    np.testing.assert_array_equal(outs[0], o)


def test_padded_batch_rows_are_independent():
  """Rows with different true_n / eps in one batch match per-row calls."""
  ns = [2, 7, BUCKET]
  epss = [0.3, 1.0, 2.5]
  rows = [rng.standard_normal(n).astype(np.float32) for n in ns]
  batch = jnp.concatenate([_padded(v) for v in rows], axis=0)
  out = bound_op("soft_sort/l2/desc", impl="lax")(
      batch, jnp.array(ns, jnp.int32), jnp.array(epss, jnp.float32))
  for i, (v, n, eps) in enumerate(zip(rows, ns, epss)):
    want = np.asarray(soft_sort(jnp.asarray(v), eps, impl="lax"))
    np.testing.assert_array_equal(np.asarray(out)[i, :n], want)


# ---------------------------------------------------------------------------
# Property tests (hypothesis; installed via `pip install -e .[dev]`).
# ---------------------------------------------------------------------------

if _HAS_HYPOTHESIS:
  SETTINGS = dict(max_examples=25, deadline=None)

  floats = st.floats(min_value=-100, max_value=100, allow_nan=False,
                     allow_infinity=False, width=32)
  vectors = st.lists(floats, min_size=1, max_size=BUCKET)
  eps_strat = st.floats(min_value=float(np.float32(1e-3)), max_value=1e3,
                        allow_nan=False, width=32)
  backend_strat = st.sampled_from(BACKENDS)
  reg_strat = st.sampled_from(REGS)

  @given(vectors, eps_strat, backend_strat, reg_strat)
  @settings(**SETTINGS)
  def test_property_padded_soft_sort_bitwise(v, eps, impl, reg):
    arr = np.asarray(v, np.float32)
    got = _run(f"soft_sort/{reg}/desc", impl, arr, eps)
    want = np.asarray(soft_sort(jnp.asarray(arr), eps, reg, impl=impl))
    np.testing.assert_array_equal(got, want)

  @given(vectors, eps_strat, backend_strat, reg_strat)
  @settings(**SETTINGS)
  def test_property_padded_soft_rank_bitwise(v, eps, impl, reg):
    arr = np.asarray(v, np.float32)
    got = _run(f"soft_rank/{reg}/desc", impl, arr, eps)
    want = np.asarray(soft_rank(jnp.asarray(arr), eps, reg, impl=impl))
    np.testing.assert_array_equal(got, want)
