"""The gather-free ``dense`` isotonic backend against ``scan``.

Both run the same divide-and-conquer PAV with the same merge order; only
how blocks are read differs (gathers in ``scan``, static slices and masked
reductions in ``dense``).  L2 merges are additions and its block value one
division, each correctly rounded, so L2 is compared bit for bit.  KL merges
go through ``logaddexp``, whose rounding on CPU depends on the shapes the
compiler sees around it (``scan`` of one row alone and of the same row in
a batch of 1024 differ by up to 64 ulps), so KL is compared to a few ulps.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import dispatch as D
from repro.kernels.pav_dense import pav_kl_dense, pav_l2_dense
from repro.kernels.pav_scan import pav_kl_scan, pav_l2_scan

try:
  from hypothesis import given, settings, strategies as st
  _HAS_HYPOTHESIS = True
except ImportError:
  _HAS_HYPOTHESIS = False

rng = np.random.default_rng(14)


def _bits(x):
  return np.asarray(x, np.float32).view(np.uint32)


def _assert_bitwise(got, want):
  assert got.shape == want.shape and got.dtype == want.dtype
  np.testing.assert_array_equal(_bits(got), _bits(want))


def _assert_kl_close(got, want):
  assert got.shape == want.shape and got.dtype == want.dtype
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _rows(kind, shape):
  """Solver inputs of one kind: random, ties, already non-increasing
  (nothing pools), non-decreasing (each row pools into one block)."""
  if kind == "normal":
    y = rng.normal(size=shape) * 3
  elif kind == "ties":
    y = rng.integers(-2, 3, size=shape).astype(np.float64)
  elif kind == "sorted":
    y = -np.sort(rng.normal(size=shape), -1)
  elif kind == "pools":
    y = np.sort(rng.normal(size=shape), -1)
  return jnp.asarray(y, jnp.float32)


def _kl_pair(kind, shape):
  s = -np.sort(-np.asarray(_rows(kind, shape)), -1)
  w = -np.sort(rng.normal(size=shape), -1)
  if kind == "pools":
    s = np.asarray(_rows("pools", shape))
  return jnp.asarray(s, jnp.float32), jnp.asarray(w, jnp.float32)


KINDS = ["normal", "ties", "sorted", "pools"]
SHAPES = [(1, 2), (3, 5), (4, 7), (2, 33), (5, 100), (1, 1000), (256, 256)]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("shape", SHAPES)
def test_l2_dense_bitwise_equals_scan(shape, kind):
  y = _rows(kind, shape)
  _assert_bitwise(pav_l2_dense(y), pav_l2_scan(y))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("shape", SHAPES)
def test_kl_dense_matches_scan(shape, kind):
  s, w = _kl_pair(kind, shape)
  _assert_kl_close(pav_kl_dense(s, w), pav_kl_scan(s, w))


def test_one_long_row_bitwise_equals_scan():
  """The soft-LTS regime: one row of 32 x 4096 token losses."""
  y = _rows("normal", (1, 131072))
  _assert_bitwise(pav_l2_dense(y), pav_l2_scan(y))
  s, w = _kl_pair("normal", (1, 131072))
  _assert_kl_close(pav_kl_dense(s, w), pav_kl_scan(s, w))


@pytest.mark.parametrize("shape", [(0, 8), (3, 1), (3, 0)])
def test_degenerate_shapes_pass_through_like_scan(shape):
  y = jnp.asarray(rng.normal(size=shape), jnp.float32)
  _assert_bitwise(pav_l2_dense(y), pav_l2_scan(y))
  _assert_kl_close(pav_kl_dense(y, y * 0.5), pav_kl_scan(y, y * 0.5))


def test_infinite_and_signed_zero_entries_bitwise():
  """The masked reads select exactly one element: -0.0 and infinities
  elsewhere in a window cannot leak into them."""
  y = jnp.asarray([[0.0, -0.0, -0.0, 5.0, -np.inf, -0.0, 2.0, 1.0, -0.0]],
                  jnp.float32)
  _assert_bitwise(pav_l2_dense(y), pav_l2_scan(y))


@pytest.mark.parametrize("half", [jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("reg", ["l2", "kl"])
def test_half_inputs_through_dispatch_bitwise(half, reg):
  """dispatch promotes bf16/f16 to f32, solves, and demotes: the same
  bits as ``scan`` in the half dtype (L2), within one of its ulps (KL)."""
  shape = (3, 4, 37)
  x = jnp.asarray(rng.normal(size=shape), half)
  args = (x,) if reg == "l2" else (-jnp.sort(-x, -1), -jnp.sort(-x, -1) / 2)
  got = D.dispatch("isotonic", reg, "dense", *args)
  want = D.dispatch("isotonic", reg, "scan", *args)
  assert got.dtype == half and got.shape == shape
  if reg == "l2":
    np.testing.assert_array_equal(np.asarray(got).view(np.uint16),
                                  np.asarray(want).view(np.uint16))
  else:
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-2)


if _HAS_HYPOTHESIS:
  # A few fixed shapes, so that examples share compiled programs.
  shapes = st.sampled_from([(1, 5), (3, 16), (2, 37)])
  floats = st.floats(min_value=-1e3, max_value=1e3, allow_nan=False,
                     allow_infinity=False, width=32)

  @given(shapes.flatmap(lambda sh: st.tuples(
      st.just(sh), st.lists(st.sampled_from([-1.0, 0.0, 2.5]) | floats,
                            min_size=sh[0] * sh[1],
                            max_size=sh[0] * sh[1]))))
  @settings(max_examples=40, deadline=None)
  def test_property_dense_bitwise_equals_scan(case):
    shape, values = case
    y = jnp.asarray(np.asarray(values, np.float32).reshape(shape))
    _assert_bitwise(pav_l2_dense(y), pav_l2_scan(y))
    s = -jnp.sort(-y, -1)
    w = jnp.flip(jnp.sort(y, -1), -1) * 0.25
    _assert_kl_close(pav_kl_dense(s, w), pav_kl_scan(s, w))
