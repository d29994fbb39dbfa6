"""The ``sortperm`` projection path against ``fused``, bitwise.

``sortperm`` is the fused pipeline with every permutation a key-value sort
that carries the data instead of a gather (the TPU route): the same sorted
values, the same sigma and tie order, the same solve.  So each operator's
output and both gradients (z and w) must equal ``fused`` bit for bit, on
tied inputs, with unbatched and batched weights, with permutations from a
``SortContext``, and past the 65,535-element u32 pack limit.

The comparison runs op by op (no enclosing ``jit``).  Inside one jitted
program XLA:CPU fuses the arithmetic around a gather differently from the
arithmetic around a sort, and the KL backward's transcendentals then move
by an ulp in either program against its op-by-op run; op by op the two
paths do the same arithmetic and differ only in how data moves.  Under
``jit`` the forward values still agree bitwise (checked below).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SortContext, soft_quantile, soft_rank, soft_sort,
                        soft_topk_mask)
from repro.core.projection import projection_permutahedron
from repro.obs import metrics
from repro.plan import ExecutionPlan, PlanRule
from repro.serving.ops import bound_op

NS = [1, 2, 64, 256, 70_000]
BUCKET = 16


def _tied(shape, seed):
  """Values on a 0.5 grid, so ties and repeated values are common."""
  local = np.random.default_rng(seed)
  return jnp.asarray((local.integers(-8, 9, size=shape) / 2)
                     .astype(np.float32))


def _rows(n):
  return (1, n) if n > 4096 else (3, n)


# Each case: (function of its differentiable arguments, arguments).  The
# operators differentiate through z (soft_rank, soft_topk_mask) or w
# (soft_sort, soft_quantile); the raw projections through both.
def _case(name, reg, n, path):
  shape = _rows(n)
  x = _tied(shape, n)
  if name == "soft_sort":
    return (lambda t: soft_sort(t, 0.7, reg, "DESCENDING"),), (x,)
  if name == "soft_sort_ctx":
    return (lambda t: soft_sort(t, 0.7, reg, "ASCENDING",
                                sort_context=SortContext(t)),), (x,)
  if name == "soft_rank":
    return (lambda t: soft_rank(t, 0.3, reg, "ASCENDING"),), (x,)
  if name == "soft_rank_ctx":
    return (lambda t: soft_rank(t, 0.3, reg,
                                sort_context=SortContext(t)),), (x,)
  if name == "soft_topk_mask":
    k = max(1, n // 4)
    return (lambda t: soft_topk_mask(t, k, 0.5, reg),), (x,)
  if name == "soft_quantile":
    return (lambda t: soft_quantile(t, 0.3, 0.2, reg),), (x,)
  w = _tied((n,) if name == "proj_unbatched_w" else shape, n + 1)
  if name == "proj_perm":
    ctx_z, ctx_w = SortContext(x), SortContext(w)
    perms = dict(z_perm=ctx_z.descending()[1:], w_perm=ctx_w.descending()[1:])
  else:
    perms = {}
  return (lambda z, v: projection_permutahedron(z, v, reg, path=path,
                                                **perms),), (x, w)


OPS = ["soft_sort", "soft_sort_ctx", "soft_rank", "soft_rank_ctx",
       "soft_topk_mask", "soft_quantile", "proj_unbatched_w",
       "proj_batched_w", "proj_perm"]


def _forward_and_grads(name, reg, n, path, monkeypatch):
  monkeypatch.setenv("REPRO_PROJECTION", path)
  (fn,), args = _case(name, reg, n, path)
  out = fn(*args)
  u = jnp.asarray(np.random.default_rng(n + 2)
                  .standard_normal(out.shape).astype(np.float32))
  grads = jax.grad(lambda *a: jnp.sum(fn(*a) * u),
                   argnums=tuple(range(len(args))))(*args)
  return [out, *grads]


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("reg", ["l2", "kl"])
@pytest.mark.parametrize("name", OPS)
def test_sortperm_matches_fused_bitwise(name, reg, n, monkeypatch):
  want = _forward_and_grads(name, reg, n, "fused", monkeypatch)
  got = _forward_and_grads(name, reg, n, "sortperm", monkeypatch)
  assert len(got) == len(want)
  for g, w in zip(got, want):
    np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("reg", ["l2", "kl"])
@pytest.mark.parametrize("n", [64, 70_000])
def test_sortperm_forward_matches_fused_bitwise_under_jit(reg, n):
  z = _tied(_rows(n), n)
  w = _tied((n,), n + 1)
  outs = [jax.jit(lambda a, b, p=p: projection_permutahedron(
      a, b, reg, path=p))(z, w) for p in ("fused", "sortperm")]
  np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


def test_sortperm_calls_are_counted_under_its_own_name():
  metrics.set_enabled(True)
  metrics.reset()
  try:
    theta = _tied((2, 8), 0)
    soft_rank(theta, 0.5, "l2", plan=_SORTPERM["scan"])
    assert metrics.counter_value("dispatch_calls", op="projection",
                                 regularization="l2",
                                 backend="sortperm") == 1
    assert metrics.counter_value("projection_fused_calls",
                                 regularization="l2") == 0
  finally:
    metrics.set_enabled(None)
    metrics.reset()


# ---------------------------------------------------------------------------
# The serving pad contract (tests/test_padding_invariance.py) on this path.
# ---------------------------------------------------------------------------

_SORTPERM = {impl: ExecutionPlan(
    name=f"sortperm-{impl}",
    rules=(PlanRule("forward", impl),
           PlanRule("projection", "sortperm", op="projection")))
             for impl in ("scan", "dense")}


def _padded_run(key, impl, values, eps, extra=None):
  n = values.shape[-1]
  row = np.zeros((1, BUCKET), np.float32)
  row[0, :n] = values
  args = [jnp.asarray(row), jnp.array([n], jnp.int32),
          jnp.array([eps], jnp.float32)]
  if extra is not None:
    args.append(extra)
  out = bound_op(key, impl=impl, plan=_SORTPERM[impl])(*args)
  return np.asarray(out)[0, :n]


@pytest.mark.parametrize("impl", ["scan", "dense"])
@pytest.mark.parametrize("reg", ["l2", "kl"])
@pytest.mark.parametrize("op", ["soft_sort", "soft_rank", "soft_topk"])
@pytest.mark.parametrize("n", [1, 5, 11, BUCKET])
def test_sortperm_padding_is_bitwise(op, reg, impl, n):
  v = np.asarray(_tied((n,), 40 + n)) * 1.3
  plan = _SORTPERM[impl]
  if op == "soft_topk":
    k = max(1, n // 3)
    got = _padded_run(f"soft_topk/{reg}", impl, v, 0.5,
                      extra=jnp.array([k], jnp.int32))
    want = soft_topk_mask(jnp.asarray(v), k, 0.5, reg, impl=impl, plan=plan)
  else:
    got = _padded_run(f"{op}/{reg}/desc", impl, v, 0.7)
    ref = soft_sort if op == "soft_sort" else soft_rank
    want = ref(jnp.asarray(v), 0.7, reg, impl=impl, plan=plan)
  np.testing.assert_array_equal(got, np.asarray(want))
