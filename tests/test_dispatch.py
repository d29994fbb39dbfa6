"""Dispatch layer: backend registry, auto resolution, cross-backend
equivalence (forward AND custom-VJP) on randomized batched inputs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import soft_rank, soft_sort
from repro.core.isotonic import isotonic_kl, isotonic_l2
from repro.kernels import dispatch as D

rng = np.random.default_rng(7)

BATCHED_SHAPES = [(9,), (2, 3, 17)]


# ---------------------------------------------------------------------------
# Registry / resolution semantics.
# ---------------------------------------------------------------------------


def test_registry_contains_all_backends_for_both_regs():
  for reg in ("l2", "kl"):
    have = set(D.registered_backends("isotonic", reg))
    assert {"lax", "scan", "dense", "pallas", "minimax"} <= have


def test_backward_registry_contains_both_formulations():
  for reg in ("l2", "kl"):
    have = set(D.registered_backward_backends("isotonic", reg))
    assert have == {"segscan", "scatter"}


def test_auto_resolution_is_deterministic_per_platform():
  # Expected routes come from the committed autotuned default plan for
  # the platform it was measured on (cpu: lax for small-n few-row and
  # huge-n huge-batch cells, scan everywhere in between; see
  # src/repro/plan/default_plan.json) and from the built-in plan
  # everywhere else (tpu -> dense at every shape; gpu is unmeasured ->
  # builtin chain: minimax under its small-n cap, scan beyond).
  for platform, shape, want in [
      ("tpu", (4, 9), "dense"),
      ("tpu", (256, 4096), "dense"),
      ("cpu", (4, 9), "lax"),
      ("cpu", (4, D.AUTO_MINIMAX_MAX_N + 1), "lax"),
      ("cpu", (1_000_000, 64), "scan"),
      ("cpu", (1, 10_000), "scan"),
      ("cpu", (32, 10_000), "scan"),
      ("cpu", (256, 10_000), "lax"),
      ("gpu", (4, 9), "minimax"),
      # huge flattened batch at small n: rows * n^2 memory rules minimax out
      ("gpu", (1_000_000, 64), "scan"),
      ("gpu", (4, 4096), "scan"),
  ]:
    got = [D.resolve_backend("isotonic", "l2", None, shape=shape,
                             platform=platform) for _ in range(3)]
    assert got == [want] * 3, (platform, shape, got)


def test_builtin_plan_routes_projection_by_platform(monkeypatch):
  # TPU permutes by sorting payloads (gathers are slow there); CPU and
  # GPU keep the fused gathers and packed integer sorts.
  for platform, want in [("tpu", "sortperm"), ("cpu", "fused"),
                         ("gpu", "fused")]:
    for shape in [None, (1024, 256), (8192, 64), (1, 131072)]:
      got = D.resolve_projection(None, "l2", shape=shape, platform=platform)
      assert got == want, (platform, shape, got)
  # The environment and an explicit path= still override the plan.
  monkeypatch.setenv(D.PROJECTION_ENV_VAR, "fused")
  assert D.resolve_projection(None, "l2", platform="tpu") == "fused"
  monkeypatch.setenv(D.PROJECTION_ENV_VAR, "sortperm")
  assert D.resolve_projection(None, "kl", platform="cpu") == "sortperm"
  assert D.resolve_projection("composed", "l2", platform="tpu") == "composed"
  monkeypatch.delenv(D.PROJECTION_ENV_VAR)
  assert D.resolve_projection("fused", "l2", platform="tpu") == "fused"
  assert D.resolve_projection("sortperm", "l2", platform="cpu") == "sortperm"


def test_shapeless_auto_resolution_never_picks_minimax():
  """Regression: shape=None used to read as n=0, satisfying the small-n
  test and silently routing arbitrarily large problems to the O(n^2)
  backend."""
  for platform in ("cpu", "gpu"):
    assert D.resolve_backend("isotonic", "l2", None, shape=None,
                             platform=platform) == "scan"
  assert D.resolve_backend("isotonic", "kl", None, shape=None,
                           platform="tpu") == "dense"


def test_explicit_backend_wins_over_default():
  with D.use_backend("minimax"):
    assert D.resolve_backend("isotonic", "l2", "lax", shape=(4, 9)) == "lax"
    assert D.resolve_backend("isotonic", "l2", None, shape=(4, 9)) == "minimax"


def test_env_var_override(monkeypatch):
  monkeypatch.setenv(D.ENV_VAR, "minimax")
  assert D.resolve_backend("isotonic", "l2", None, shape=(4, 500)) == "minimax"
  # explicit argument still wins over the environment
  assert D.resolve_backend("isotonic", "l2", "lax", shape=(4, 500)) == "lax"


def test_unknown_backend_raises():
  with pytest.raises(ValueError):
    D.resolve_backend("isotonic", "l2", "cuda", shape=(4, 9))
  with pytest.raises(ValueError):
    D.set_default_backend("nope")


def test_use_backend_restores_previous_default():
  before = D.get_default_backend()
  with pytest.raises(RuntimeError):
    with D.use_backend("lax"):
      raise RuntimeError("boom")
  assert D.get_default_backend() == before


# ---------------------------------------------------------------------------
# lax vs pallas (interpret mode on CPU) forward + VJP equivalence.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", BATCHED_SHAPES)
def test_isotonic_l2_lax_vs_pallas_fwd_and_vjp(shape):
  y = jnp.array(rng.normal(size=shape).astype(np.float32))
  u = jnp.array(rng.normal(size=shape).astype(np.float32))
  outs, grads = {}, {}
  for b in ("lax", "scan", "dense", "pallas", "minimax"):
    outs[b] = isotonic_l2(y, b)
    grads[b] = jax.grad(lambda t: jnp.sum(isotonic_l2(t, b) * u))(y)
  for b in ("scan", "dense", "pallas", "minimax"):
    np.testing.assert_allclose(outs[b], outs["lax"], atol=1e-5)
    np.testing.assert_allclose(grads[b], grads["lax"], atol=1e-5)


@pytest.mark.parametrize("shape", BATCHED_SHAPES)
def test_isotonic_kl_lax_vs_pallas_fwd_and_vjp(shape):
  s = jnp.array(np.sort(rng.normal(size=shape), -1)[..., ::-1].copy(),
                jnp.float32)
  w = jnp.array(np.sort(rng.normal(size=shape), -1)[..., ::-1].copy(),
                jnp.float32)
  u = jnp.array(rng.normal(size=shape).astype(np.float32))
  outs, gss, gws = {}, {}, {}
  for b in ("lax", "scan", "dense", "pallas", "minimax"):
    outs[b] = isotonic_kl(s, w, b)
    gss[b], gws[b] = jax.grad(
        lambda a, c: jnp.sum(isotonic_kl(a, c, b) * u), argnums=(0, 1))(s, w)
  for b in ("scan", "dense", "pallas", "minimax"):
    np.testing.assert_allclose(outs[b], outs["lax"], atol=5e-5)
    np.testing.assert_allclose(gss[b], gss["lax"], atol=5e-5)
    np.testing.assert_allclose(gws[b], gws["lax"], atol=5e-5)


@pytest.mark.parametrize("reg", ["l2", "kl"])
@pytest.mark.parametrize("shape", [(6, 13)])
def test_soft_ops_backends_agree_end_to_end(reg, shape):
  """soft_rank/soft_sort with explicit impl: fwd + VJP agree across
  backends through the whole sort -> PAV -> scatter pipeline."""
  theta = jnp.array(rng.normal(size=shape).astype(np.float32))

  def loss(t, impl, op):
    out = op(t, 0.4, reg, impl=impl)
    return jnp.sum(jnp.sin(out))

  # soft_rank exercises the same sort->PAV->scatter pipeline as soft_sort
  # (soft_sort differs only in which argument is batched, covered by
  # test_unbatched_w_fast_path_matches_batched_w).
  op = soft_rank
  f_lax = loss(theta, "lax", op)
  g_lax = jax.grad(lambda t: loss(t, "lax", op))(theta)
  for b in ("scan", "dense", "pallas", "minimax"):
    np.testing.assert_allclose(loss(theta, b, op), f_lax, atol=1e-5)
    np.testing.assert_allclose(
        jax.grad(lambda t: loss(t, b, op))(theta), g_lax, atol=1e-5)


def test_unbatched_w_fast_path_matches_batched_w():
  """projection with w of shape (n,) must equal explicitly-broadcast w."""
  from repro.core.projection import projection_permutahedron
  z = jnp.array(rng.normal(size=(4, 8)).astype(np.float32))
  w1 = jnp.array(rng.normal(size=(8,)).astype(np.float32))
  wb = jnp.broadcast_to(w1, z.shape)
  for reg in ("l2", "kl"):
    np.testing.assert_allclose(
        projection_permutahedron(z, w1, reg),
        projection_permutahedron(z, wb, reg), atol=1e-6)
    # gradient through unbatched w accumulates over the batch
    g1 = jax.grad(lambda w: jnp.sum(
        projection_permutahedron(z, w, reg) ** 2))(w1)
    gb = jax.grad(lambda w: jnp.sum(
        projection_permutahedron(z, w, reg) ** 2))(wb)
    np.testing.assert_allclose(g1, gb.sum(0), atol=1e-4)


def test_default_path_is_single_dispatch_no_vmap():
  """The default path lowers to ONE isotonic solve over the flattened
  batch: count custom_vjp calls in the jaxpr of a batched soft_rank."""
  theta = jnp.array(rng.normal(size=(4, 3, 9)).astype(np.float32))
  jaxpr = jax.make_jaxpr(lambda t: soft_rank(t, 0.5))(theta)
  text = str(jaxpr)
  assert text.count("custom_vjp_call") == 1, text


def test_vjp_matches_finite_difference_batched_all_backends():
  y = jnp.array(rng.normal(size=(2, 5)).astype(np.float32))
  u = jnp.array(rng.normal(size=(2, 5)).astype(np.float32))
  eps = 1e-3
  # pallas omitted: its VJP is literally the same backward function (only
  # forwards differ), and grad equality to lax is asserted above.
  for b in ("lax", "scan", "dense", "minimax"):
    f = lambda t: jnp.sum(isotonic_l2(t, b) * u)
    g = jax.grad(f)(y)
    fd = np.zeros((2, 5), np.float32)
    for i in range(2):
      for j in range(5):
        fd[i, j] = (f(y.at[i, j].add(eps))
                    - f(y.at[i, j].add(-eps))) / (2 * eps)
    np.testing.assert_allclose(g, fd, atol=2e-2)


# ---------------------------------------------------------------------------
# Backward (VJP) dispatch: segscan vs scatter formulations.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", BATCHED_SHAPES + [(5, 64), (3, 100)])
def test_l2_backward_backends_agree(shape):
  """New default (segscan) vs reference (scatter): max abs diff <= 1e-5."""
  y = jnp.array(rng.normal(size=shape).astype(np.float32))
  u = jnp.array(rng.normal(size=shape).astype(np.float32))
  f = lambda t: jnp.sum(isotonic_l2(t) * u)
  with D.use_backward("segscan"):
    g_new = jax.grad(f)(y)
  with D.use_backward("scatter"):
    g_old = jax.grad(f)(y)
  assert float(jnp.max(jnp.abs(g_new - g_old))) <= 1e-5


@pytest.mark.parametrize("shape", BATCHED_SHAPES + [(5, 64)])
def test_kl_backward_backends_agree(shape):
  s = jnp.array(rng.normal(size=shape).astype(np.float32))
  w = jnp.array(rng.normal(size=shape).astype(np.float32))
  u = jnp.array(rng.normal(size=shape).astype(np.float32))
  f = lambda a, c: jnp.sum(isotonic_kl(a, c) * u)
  grads = {}
  for b in ("segscan", "scatter"):
    with D.use_backward(b):
      grads[b] = jax.grad(f, argnums=(0, 1))(s, w)
  for new, old in zip(grads["segscan"], grads["scatter"]):
    assert float(jnp.max(jnp.abs(new - old))) <= 1e-5


def test_backward_resolution_precedence(monkeypatch):
  # default: auto -> segscan
  assert D.resolve_backward("isotonic", "l2", None) == "segscan"
  # env overrides default
  monkeypatch.setenv(D.BWD_ENV_VAR, "scatter")
  assert D.resolve_backward("isotonic", "l2", None) == "scatter"
  # explicit argument wins over env
  assert D.resolve_backward("isotonic", "l2", "segscan") == "segscan"
  monkeypatch.delenv(D.BWD_ENV_VAR)
  with pytest.raises(ValueError):
    D.resolve_backward("isotonic", "l2", "cuda")
  with pytest.raises(ValueError):
    D.set_default_backward("nope")


def test_use_backward_restores_previous_default():
  before = D.get_default_backward()
  with pytest.raises(RuntimeError):
    with D.use_backward("scatter"):
      raise RuntimeError("boom")
  assert D.get_default_backward() == before


# ---------------------------------------------------------------------------
# Trace-key cache stays bounded.
# ---------------------------------------------------------------------------


def test_trace_key_cache_is_capped_and_counts_evictions(monkeypatch):
  from repro.obs import metrics
  monkeypatch.setattr(D, "TRACE_KEY_CAP", 3)
  metrics.set_enabled(True)
  try:
    metrics.reset()
    for n in range(2, 10):  # 8 distinct shapes through a cap of 3
      D.dispatch("isotonic", "l2", "lax", jnp.zeros((1, n), jnp.float32))
    assert len(D._SEEN_TRACE_KEYS) <= 3
    evicts = sum(metrics.counters("dispatch_trace_cache_evict").values())
    assert evicts == 5
    # repeats hit, never evict
    D.dispatch("isotonic", "l2", "lax", jnp.zeros((1, 9), jnp.float32))
    assert sum(metrics.counters("dispatch_trace_cache_hit").values()) == 1
  finally:
    metrics.set_enabled(None)
    metrics.reset()


# ---------------------------------------------------------------------------
# Uniform promote-compute-demote dtype contract (bf16/f16) across backends.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("half", [jnp.bfloat16, jnp.float16])
def test_half_dtype_contract_uniform_across_backends(half):
  """Every backend must accept half inputs (dispatch promotes to f32,
  computes, demotes) and agree with every other backend on the result —
  no backend carries its own casting wrapper anymore."""
  x32 = jnp.array(rng.normal(size=(3, 21)).astype(np.float32))
  xh = x32.astype(half)
  w32 = jnp.array(np.sort(rng.normal(size=(21,)))[::-1].copy()
                  .astype(np.float32))
  wh = jnp.broadcast_to(w32.astype(half), xh.shape)

  outs_l2, outs_kl = {}, {}
  for backend in ("lax", "scan", "dense", "minimax"):
    o2 = D.dispatch("isotonic", "l2", backend, xh)
    ok = D.dispatch("isotonic", "kl", backend, xh, wh)
    assert o2.dtype == half and ok.dtype == half, backend
    outs_l2[backend], outs_kl[backend] = o2, ok
  for backend in ("scan", "dense", "minimax"):
    np.testing.assert_allclose(
        np.asarray(outs_l2[backend], np.float32),
        np.asarray(outs_l2["lax"], np.float32), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(
        np.asarray(outs_kl[backend], np.float32),
        np.asarray(outs_kl["lax"], np.float32), rtol=2e-2, atol=2e-2)


def test_bf16_matches_f32_reference_through_operators():
  """bf16 in -> bf16 out for the public operators, numerically tracking
  the f32 result to bf16 precision, including gradients."""
  x32 = jnp.array(rng.normal(size=(2, 17)).astype(np.float32))
  xb = x32.astype(jnp.bfloat16)
  for fn in (lambda v: soft_sort(v, 0.5, "l2"),
             lambda v: soft_rank(v, 0.5, "kl")):
    out32 = fn(x32)
    outb = fn(xb)
    assert outb.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(outb, np.float32),
                               np.asarray(out32), rtol=4e-2, atol=4e-2)
    g32 = jax.grad(lambda v: (fn(v) ** 2).sum())(x32)
    gb = jax.grad(lambda v: (fn(v) ** 2).sum())(xb)
    assert gb.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(gb, np.float32),
                               np.asarray(g32), rtol=1e-1, atol=1e-1)


def test_backward_dispatch_promotes_half_grads():
  """dispatch_backward applies the same contract: half cotangents are
  solved in f32 and demoted, int/bool structure args pass through."""
  xb = jnp.array(rng.normal(size=(2, 9)).astype(np.float32)
                 ).astype(jnp.bfloat16)
  g = jax.grad(lambda v: isotonic_l2(v).astype(jnp.float32).sum())(xb)
  assert g.dtype == jnp.bfloat16
  assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
