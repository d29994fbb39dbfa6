"""DeepSeek-V2 on one chip's share of its experts, against the plain float32
reference (``chipbench/reference/deepseek_v2.py``, which imports nothing of
the program), at a small size on the CPU: the model's logits, loss and
gradients; the held shares adding up to the uncut layer; YaRN against the
published formulas worked by hand; the router reaching the plan; and no
assignment dropped under skewed routing."""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.reference import deepseek_v2 as ref
from chipbench.tests.small_moe import small_config
from repro.configs.base import get_config
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import transformer as T
from repro.obs import metrics

driver = harness.load_module("drivers", "moe_train_loop")
moe_apply = jax.jit(MOE.moe_apply, static_argnums=2)


@pytest.fixture(scope="module")
def small():
  c = small_config()
  return c, driver.arch_config(c)


def _batch(c, seed):
  rng = np.random.default_rng(seed)
  t = c["train"]
  toks = rng.integers(0, c["vocab_size"], (t["batch"], t["seq"] + 1))
  return {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
          "targets": jnp.asarray(toks[:, 1:], jnp.int32)}


def _ref_leaf(tree, path):
  name = driver.leaf_name(path)
  if name in tree:
    return tree[name]
  kind, rest = name.split(".", 1)
  return tree[kind][rest]


def _weights(cfg, seed):
  """Seeded random weights of the program's shapes, bfloat16 values held
  in float32; norm scales near 1."""
  rng = np.random.default_rng(seed)
  shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))

  def draw(path, a):
    x = rng.standard_normal(a.shape)
    if "norm" in jax.tree_util.keystr(path):
      x = 1.0 + 0.1 * x
    else:
      x = x / math.sqrt(a.shape[-2])
    return jnp.asarray(x, jnp.bfloat16).astype(jnp.float32)
  return jax.tree_util.tree_map_with_path(draw, shapes)


def test_program_matches_the_reference(small):
  """Logits, per-token loss and every gradient, both in float32 from the
  same weights."""
  c, cfg = small
  params = _weights(cfg, 3)
  rparams = {}
  for path, a in jax.tree_util.tree_flatten_with_path(params)[0]:
    name = driver.leaf_name(path)
    if "." in name and name.split(".", 1)[0] in ("mla_dense", "mla_moe"):
      kind, rest = name.split(".", 1)
      rparams.setdefault(kind, {})[rest] = a
    else:
      rparams[name] = a
  cfg32 = dataclasses.replace(cfg, dtype="float32", remat="none")
  batch = _batch(c, 0)
  seq = batch["tokens"].shape[1]

  @jax.jit
  def program(p):
    def loss(p):
      nll, stats = T.forward_train(cfg32, p, batch)
      return jnp.mean(nll) + 0.01 * stats["aux_loss"], nll
    logits = T.forward_prefill(cfg32, p, batch, seq)[0]
    return jax.value_and_grad(loss, has_aux=True)(p), logits

  @jax.jit
  def reference(p):
    def loss(p):
      x, aux, _ = ref.final_hidden(c, p, batch["tokens"])
      nll = ref.token_nll(p, x, batch["targets"])
      return jnp.mean(nll) + 0.01 * aux, (nll, x[:, -1] @ p["head"])
    return jax.value_and_grad(loss, has_aux=True)(p)

  with jax.default_matmul_precision("highest"):
    ((loss, nll), grads), logits = program(params)
    (rloss, (rnll, rlogits)), rgrads = reference(rparams)
  np.testing.assert_allclose(logits, rlogits, rtol=1e-4, atol=1e-4)
  np.testing.assert_allclose(nll, rnll, rtol=1e-4, atol=1e-4)
  np.testing.assert_allclose(loss, rloss, rtol=1e-5)
  for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
    want = _ref_leaf(rgrads, path)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(g, want, rtol=0, atol=1e-3 * scale + 1e-9,
                               err_msg=driver.leaf_name(path))


def _share(cfg, first):
  """The small model's expert layer as one of two chips holds it: 4 of
  its 8 experts from ``first``."""
  return dataclasses.replace(cfg, experts_held=4, first_held_expert=first,
                             dtype="float32")


def _ref_params(p):
  rp = {f"ffn.{k}": v for k, v in p.items() if k != "shared"}
  rp.update({f"ffn.shared.{k}": v for k, v in p["shared"].items()})
  return rp


def test_held_shares_add_up_to_the_uncut_layer(small):
  """Two chips holding 4 of the 8 experts each: their outputs, with the
  shared expert counted once, are the reference's layer with all 8."""
  c, cfg = small
  full = dataclasses.replace(_share(cfg, 0), experts_held=0)
  p = jax.jit(lambda k: MOE.moe_init(k, full, jnp.float32))(
      jax.random.PRNGKey(5))
  x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, cfg.d_model))
  outs = [moe_apply(dict(p, **{k: p[k][first:first + 4] for k in
                               ("we_in", "we_gate", "we_out")}),
                    x, _share(cfg, first)) for first in (0, 4)]
  sh = p["shared"]
  x2 = x.reshape(-1, cfg.d_model)
  shared = (jax.nn.silu(x2 @ sh["w_gate"]) * (x2 @ sh["w_in"])) @ sh["w_out"]
  got = sum(o for o, _ in outs).reshape(x2.shape) - shared

  z = dict(ref.dims(c), held=8, h0=0)
  with jax.default_matmul_precision("highest"):
    want, _, n_held = jax.jit(lambda x: ref.moe(z, None, x, _ref_params(p)))(
        x2)
  np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
  held = sum(float(st["moe_assignments_held"]) for _, st in outs)
  assert held == float(n_held) == x2.shape[0] * cfg.experts_per_token


def test_yarn_against_the_published_formulas():
  """DeepSeek-V2-Lite's rope: 64 dims, theta 1e4, factor 40 over 4096
  positions, beta 32 / 1.  By hand: dimension pair j turns
  4096 * theta^(-j/32) / (2 pi) times, which is 32 at j = 10.47 and 1 at
  j = 22.51, so the ramp runs from pair 10 to pair 23."""
  cfg = get_config("deepseek-v2-lite-16b")
  y = cfg.rope_yarn
  got = L.yarn_inv_freq(64, 1e4, y)
  base = 1e4 ** (-np.arange(32) / 32)
  ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
  np.testing.assert_allclose(got, base * (1 - ramp) + base / 40 * ramp,
                             rtol=1e-6)
  # m = 0.1 * 0.707 * ln 40 + 1 = 1.260804; the scores scale by m^2 / sqrt(192)
  assert abs(L.yarn_get_mscale(40, 0.707) - 1.260804) < 1e-6
  assert abs(MLA.softmax_scale(cfg) * math.sqrt(192) - 1.589627) < 1e-6
  # mscale = mscale_all_dim: the rotation itself is not rescaled
  x = jnp.ones((5, 1, 64))
  rot = L.rope(x, jnp.arange(5), 1e4, y)
  np.testing.assert_allclose(jnp.sum(rot[..., :32] ** 2 + rot[..., 32:] ** 2,
                                     -1), 64.0, rtol=1e-6)


def test_router_reaches_the_plan(small):
  """The soft top-k gate is decided by the plan like every other call:
  a forward decision is counted and no backend is pinned by argument."""
  _, cfg = small
  p = {"router": jnp.ones((cfg.d_model, cfg.num_experts))}
  x = jnp.ones((16, cfg.d_model))
  metrics.reset()
  jax.jit(lambda p, x: MOE.route(p, x, cfg)).lower(p, x)
  decided = metrics.counters("plan_decide{")
  assert any("kind=forward" in k for k in decided), decided
  pinned = [k for k in metrics.counters() if "source=arg" in k]
  assert not pinned, pinned


def test_no_held_assignment_is_dropped_under_skew(small):
  """Every token routed to two of the held experts: all assignments
  computed, as the dense reference computes them."""
  c, cfg = small
  cfg = _share(cfg, 0)
  p = jax.jit(lambda k: MOE.moe_init(k, cfg, jnp.float32))(
      jax.random.PRNGKey(4))
  p["router"] = jnp.zeros_like(p["router"]).at[:, :2].set(1.0)
  x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, cfg.d_model)) + 1.0
  out, stats = moe_apply(p, x, cfg)
  t = x.shape[0] * x.shape[1]
  assert float(stats["moe_assignments_held"]) == 2 * t
  assert float(stats["moe_busiest_share"]) == 0.5

  z = dict(ref.dims(c), held=4, h0=0)
  with jax.default_matmul_precision("highest"):
    want, _, n_held = jax.jit(lambda x: ref.moe(z, None, x, _ref_params(p)))(
        x.reshape(t, -1))
  assert float(n_held) == 2 * t
  np.testing.assert_allclose(out.reshape(t, -1), want, rtol=1e-4, atol=1e-5)


def test_rows_past_the_held_assignments_never_reach_the_result(
    small, monkeypatch):
  """A grouped product may leave anything in the buffer's rows past the
  held assignments, in its output and in its gradient (the TPU's does):
  NaN there changes neither the layer's output nor any gradient."""
  _, cfg = small
  cfg = _share(cfg, 0)
  p = jax.jit(lambda k: MOE.moe_init(k, cfg, jnp.float32))(
      jax.random.PRNGKey(8))
  x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, cfg.d_model))

  def value_and_grads(p, x):
    def f(p, x):
      out, stats = MOE.moe_apply(p, x, cfg)
      return jnp.sum(out * x) + stats["aux_loss"]
    return jax.value_and_grad(f, argnums=(0, 1))(p, x)

  want = jax.jit(value_and_grads)(p, x)
  exact = jax.lax.ragged_dot

  @jax.custom_vjp
  def leaky(lhs, rhs, sizes):
    rows = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(sizes)
    return jnp.where(rows, exact(lhs, rhs, sizes), jnp.nan)

  def leaky_fwd(lhs, rhs, sizes):
    return leaky(lhs, rhs, sizes), (lhs, rhs, sizes)

  def leaky_bwd(res, g):
    lhs, rhs, sizes = res
    rows = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(sizes)
    dl, dr = jax.vjp(lambda a, b: exact(a, b, sizes), lhs, rhs)[1](
        jnp.where(rows, g, 0.0))
    return jnp.where(rows, dl, jnp.nan), dr, None

  leaky.defvjp(leaky_fwd, leaky_bwd)
  monkeypatch.setattr(jax.lax, "ragged_dot", leaky)
  got = jax.jit(lambda p, x: value_and_grads(p, x))(p, x)   # traced anew
  for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("model_ways,refused", [(4, True), (1, False)])
def test_expert_sharded_mesh_is_refused(small, model_ways, refused):
  """Every chip computes all of its held experts: under a mesh that shards
  their weights by expert the layer refuses to run, where it would gather
  every expert's weights into each product; an unsharded mesh runs."""
  from repro.sharding import specs as SP
  _, cfg = small
  cfg = _share(cfg, 0)
  p = jax.eval_shape(lambda: MOE.moe_init(jax.random.PRNGKey(0), cfg,
                                          jnp.float32))
  x = jax.ShapeDtypeStruct((2, 32, cfg.d_model), jnp.float32)
  mesh = jax.sharding.AbstractMesh((8 // model_ways, model_ways),
                                   ("data", "model"))
  rules = SP.ShardingRules(mesh, data_axes=("data",), model_axis="model")
  with SP.use_rules(rules):
    if refused:
      with pytest.raises(NotImplementedError, match="expert parallelism"):
        jax.eval_shape(lambda p, x: MOE.moe_apply(p, x, cfg), p, x)
    else:
      out, _ = jax.eval_shape(lambda p, x: MOE.moe_apply(p, x, cfg), p, x)
      assert out.shape == x.shape
