"""Per-architecture smoke tests: reduced same-family configs, one forward /
train step on CPU, asserting output shapes + finiteness; decode consistency
for representative families."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import all_assigned
from repro.configs.smoke import smoke_config
from repro.models import transformer as T

# Fast tier covers one dense and one MoE family; the full per-arch sweep
# runs in the slow tier (CI slow-tests job).
FAST_ARCHS = ("llama3.2-1b", "deepseek-v2-lite-16b")
ARCHS = [a if a in FAST_ARCHS else pytest.param(a, marks=pytest.mark.slow)
         for a in all_assigned()]


def make_batch(cfg, key, b=2, s=32):
  ks = jax.random.split(key, 3)
  if cfg.frontend == "audio":
    return {
        "embeds": jax.random.normal(ks[0], (b, s, cfg.d_model), jnp.float32),
        "targets": jax.random.randint(
            ks[1], (b, s, cfg.num_codebooks), 0, cfg.vocab_size),
    }
  if cfg.frontend == "vision":
    st_ = s - cfg.num_patches
    return {
        "tokens": jax.random.randint(ks[0], (b, st_), 0, cfg.vocab_size),
        "image_embeds": jax.random.normal(
            ks[1], (b, cfg.num_patches, cfg.d_model), jnp.float32),
        "targets": jax.random.randint(ks[2], (b, st_), 0, cfg.vocab_size),
    }
  return {
      "tokens": jax.random.randint(ks[0], (b, s), 0, cfg.vocab_size),
      "targets": jax.random.randint(ks[1], (b, s), 0, cfg.vocab_size),
  }


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_grad(arch):
  cfg = smoke_config(arch)
  key = jax.random.PRNGKey(0)
  params = T.init_params(cfg, key)
  batch = make_batch(cfg, key)

  losses, stats = jax.jit(lambda p, b: T.forward_train(cfg, p, b))(
      params, batch)
  tgt = batch["targets"]
  expect = tgt.shape[:2]
  assert losses.shape == expect
  assert bool(jnp.all(jnp.isfinite(losses)))
  # loss should be ~log(vocab) at init (random labels)
  assert abs(float(losses.mean()) - np.log(cfg.vocab_size)) < 2.0

  def scalar_loss(p):
    l, a = T.forward_train(cfg, p, batch)
    return jnp.mean(l) + 0.01 * a["aux_loss"]

  g = jax.jit(jax.grad(scalar_loss))(params)
  assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["llama3.2-1b", "gemma3-12b",
                                  "recurrentgemma-2b", "xlstm-350m"])
def test_decode_matches_full_forward(arch):
  cfg = smoke_config(arch)
  key = jax.random.PRNGKey(1)
  params = T.init_params(cfg, key)
  b, s = 2, 24
  toks = jax.random.randint(key, (b, s + 3), 0, cfg.vocab_size)
  batch = {"tokens": toks[:, :s], "targets": toks[:, :s]}
  lg, cache = jax.jit(
      lambda p, bb: T.forward_prefill(cfg, p, bb, s + 8))(params, batch)
  dec = jax.jit(lambda p, c, t, pos: T.forward_decode(cfg, p, c, t, pos))

  def full(tokens):
    return T.forward_prefill(
        cfg, params, {"tokens": tokens, "targets": tokens},
        tokens.shape[1])[0]

  full_j = jax.jit(full)
  for i in range(3):
    lg, cache = dec(params, cache, toks[:, s + i], jnp.int32(s + i))
    ref = full_j(toks[:, :s + i + 1])
    tol = 5e-3 if arch == "xlstm-350m" else 1e-4
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref), atol=tol)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "grok-1-314b"])
def test_moe_decode_matches_with_lossless_capacity(arch):
  # The MoE layer is dropless: no capacity to raise.
  cfg = smoke_config(arch)
  key = jax.random.PRNGKey(1)
  params = T.init_params(cfg, key)
  b, s = 2, 16
  toks = jax.random.randint(key, (b, s + 2), 0, cfg.vocab_size)
  batch = {"tokens": toks[:, :s], "targets": toks[:, :s]}
  lg, cache = jax.jit(
      lambda p, bb: T.forward_prefill(cfg, p, bb, s + 4))(params, batch)
  dec = jax.jit(lambda p, c, t, pos: T.forward_decode(cfg, p, c, t, pos))
  for i in range(2):
    lg, cache = dec(params, cache, toks[:, s + i], jnp.int32(s + i))
    ref = T.forward_prefill(
        cfg, params,
        {"tokens": toks[:, :s + i + 1], "targets": toks[:, :s + i + 1]},
        s + i + 1)[0]
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref), atol=1e-4)


@pytest.mark.slow
def test_soft_topk_router_vs_softmax_router_gradients():
  """The paper router propagates gradient to ALL expert logits; softmax
  top-k only to the selected ones."""
  cfg = smoke_config("grok-1-314b")
  key = jax.random.PRNGKey(0)
  params = T.init_params(cfg, key)
  batch = make_batch(cfg, key, b=2, s=16)

  def router_grad(router_kind):
    c = dataclasses.replace(cfg, router=router_kind)

    def loss(p):
      l, a = T.forward_train(c, p, batch)
      return jnp.mean(l) + 0.01 * a["aux_loss"]

    g = jax.grad(loss)(params)
    flat = jax.tree_util.tree_flatten_with_path(g)[0]
    outs = []
    for path, leaf in flat:
      pstr = "/".join(str(getattr(k, "key", k)) for k in path)
      if pstr.endswith("ffn/router"):
        outs.append(np.asarray(leaf))
    return np.concatenate([o.ravel() for o in outs])

  g_soft = router_grad("soft_topk")
  g_hard = router_grad("softmax_topk")
  assert np.isfinite(g_soft).all() and np.isfinite(g_hard).all()
  # soft router should have at least as many non-zero entries
  nz_soft = np.mean(np.abs(g_soft) > 1e-12)
  nz_hard = np.mean(np.abs(g_hard) > 1e-12)
  assert nz_soft >= nz_hard
