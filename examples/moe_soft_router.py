"""MoE language model with the paper's soft-top-k router, end to end.

Trains a small MoE LM twice — once with the standard softmax-top-k router
and once with the projection-based soft-top-k router (dense gradients to
every expert logit) — then serves a few greedy generations from the
soft-routed model.  Reports loss and expert load balance (coefficient of
variation of expert loads; lower = better balanced).

  PYTHONPATH=src python examples/moe_soft_router.py
"""

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.data.pipeline import pipeline_for_arch
from repro.launch import steps as ST
from repro.models import transformer as T
from repro.optim import adamw


def make_cfg(router: str) -> ArchConfig:
  return ArchConfig(
      name=f"moe-{router}", family="moe", num_layers=4, d_model=128,
      num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256, vocab_size=4096,
      block_cycle=("moe",), num_experts=8, experts_per_token=2,
      moe_d_ff=128, router=router, router_eps=1.0,
      dtype="float32", remat="none", q_chunk=64, kv_chunk=64,
      xent_chunk=64)


def expert_load_cv(cfg, params, batch):
  """Coefficient of variation of expert assignment counts (balance
  metric)."""
  from repro.models import layers as L
  from repro.models.moe import route
  x, _ = T._embed_inputs(cfg, params, batch)
  lp = jax.tree.map(lambda a: a[0], params["seg0"]["l0_moe"])
  h = L.norm_apply(lp["norm1"], x, cfg.norm)
  ids, _, _ = route(lp["ffn"], h.reshape(-1, cfg.d_model), cfg)
  loads = jnp.sum(jax.nn.one_hot(ids, cfg.num_experts), axis=(0, 1))
  return float(jnp.std(loads) / jnp.maximum(jnp.mean(loads), 1e-9))


def train_one(router: str, steps: int, batch_size: int, seq: int):
  cfg = make_cfg(router)
  pipe = pipeline_for_arch(cfg, batch_size, seq, seed=0)
  params = T.init_params(cfg, jax.random.PRNGKey(0))
  opt_cfg = adamw.AdamWConfig(lr=1e-3)
  opt = ST.init_opt_state(cfg, opt_cfg, params)
  step_fn = jax.jit(ST.make_train_step(cfg, opt_cfg))
  batch = None
  for step in range(steps):
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(step).items()}
    params, opt, m = step_fn(params, opt, batch)
    if step % 10 == 0:
      print(f"  [{router}] step {step:3d} loss {float(m['loss']):.4f} "
            f"aux {float(m['aux_loss']):.3f}")
  cv = expert_load_cv(cfg, params, batch)
  return cfg, params, float(m["loss"]), cv


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--steps", type=int, default=40)
  ap.add_argument("--batch", type=int, default=8)
  ap.add_argument("--seq", type=int, default=64)
  args = ap.parse_args()

  results = {}
  for router in ("softmax_topk", "soft_topk"):
    print(f"[moe] training with router={router}")
    cfg, params, loss, cv = train_one(router, args.steps, args.batch,
                                      args.seq)
    results[router] = (loss, cv)
    if router == "soft_topk":
      # quick greedy generation from the soft-routed model
      prompt = jnp.zeros((2, 16), jnp.int32)
      logits, caches = jax.jit(
          lambda p, b: T.forward_prefill(cfg, p, b, 32))(
              params, {"tokens": prompt, "targets": prompt})
      dec = jax.jit(lambda p, c, t, pos: T.forward_decode(cfg, p, c, t, pos))
      toks = []
      tok = jnp.argmax(logits, -1)
      for i in range(8):
        toks.append(np.asarray(tok))
        logits, caches = dec(params, caches, tok, jnp.int32(16 + i))
        tok = jnp.argmax(logits, -1)
      print("  [soft_topk] sample generation:", np.stack(toks, 1)[0].tolist())

  print("\nrouter comparison (lower is better):")
  for router, (loss, cv) in results.items():
    print(f"  {router:14s} final-loss {loss:.4f}   expert-load CV {cv:.3f}")


if __name__ == "__main__":
  main()
