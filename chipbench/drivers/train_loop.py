"""Closed loop of ``repro.launch.train.Trainer.run`` steps.

Set-up builds one trainer for the configuration (the registered model
with the configuration's replacements, checked against its published
widths), makes its weights and optimizer state in one jitted call from
the seed, and drives it through the first ``check_steps`` steps with the
benchmark's own token feed; those steps compile the train step, and what
they leave (each step's loss, the first gradient as the optimizer's first
moment holds it, the weights' change) is what the reference is compared
with.  The window then goes on stepping the same trainer and state.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import sys
import time

import numpy as np

from chipbench import counts
from chipbench.harness import Compared, Window
from chipbench.traffic import generator

# program parameter path -> the reference's leaf name
_LAYER_NAMES = {"norm1": "ln1", "norm2": "ln2"}


def leaf_name(path) -> str:
  keys = [getattr(k, "key", str(k)) for k in path]
  if keys[0] == "embed":
    return "embed"
  if keys[0] == "lm_head":
    return "head"
  if keys[0] == "final_norm":
    return f"final_norm.{keys[1]}"
  if keys[2] in _LAYER_NAMES:
    return f"{_LAYER_NAMES[keys[2]]}.{keys[3]}"
  return keys[3]


def program_norms(tree):
  """Per-layer norms of the program's stacked leaves (its ``seg*``
  segments), one norm for each other leaf; jitted by the caller."""
  import jax
  import jax.numpy as jnp
  out = {}
  for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
    a = a.astype(jnp.float32)
    if getattr(path[0], "key", "").startswith("seg"):
      out[leaf_name(path)] = jnp.sqrt(jnp.sum(
          jnp.square(a).reshape(a.shape[0], -1), -1))
    else:
      out[leaf_name(path)] = jnp.sqrt(jnp.sum(jnp.square(a)))[None]
  return out


class Feed:
  """The trainer's batches, drawn by the benchmark from the seed."""

  def __init__(self, seed: int, train: dict, vocab: int):
    self.seed, self.train, self.vocab = seed, train, vocab

  def batch_at(self, step: int) -> dict:
    t = self.train
    return generator.token_batch(self.seed, step, t["batch"], t["seq"],
                                 self.vocab, t["corrupt_fraction"])


def arch_config(c: dict):
  """The program's configuration for ``c``, checked against the widths
  the configuration file publishes."""
  from repro.configs.base import get_config
  prog = c["program"]
  cfg = dataclasses.replace(get_config(prog["registered"]), **prog["replace"])
  want = {"d_model": c["hidden_size"], "num_heads": c["num_attention_heads"],
          "num_kv_heads": c["num_key_value_heads"],
          "head_dim": c["hidden_size"] // c["num_attention_heads"],
          "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
          "num_layers": c["num_hidden_layers"], "norm": "layernorm",
          "mlp_variant": "swiglu" if c["hidden_act"] == "silu" else "gelu",
          "rope_theta": float(c["rope_theta"]),
          "tie_embeddings": c["tie_word_embeddings"],
          "dtype": c["torch_dtype"], "grad_accum": c["grad_accum"],
          "loss_trim_fraction": c["train"]["loss_trim_fraction"],
          "loss_trim_eps": c["train"]["loss_trim_eps"]}
  got = {k: getattr(cfg, k) for k in want}
  if got != want:
    raise ValueError(f"the program's configuration departs from the file: "
                     f"{ {k: (got[k], want[k]) for k in want if got[k] != want[k]} }")
  return cfg


def worst_gap(prog: dict, ref: dict, keep=None) -> float:
  """Largest |program norm - reference norm| over the reference norm of
  that leaf or of the median leaf, whichever is larger."""
  names = sorted(ref)
  allref = np.concatenate([ref[k] for k in names])
  med = float(np.median(allref))
  worst = 0.0
  for k in names:
    mask = np.ones(len(ref[k]), bool) if keep is None else keep[k]
    gap = np.abs(prog[k] - ref[k]) / np.maximum(ref[k], med)
    if mask.any():
      worst = max(worst, float(gap[mask].max()))
  return worst


class TrainLoop:

  def __init__(self, ctx):
    self.ctx = ctx
    self.key = generator.key32(ctx.seed)

  def setup(self):
    import jax
    from repro.launch import steps as ST
    from repro.launch.train import Trainer, TrainerState
    from repro.models import transformer as T
    from repro.optim import adamw

    c = self.ctx.config
    tr, a = c["train"], c["adamw"]
    self.cfg = cfg = arch_config(c)
    opt = adamw.AdamWConfig(lr=tr["learning_rate"], b1=a["b1"], b2=a["b2"],
                            eps=a["eps"], weight_decay=a["weight_decay"],
                            clip_norm=a["clip_norm"],
                            moment_dtype=c["adam_moment_dtype"])
    self.trainer = Trainer(cfg, opt, batch=tr["batch"], seq=tr["seq"],
                           ckpt_dir=None, total_steps=tr["total_steps"],
                           seed=self.key)
    sched = c["schedule"]
    if (sched["total"] != tr["total_steps"]
        or sched["warmup"] != min(100, tr["total_steps"] // 10 + 1)):
      raise ValueError("the configuration's schedule is not the trainer's")
    self.feed = self.trainer.pipeline = Feed(self.ctx.seed, tr,
                                             c["vocab_size"])

    def init(key):
      params = T.init_params(cfg, key)
      return params, ST.init_opt_state(cfg, opt, params)

    key = jax.random.PRNGKey(self.key)
    params, opt_state = jax.jit(init)(key)
    self.state = TrainerState(params, opt_state, 0)
    norms = jax.jit(program_norms)
    self.losses = []
    with contextlib.redirect_stdout(sys.stderr):
      for i in range(self.ctx.traffic["check_steps"]):
        self.state, m = self.trainer.run(self.state, 1)
        self.losses.append(float(m["loss"]))
        if i == 0:
          self.first = {k: np.asarray(v, np.float64) / (1 - a["b1"])
                        for k, v in norms(
                            self.state.opt_state["adam"]["m"]).items()}
    # The weights the run started from, made again by the same jitted
    # init and held as the program holds them (bfloat16), so that the
    # difference reads only what the steps moved.
    start = jax.jit(lambda k: T.init_params(cfg, k))(key)
    change = jax.jit(lambda p, q: program_norms(jax.tree.map(
        lambda x, y: x.astype("float32") - y.astype("float32"), p, q)))
    self.change = {k: np.asarray(v, np.float64)
                   for k, v in change(self.state.params, start).items()}
    del start

  def window(self, seconds: float) -> Window:
    steps = 0
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(sys.stderr):
      while True:
        self.state, _ = self.trainer.run(self.state, 1)
        steps += 1
        if time.perf_counter() - t0 >= seconds:
          break
    elapsed = time.perf_counter() - t0
    tr = self.ctx.config["train"]
    return Window(seconds=elapsed, attempted=steps,
                  flops_per_step=counts.train_flops(
                      self.ctx.config, tr["batch"], tr["seq"]),
                  op_bytes_per_step=counts.soft_op_bytes(
                      tr["batch"] * tr["seq"]))

  def hlo_texts(self):
    import jax
    spec = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    s = self.state
    return [self.trainer.train_step.lower(
        spec(s.params), spec(s.opt_state),
        spec(self.feed.batch_at(0))).compile().as_text()]

  def release(self):
    import gc
    self.state = self.trainer = None
    gc.collect()

  def batches(self):
    import jax.numpy as jnp
    return [{k: jnp.asarray(v) for k, v in self.feed.batch_at(s).items()}
            for s in range(self.ctx.traffic["check_steps"])]

  def reference(self, **kw):
    import jax
    ref = importlib.import_module(
        f"chipbench.reference.{self.ctx.config['reference']}")
    with jax.default_matmul_precision("highest"):
      return ref.Reference(self.ctx.config, **kw).run(
          jax.random.PRNGKey(self.key), self.batches())

  def gaps(self, program, ref) -> dict[str, float]:
    """The three numbers compared, between readings (losses, first
    gradient norms, change norms) of a run and of the reference."""
    (p_loss, p_first, p_change), (r_loss, r_first, r_change) = program, ref
    med = float(np.median(np.concatenate(list(r_first.values()))))
    moved = {k: v >= 1e-3 * med for k, v in r_first.items()}
    return {
        "loss_gap": max(abs(p - r) / abs(r) for p, r in zip(p_loss, r_loss)),
        "grad_gap": worst_gap(p_first, r_first),
        "update_gap": worst_gap(p_change, r_change, moved),
    }

  def readings(self):
    return self.losses, self.first, self.change

  def compare(self) -> list[Compared]:
    limits = self.ctx.traffic["limits"]
    got = self.gaps(self.readings(), self.reference())
    return [Compared(k, v, limits[k]) for k, v in got.items()]


def build(ctx):
  return TrainLoop(ctx)
