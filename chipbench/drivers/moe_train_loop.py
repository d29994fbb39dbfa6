"""Closed loop of ``repro.launch.train.Trainer.run`` steps of DeepSeek-V2
on one chip's share of its experts.

The train loop's pieces (``drivers/train_loop.py``), with what this
architecture needs instead: its own check of the program's configuration
against the published widths, its own leaf names (the leading dense layer
and the expert layers apart, each shared-expert matrix under its own
name), Zipf-distributed tokens and the model FLOPs of the held share.  Set
up, the window and the comparison are the train loop's: the first
``check_steps`` steps compile the step and leave the readings compared
with ``chipbench/reference/deepseek_v2.py``.  The window also reports the
held experts' assignments that the step's counter
(``moe_assignments{where=held}``) recorded in it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys
import time

import numpy as np

from chipbench import counts, harness, moe_counts
from chipbench.harness import Window
from chipbench.traffic import generator

train_loop = harness.load_module("drivers", "train_loop")

HELD_COUNTER = "moe_assignments{where=held}"


def leaf_name(path) -> str:
  """``seg<i>/l0_<kind>/a/b`` -> ``<kind>.a.b``; ``embed``, ``head`` and
  ``final_norm.scale`` otherwise (the reference's names)."""
  keys = [getattr(k, "key", str(k)) for k in path]
  if keys[0] == "embed":
    return "embed"
  if keys[0] == "lm_head":
    return "head"
  if keys[0] == "final_norm":
    return f"final_norm.{keys[1]}"
  return ".".join([keys[1].split("_", 1)[1]] + keys[2:])


def program_norms(tree):
  """Per-layer norms of the program's stacked leaves, one norm for each
  other leaf; jitted by the caller."""
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


class ZipfFeed:
  """Tokens drawn i.i.d. from Zipf(s) over the vocabulary, through a
  rank -> id permutation drawn from the seed, so that a few ids (and the
  experts they route to) take most of the traffic, as on text; the target
  is the next token."""

  def __init__(self, seed: int, train: dict, vocab: int):
    self.seed, self.train = seed, train
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -train["zipf_s"]
    self.cdf = np.cumsum(w) / w.sum()
    self.ids = generator.seed_rng(seed, 13).permutation(vocab)

  def batch_at(self, step: int) -> dict:
    t = self.train
    u = generator.seed_rng(self.seed, 14, step).random((t["batch"],
                                                        t["seq"] + 1))
    ranks = np.minimum(np.searchsorted(self.cdf, u), len(self.cdf) - 1)
    stream = self.ids[ranks].astype(np.int32)
    return {"tokens": stream[:, :-1].copy(), "targets": stream[:, 1:].copy()}


# The published settings that the program implements only as published.
_FIXED = {"q_lora_rank": None, "n_group": 1, "topk_group": 1,
          "topk_method": "greedy", "scoring_func": "softmax",
          "routed_scaling_factor": 1, "moe_layer_freq": 1,
          "attention_bias": False, "hidden_act": "silu"}


def arch_config(c: dict):
  """The program's configuration for ``c``, checked against the widths,
  the routing and the rope scaling the configuration file publishes."""
  from repro.configs.base import Yarn, get_config
  prog = c["program"]
  cfg = dataclasses.replace(get_config(prog["registered"]), **prog["replace"])
  fixed = {k: c[k] for k in _FIXED if c[k] != _FIXED[k]}
  if fixed or c["rope_scaling"]["type"] != "yarn":
    raise ValueError(f"the program implements DeepSeek-V2 only with "
                     f"{_FIXED} and yarn rope scaling; the file has {fixed}")
  y, dep = c["rope_scaling"], c["deployment"]
  want = {
      "d_model": c["hidden_size"], "num_heads": c["num_attention_heads"],
      "num_kv_heads": c["num_key_value_heads"],
      "head_dim": c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
      "qk_nope_dim": c["qk_nope_head_dim"],
      "qk_rope_dim": c["qk_rope_head_dim"], "v_head_dim": c["v_head_dim"],
      "kv_lora_rank": c["kv_lora_rank"], "d_ff": c["intermediate_size"],
      "moe_d_ff": c["moe_intermediate_size"],
      "num_experts": dep["router_experts"],
      "experts_held": c["n_routed_experts"],
      "first_held_expert": dep["first_held_expert"],
      "experts_per_token": c["num_experts_per_tok"],
      "num_shared_experts": c["n_shared_experts"],
      "norm_topk_prob": c["norm_topk_prob"],
      "first_dense_layers": c["first_k_dense_replace"],
      "num_layers": c["num_hidden_layers"], "vocab_size": c["vocab_size"],
      "norm": "rmsnorm", "mlp_variant": "swiglu",
      "rope_theta": float(c["rope_theta"]),
      "rope_yarn": Yarn(factor=float(y["factor"]),
                        original_max_position=y[
                            "original_max_position_embeddings"],
                        beta_fast=float(y["beta_fast"]),
                        beta_slow=float(y["beta_slow"]),
                        mscale=float(y["mscale"]),
                        mscale_all_dim=float(y["mscale_all_dim"])),
      "tie_embeddings": c["tie_word_embeddings"],
      "dtype": c["torch_dtype"], "grad_accum": c["grad_accum"],
      "router": "soft_topk", "router_eps": c["train"]["router_eps"],
      "loss_trim_fraction": c["train"]["loss_trim_fraction"]}
  got = {k: getattr(cfg, k) for k in want}
  if got != want:
    raise ValueError(f"the program's configuration departs from the file: "
                     f"{ {k: (got[k], want[k]) for k in want if got[k] != want[k]} }")
  return cfg


class MoeTrainLoop(train_loop.TrainLoop):

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
    self.feed = self.trainer.pipeline = ZipfFeed(self.ctx.seed, tr,
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
    start = jax.jit(lambda k: T.init_params(cfg, k))(key)
    change = jax.jit(lambda p, q: program_norms(jax.tree.map(
        lambda x, y: x.astype("float32") - y.astype("float32"), p, q)))
    self.change = {k: np.asarray(v, np.float64)
                   for k, v in change(self.state.params, start).items()}
    del start

  def window(self, seconds: float) -> Window:
    from repro.obs import metrics
    held = metrics.counters(HELD_COUNTER).get(HELD_COUNTER, 0)
    steps = 0
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(sys.stderr):
      while True:
        self.state, _ = self.trainer.run(self.state, 1)
        steps += 1
        if time.perf_counter() - t0 >= seconds:
          break
    elapsed = time.perf_counter() - t0
    c = self.ctx.config
    tr = c["train"]
    held = metrics.counters(HELD_COUNTER).get(HELD_COUNTER, 0) - held
    return Window(seconds=elapsed, attempted=steps,
                  histograms={HELD_COUNTER: held} if held else {},
                  flops_per_step=moe_counts.train_flops(c, tr["batch"],
                                                        tr["seq"]),
                  op_bytes_per_step=counts.soft_op_bytes(
                      moe_counts.router_entries(c, tr["batch"], tr["seq"])))


def build(ctx):
  # The metric readers and ``chipbench.control`` key on the loop's kind:
  # this is the train loop, with this architecture's pieces.
  ctx.traffic = dict(ctx.traffic, driver="train_loop")
  return MoeTrainLoop(ctx)
