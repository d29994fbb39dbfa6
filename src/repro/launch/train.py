"""End-to-end training driver with fault tolerance.

Features (designed for 1000+ node operation, exercised here on CPU):
  * checkpoint/restart: atomic async checkpoints every N steps; on start,
    auto-resume from the latest checkpoint (data pipeline is stateless, so
    resume = restore params/opt + continue from step);
  * preemption handling: SIGTERM/SIGINT trigger a final synchronous
    checkpoint before exit (the standard TPU-preemption protocol);
  * straggler mitigation: per-step deadline tracking — steps slower than
    ``straggler_factor`` x the rolling median are logged and counted; the
    hook is where a real fleet controller would re-shard or evict (on a
    single host we record + expose the metric);
  * elastic restart: checkpoints are mesh-independent (gathered arrays) —
    restoring onto a different mesh shape re-shards via the in_shardings
    of the restored step (see repro/checkpoint/checkpointer.py);
  * paper integration: ``--trim-frac`` enables the soft-LTS robust token
    loss; ``--router soft_topk`` is the projection router (MoE archs);
    ``--compress-grads`` turns on int8+error-feedback gradient exchange.

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import signal
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from repro import plan as repro_plan
from repro.checkpoint import checkpointer as ckpt
from repro.configs.base import get_config, parse_overrides
from repro.data.pipeline import pipeline_for_arch
from repro.launch import steps as ST
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.obs import artifacts as obs_artifacts
from repro.obs import metrics as obs_metrics
from repro.obs.tracing import build_lines, span
from repro.optim import adamw
from repro.optim.schedule import cosine_with_warmup


@dataclasses.dataclass
class TrainerState:
  params: object
  opt_state: object
  step: int


class Trainer:

  def __init__(self, cfg, opt_cfg, *, batch: int, seq: int,
               ckpt_dir: str | None, ckpt_every: int = 50,
               compress_grads: bool = False, total_steps: int = 1000,
               corrupt_fraction: float = 0.0, seed: int = 0):
    self.cfg = cfg
    self.opt_cfg = opt_cfg
    self.pipeline = pipeline_for_arch(
        cfg, batch, seq, seed=seed, corrupt_fraction=corrupt_fraction)
    self.ckpt_dir = ckpt_dir
    self.ckpt_every = ckpt_every
    self.async_ckpt = (ckpt.AsyncCheckpointer(ckpt_dir)
                       if ckpt_dir else None)
    self.total_steps = total_steps
    sched = lambda s: cosine_with_warmup(
        s, warmup=min(100, total_steps // 10 + 1), total=total_steps)
    # Params and optimizer state are donated: the step updates them in
    # place instead of holding the old and the new copies at once.
    self.train_step = jax.jit(ST.make_train_step(
        cfg, opt_cfg, lr_schedule=sched, compress_grads=compress_grads),
        donate_argnums=(0, 1))
    self.compress_grads = compress_grads
    self.seed = seed
    self._preempted = False
    self._step_times: list[float] = []   # the repro_train_step spans
    self._builds_shown = False
    self.straggler_factor = 2.0
    self.straggler_events = 0

  # -- lifecycle ----------------------------------------------------------

  def init_or_restore(self) -> TrainerState:
    key = jax.random.PRNGKey(self.seed)
    params = T.init_params(self.cfg, key)
    opt_state = ST.init_opt_state(self.cfg, self.opt_cfg, params,
                                  compress_grads=self.compress_grads)
    state = TrainerState(params, opt_state, 0)
    if self.ckpt_dir and ckpt.latest_step(self.ckpt_dir) is not None:
      tree = {"params": params, "opt": opt_state}
      restored, meta = ckpt.restore(self.ckpt_dir, tree)
      state = TrainerState(restored["params"], restored["opt"],
                           int(meta["step"]))
      print(f"[train] resumed from step {state.step}")
    return state

  def install_preemption_handler(self):
    def handler(signum, frame):
      print(f"[train] caught signal {signum}: checkpoint-and-exit")
      self._preempted = True
    signal.signal(signal.SIGTERM, handler)
    signal.signal(signal.SIGINT, handler)

  def maybe_flag_straggler(self, dt: float):
    self._step_times.append(dt)
    window = self._step_times[-32:]
    if len(window) >= 8:
      med = statistics.median(window)
      if dt > self.straggler_factor * med:
        self.straggler_events += 1
        print(f"[train] straggler step: {dt*1e3:.0f} ms vs median "
              f"{med*1e3:.0f} ms (event #{self.straggler_events})")

  def record_moe(self, metrics):
    """The MoE counters the step returned: the assignments computed by
    the held experts, and the busiest held expert's share of them."""
    if "moe_assignments_held" in metrics:
      obs_metrics.counter_inc("moe_assignments",
                              int(metrics["moe_assignments_held"]),
                              where="held")
      obs_metrics.observe("moe_busiest_share",
                          float(metrics["moe_busiest_share"]))

  # -- main loop ----------------------------------------------------------

  def run(self, state: TrainerState, num_steps: int):
    metrics = {}
    for step in range(state.step, min(state.step + num_steps,
                                      self.total_steps)):
      if self._preempted:
        break
      batch = {k: jnp.asarray(v)
               for k, v in self.pipeline.batch_at(step).items()
               if k != "corrupt_mask"}
      with span("repro_train_step") as step_span:
        state.params, state.opt_state, metrics = self.train_step(
            state.params, state.opt_state, batch)
        jax.block_until_ready(metrics["loss"])
      dt = step_span.seconds
      self.maybe_flag_straggler(dt)
      self.record_moe(metrics)
      if not self._builds_shown:
        self._builds_shown = True
        for line in build_lines():
          print(f"[build] {line}")
      state.step = step + 1
      if step % 10 == 0 or step == state.step - 1:
        print(f"[train] step {step:5d} loss {float(metrics['loss']):.4f} "
              f"gnorm {float(metrics['grad_norm']):.3f} ({dt*1e3:.0f} ms)")
      if self.async_ckpt and state.step % self.ckpt_every == 0:
        self.async_ckpt.save(
            state.step, {"params": state.params, "opt": state.opt_state},
            {"step": state.step})
    # final (synchronous) checkpoint — also the preemption path
    if self.async_ckpt:
      self.async_ckpt.wait()
      ckpt.save(self.ckpt_dir, state.step,
                {"params": state.params, "opt": state.opt_state},
                {"step": state.step})
    return state, metrics

  def bench_results(self, final_metrics) -> list[dict]:
    """Structured run summary for the schema-v1 bench artifact."""
    times = sorted(self._step_times)
    if not times:
      return []
    median = times[len(times) // 2]
    return [{
        "name": "train/step",
        "median_step_us": median * 1e6,
        "p90_step_us": times[min(len(times) - 1,
                                 int(len(times) * 0.9))] * 1e6,
        "steps_timed": len(times),
        "straggler_events": self.straggler_events,
        "final_loss": float(final_metrics.get("loss", float("nan"))),
    }]


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--arch", required=True)
  ap.add_argument("--smoke", action="store_true",
                  help="reduced same-family config (CPU-sized)")
  ap.add_argument("--steps", type=int, default=100)
  ap.add_argument("--batch", type=int, default=8)
  ap.add_argument("--seq", type=int, default=128)
  ap.add_argument("--lr", type=float, default=3e-4)
  ap.add_argument("--trim-frac", type=float, default=0.0)
  ap.add_argument("--router", default=None)
  ap.add_argument("--corrupt", type=float, default=0.0)
  ap.add_argument("--compress-grads", action="store_true")
  ap.add_argument("--ckpt-dir", default=None)
  ap.add_argument("--ckpt-every", type=int, default=50)
  ap.add_argument("--bench-json", default=None, metavar="PATH",
                  help="write a schema-v1 BENCH artifact (step-time "
                       "distribution + dispatch metrics) on exit")
  ap.add_argument("--plan", default=None, metavar="PLAN_JSON",
                  help="install an ExecutionPlan (repro.plan JSON) as the "
                       "active plan for every dispatch decision")
  ap.add_argument("--set", action="append", dest="overrides")
  args = ap.parse_args()
  enable_compile_cache()

  if args.plan:
    repro_plan.set_active_plan(repro_plan.load_plan(args.plan))

  if args.smoke:
    from repro.configs.smoke import smoke_config
    cfg = smoke_config(args.arch)
  else:
    cfg = get_config(args.arch)
  over = parse_overrides(args.overrides)
  if args.trim_frac:
    over["loss_trim_fraction"] = args.trim_frac
  if args.router:
    over["router"] = args.router
  if over:
    cfg = dataclasses.replace(cfg, **over)

  opt_cfg = adamw.AdamWConfig(lr=args.lr)
  trainer = Trainer(cfg, opt_cfg, batch=args.batch, seq=args.seq,
                    ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                    compress_grads=args.compress_grads,
                    total_steps=args.steps, corrupt_fraction=args.corrupt)
  trainer.install_preemption_handler()
  state = trainer.init_or_restore()
  state, metrics = trainer.run(state, args.steps)
  print(f"[train] done at step {state.step}; "
        f"final loss {float(metrics.get('loss', float('nan'))):.4f}; "
        f"stragglers {trainer.straggler_events}")
  if args.bench_json:
    obs_artifacts.write_bench_artifact(
        args.bench_json, trainer.bench_results(metrics),
        obs_artifacts.collect_meta(
            suite="train", arch=args.arch, smoke=bool(args.smoke),
            batch=args.batch, seq=args.seq, steps=state.step,
            **repro_plan.plan_provenance()))


if __name__ == "__main__":
  main()
