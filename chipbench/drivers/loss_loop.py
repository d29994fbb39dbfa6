"""Closed loop over one jitted loss-and-gradient program, no model.

Two losses, named by the traffic mix's ``loss``:

* ``spearman`` -- the soft Spearman loss of the configuration's lists,
  ``lists_per_step`` of them per step, each padded to ``slate`` with the
  exact padding of ``repro.serving.ops`` (its ``spearman`` op); the step
  is the gradient of the mean loss over the lists.
* ``trimmed`` -- ``repro.core.losses.soft_trimmed_token_loss`` over one
  row of ``sequences x seq`` per-token losses, with the configuration's
  trim fraction and strength.

Set-up draws ``batches`` inputs from the seed, compiles the step and runs
it once on each.  The window cycles over the batches with one step in
flight: step i + 1 is dispatched before step i is waited for, so the
host's wake-up falls inside the device's work.  The window closes once
the last step dispatched is done.  Afterwards the newest outputs on
every batch are compared, row by row, with the float64 reference.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import counts
from chipbench.harness import Compared, Window
from chipbench.reference import soft_ops
from chipbench.traffic import generator


class LossLoop:

  def __init__(self, ctx):
    self.ctx = ctx
    self.loss = ctx.traffic["loss"]
    if self.loss not in ("spearman", "trimmed"):
      raise ValueError(f"unknown loss {self.loss!r}")
    self.host = []      # per batch: the inputs, as numpy
    self.last = {}      # batch -> newest (per-row losses, gradient)

  # -- inputs and program ---------------------------------------------------

  def _inputs(self):
    c, t = self.ctx.config, self.ctx.traffic
    rng = generator.seed_rng(self.ctx.seed, 1)
    for _ in range(t["batches"]):
      if self.loss == "spearman":
        scores, true_n, target = generator.slate_batch(
            c, t["lists_per_step"], t["slate"], rng)
        eps = np.full(len(true_n), c["regularization_strength"], np.float32)
        self.host.append((scores, true_n, eps, target))
      else:
        n = t["sequences"] * t["seq"]
        self.host.append((generator.token_losses(t["token_losses"], n, rng),))

  def program(self):
    """The jitted step: inputs -> ((mean loss, per-row losses), grad)."""
    import jax
    import jax.numpy as jnp
    c = self.ctx.config
    if self.loss == "spearman":
      from repro.serving.ops import padded_op
      direction = {"DESCENDING": "desc", "ASCENDING": "asc"}[c["direction"]]
      fn = padded_op(f"spearman/{c['regularization']}/{direction}").fn

      def objective(v, n, e, t):
        rows = fn(v, n, e, t)
        return jnp.mean(rows), rows
    else:
      from repro.core.losses import soft_trimmed_token_loss
      train = c["train"]

      def objective(x):
        loss = soft_trimmed_token_loss(x, train["loss_trim_fraction"],
                                       train["loss_trim_eps"])
        return loss, loss[None]
    return jax.jit(jax.value_and_grad(objective, has_aux=True))

  def setup(self):
    import jax
    self._inputs()
    self.args = [tuple(jax.device_put(a) for a in batch)
                 for batch in self.host]
    self.exe = self.program().lower(*self.args[0]).compile()
    for b, args in enumerate(self.args):
      self.last[b] = jax.block_until_ready(self.exe(*args))

  # -- the window -----------------------------------------------------------

  def window(self, seconds: float) -> Window:
    import jax
    steps = 0
    nb = len(self.args)
    pending = None
    t0 = time.perf_counter()
    while True:
      b = steps % nb
      out = self.exe(*self.args[b])
      steps += 1
      if pending is not None:
        self.last[pending[0]] = jax.block_until_ready(pending[1])
      pending = (b, out)
      if time.perf_counter() - t0 >= seconds:
        break
    self.last[pending[0]] = jax.block_until_ready(pending[1])
    elapsed = time.perf_counter() - t0
    return Window(seconds=elapsed, attempted=steps,
                  op_bytes_per_step=counts.soft_op_bytes(
                      self.host[0][0].size))

  def hlo_texts(self):
    return [self.exe.as_text()]

  def release(self):
    import jax
    self.last = {b: jax.device_get(out) for b, out in self.last.items()}
    del self.exe, self.args

  # -- correctness ------------------------------------------------------------

  def reference(self, b: int, rnd=float):
    """Per-row losses and the gradient of the step on batch b."""
    c = self.ctx.config
    if self.loss == "spearman":
      scores, true_n, eps, target = self.host[b]
      rows, slate = scores.shape
      losses = np.zeros(rows)
      grad = np.zeros((rows, slate))
      for i, n in enumerate(true_n):
        losses[i], g = soft_ops.spearman_row(scores[i, :n], target[i, :n],
                                             float(eps[i]), rnd)
        grad[i, :n] = g / rows
      return losses, grad
    (x,) = self.host[b]
    train = c["train"]
    loss, g = soft_ops.trimmed_loss(x, train["loss_trim_fraction"],
                                    train["loss_trim_eps"], rnd)
    return np.array([loss]), g[None]

  def gaps(self, outputs=None) -> dict[str, float]:
    """The largest relative gap of the per-row losses and of the
    gradient, over every row of every batch, between ``outputs`` (the
    program's, by default) and the float64 reference."""
    outputs = outputs or self.last
    loss_gap = grad_gap = 0.0
    for b in range(len(self.host)):
      (_, rows), grad = outputs[b]
      grad = np.asarray(grad, np.float64).reshape(len(rows), -1)
      want_rows, want_grad = self.reference(b)
      rows = np.asarray(rows, np.float64)
      loss_gap = max(loss_gap, float(np.max(
          np.abs(rows - want_rows) / np.maximum(1.0, np.abs(want_rows)))))
      scale = np.abs(want_grad).max(axis=-1)
      scale = np.maximum(scale, np.median(scale))
      grad_gap = max(grad_gap, float(np.max(
          np.abs(grad - want_grad).max(axis=-1) / scale)))
    return {"loss_gap": loss_gap, "grad_gap": grad_gap}

  def compare(self) -> list[Compared]:
    limits = self.ctx.traffic["limits"]
    return [Compared(k, v, limits[k]) for k, v in self.gaps().items()]


def build(ctx):
  return LossLoop(ctx)
