"""Whole runs of each cell on the CPU, sound and with the timed path
broken underneath: ``correct`` must come out true, then false for every
fault the cell can have (a step that leaves its state unchanged, half of
the batch left out with the mean over the rest, an answer altered where
it is produced).  No cell spans chips, so none can drop an exchange."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from chipbench.tests.small import control_small, run_small


@pytest.mark.parametrize("workload", ["rank-loss.mslr", "lts-loss.stablelm-3b",
                                      "serve.mslr-steady",
                                      "lts-step.stablelm-3b"])
def test_sound_run_is_correct(workload):
  out = run_small(workload)
  assert out["correct"], out["compared"]
  assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("workload", ["rank-loss.mslr", "lts-loss.stablelm-3b",
                                      "serve.mslr-steady",
                                      "lts-step.stablelm-3b"])
def test_control_is_not_correct(workload):
  out = control_small(workload)
  assert out["rc"] == 0 and out["correct"] is False, out
  assert any(v["value"] is None or v["value"] > v["limit"]
             for v in out["compared"].values())


# -- the loss loops ------------------------------------------------------------


def _spearman_fault(monkeypatch, change):
  from repro.serving import ops
  real = ops.padded_op

  def patched(key):
    spec = real(key)
    fn = spec.fn
    return type(spec)(**{**spec.__dict__,
                         "fn": lambda *a, **k: change(fn(*a, **k))})
  monkeypatch.setattr(ops, "padded_op", patched)


def _half_rows(rows):
  q = rows.shape[0] // 2
  return jnp.concatenate([2.0 * rows[:q], 0.0 * rows[q:]])


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_rank_loss_faults(monkeypatch, fault):
  change = {"half_batch": _half_rows,
            "answer_altered": lambda rows: rows.at[0].multiply(1.5)}[fault]
  _spearman_fault(monkeypatch, change)
  assert not run_small("rank-loss.mslr")["correct"]


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_trimmed_loss_faults(monkeypatch, fault):
  from repro.core import losses
  real = losses.soft_trimmed_token_loss

  def patched(x, frac, eps):
    if fault == "half_batch":
      return real(x[:x.shape[0] // 2], frac, eps)
    return real(x.at[0].add(10.0), frac, eps)
  monkeypatch.setattr(losses, "soft_trimmed_token_loss", patched)
  assert not run_small("lts-loss.stablelm-3b")["correct"]


# -- the engine ------------------------------------------------------------------


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_serve_faults(monkeypatch, fault):
  from repro.serving import engine
  real = engine.bound_op

  def patched(*args):
    fn = real(*args)

    def broken(values, *rest):
      out = fn(values, *rest)
      if fault == "answer_altered":
        return out.at[:, 0].add(1.0)
      rows = out.shape[0]
      return out.at[rows - rows // 2:].set(0.0) if rows > 1 else out + 1.0
    return broken
  monkeypatch.setattr(engine, "bound_op", patched)
  assert not run_small("serve.mslr-steady")["correct"]


# -- the train step ----------------------------------------------------------------


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_faults(monkeypatch, fault):
  from repro.launch import steps
  if fault == "state_unchanged":
    real = steps.make_train_step

    def patched(*a, **k):
      step = real(*a, **k)

      def unchanged(params, opt_state, batch):
        _, _, metrics = step(params, opt_state, batch)
        return params, opt_state, metrics
      return unchanged
    monkeypatch.setattr(steps, "make_train_step", patched)
  else:
    real = steps.loss_from_batch

    def patched(cfg, params, batch):
      half = jax.tree.map(lambda v: v[:v.shape[0] // 2], batch)
      return real(cfg, params, half)
    monkeypatch.setattr(steps, "loss_from_batch", patched)
  assert not run_small("lts-step.stablelm-3b")["correct"]
