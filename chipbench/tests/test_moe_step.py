"""The DeepSeek-V2 cell cut small, run whole on the CPU through
``chipbench.run.main``: correct when sound, not correct when the timed
path is broken underneath (a step that leaves its state unchanged, half
of the batch left out).  At this width the int8 control reads within the
sound runs' spread (the program's bfloat16 activations round about as
much as int8 operands do at d = 64), so the control is shown to fail on
the chip, at the cell's own size (PERF.md)."""

from __future__ import annotations

import contextlib
import io
import json

import jax
import pytest

from chipbench import harness, moe_counts, run
from chipbench.tests.small_moe import FILE, shrink

CELL = "moe-step.deepseek-v2-lite"


def _run(seed, trace=0, **kw):
  out = io.StringIO()
  with contextlib.redirect_stdout(out):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.5", "--trace", str(trace)], allow_cpu=True,
                  tweak=shrink, **kw)
  assert rc == 0
  return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct():
  out = _run(2**31 + 15)
  assert out["correct"], out["compared"]
  assert set(out["metrics"]) == {"step_ms", "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_faults_are_not_correct(monkeypatch, fault):
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
  assert not _run(2**31 + 16)["correct"]


def test_flops_of_the_published_share():
  c = harness.load_json(FILE)
  # 409M active parameters a token (ISSUE's table): dense layer 81.0M,
  # 8 expert layers of 37.7M, head 26.2M.
  assert abs(moe_counts.active_params(c) / 1e6 - 408.7) < 0.5
  assert abs(moe_counts.train_flops(c, 1, 8192) / 1e12 - 29.4) < 0.2


def test_expert_readers_take_the_compilers_ragged_dots():
  """The TPU compiler names ``lax.ragged_dot`` ``ragged-dot-*`` and drops
  its scope: the experts' readers count those ops too, nothing else
  outside the scope."""
  from chipbench.harness import Record, Window
  from chipbench.trace import Op, Summary
  ms = 1e6
  ops = [Op("ragged-dot-none.5", 0, 30 * ms, "ragged-dot-none", "d"),
         Op("fusion.1", 0, 10 * ms,
            "jit(train_step)/transpose(jvp(repro_moe_experts))/mul", "d"),
         Op("fusion.2", 0, 5 * ms, "jit(train_step)/repro_mla/dot", "d")]
  w = Window(seconds=1.0, attempted=2,
             histograms={"moe_assignments{where=held}": 1000})
  c = harness.load_json(FILE)
  rec = Record(workload={}, config=c, traffic={}, window=w,
               peaks={"bf16_flops_per_s": 197e12},
               trace=Summary(window_s=1.0, busy_s=1.0, ops=ops, gaps=[]))
  read = lambda m: harness.load_module("metrics", m).read(rec)
  assert read("moe_experts_ms.step") == 20.0
  assert read("mla_ms.step") == 2.5
  want = 100 * 1000 * moe_counts.expert_flops_per_assignment(c) / (
      0.04 * 197e12)
  assert abs(read("moe_experts_roofline.step") - want) < 1e-9
