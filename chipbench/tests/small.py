"""Each cell cut to a size a CPU test can hold, and a runner that drives
a whole run of it (``chipbench.run.main``) without looking for a chip."""

from __future__ import annotations

import contextlib
import io
import json
from unittest import mock

from chipbench import harness, run

# The serving cell, not yet in BENCHMARK.json (PERF.md, Open questions):
# its driver runs here with no metric but the set-up time.
SERVE_CELL = {
    "name": "serve.mslr-steady",
    "config": "mslr-listwise",
    "traffic": "mslr-steady",
    "chips": 1,
    "why": "open-loop Poisson re-rank requests from 4 clients (one sends "
           "half), MSLR list lengths untruncated: the engine's host path",
}


def spec_with_candidates() -> dict:
  spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
  if all(w["name"] != SERVE_CELL["name"] for w in spec["workloads"]):
    spec["workloads"] = spec["workloads"] + [SERVE_CELL]
  return spec


def shrink(ctx):
  t, c = ctx.traffic, ctx.config
  kind = t["driver"]
  if kind == "loss_loop" and t["loss"] == "spearman":
    t.update(lists_per_step=16, slate=32, batches=2)
  elif kind == "loss_loop":
    t.update(sequences=2, seq=64, batches=2)
  elif kind == "serve_open_loop":
    t.update(rate_per_s=40.0, max_bucket=256, max_batch=4, check_sample=1000)
    c["list_length"] = dict(c["list_length"], max=256)
  elif kind == "train_loop":
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             intermediate_size=128, vocab_size=256, num_hidden_layers=2)
    c["program"]["replace"].update(
        d_model=64, num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128,
        vocab_size=256, num_layers=2)
    c["train"].update(batch=2, seq=64)
    # This width's own readings on the CPU (bfloat16 rounds differently at
    # d = 64): sound runs read loss 3.8e-4, gradient 0.033 and update 0.011
    # at most; the int8 control loss 7e-4 to 2.9e-3; half of the batch
    # 0.014, 0.12 and 0.15 at least (5 seeds).
    t["limits"] = {"loss_gap": 1e-3, "grad_gap": 0.05, "update_gap": 0.05}


def run_small(workload: str, seed: int = 2**31 + 11, seconds: float = 0.5,
              trace: int = 0) -> dict:
  """One whole run of the shrunk cell on the CPU; its result line."""
  out = io.StringIO()
  with contextlib.redirect_stdout(out), \
      mock.patch.object(harness, "benchmark", spec_with_candidates):
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  allow_cpu=True, tweak=shrink)
  assert rc == 0
  return json.loads(out.getvalue().strip().splitlines()[-1])


def control_small(workload: str, seed: int = 2**31 + 13,
                  seconds: float = 0.5) -> dict:
  """One whole run of the shrunk cell on the CPU with the control in the
  program's place (``chipbench.control``)."""
  from chipbench import control
  with mock.patch.object(harness, "benchmark", spec_with_candidates):
    return control.control_run(workload, seed, seconds, allow_cpu=True,
                               tweak=shrink)


def serve_window(seed: int = 2**31 + 17, seconds: float = 0.5):
  """The shrunk serving cell's window on the CPU (its ``Window``)."""
  ctx = harness.resolve(spec_with_candidates(), "serve.mslr-steady")
  ctx.seed, ctx.seconds = seed, seconds
  shrink(ctx)
  harness.use_program()
  cell = harness.load_module("drivers", "serve_open_loop").build(ctx)
  cell.setup()
  try:
    return cell.window(seconds)
  finally:
    cell.release()
