"""The control of ``correct``, run through the harness's own comparison.

    python3 -m chipbench.control --workload <name> --seeds 1,2,3 [--seconds 2]

For each seed, in one process, one whole run of the cell
(``chipbench.run.main``: set-up, a short window at the cell's own size
and load, then the comparison), with the control put in the program's
place once the window has closed: the reference computed one precision
below the configuration's, bfloat16 for the float32 operators and the
engine (every intermediate rounded), int8 matmul operands for the
bfloat16 train step.  Before the swap it reads the numbers the run
compares for the program (the lower readings of the limits) and for the
control (the upper ones), and for the train step also for one planted
fault, half of the batch's tokens left out of the loss.  One JSON line
per seed, with the run's own ``correct``, which has to read false; the
exit code is 1 when any seed's control came out correct.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys

from chipbench import run


def swap_in_control(cell, kind: str) -> dict:
  """Read the numbers compared for the program and for the control (and
  the train step's planted fault), then put the control's outputs where
  the comparison reads the program's.  Returns the readings."""
  from chipbench.reference import soft_ops
  if kind == "loss_loop":
    ctrl = {b: ((None, rows), grad) for b, (rows, grad) in
            ((b, cell.reference(b, soft_ops.BF16))
             for b in range(len(cell.host)))}
    out = {"program": cell.gaps(), "control": cell.gaps(outputs=ctrl)}
    cell.last = ctrl
    return out
  if kind == "serve_open_loop":
    idx = cell.sample()
    ctrl = {}
    for i in idx:
      r = cell.requests[i]
      ctrl[i], _ = soft_ops.soft_rank_desc(r.values, r.eps, soft_ops.BF16)
    out = {"program": {"value_gap": cell.value_gap(cell.answers(idx))},
           "control": {"value_gap": cell.value_gap(ctrl)}}
    cell.answers = lambda idx: {i: ctrl[i] for i in idx}
    return out
  if kind == "train_loop":
    ref = cell.reference()
    ctrl = cell.reference(quantize="int8")
    out = {"program": cell.gaps(cell.readings(), ref),
           "control": cell.gaps(ctrl, ref),
           "half_batch": cell.gaps(cell.reference(keep_tokens=0.5), ref)}
    cell.readings = lambda: ctrl
    cell.reference = lambda **_: ref
    return out
  raise ValueError(kind)


def control_run(workload: str, seed: int, seconds: float, **kw) -> dict:
  """One run of ``workload`` with the control in the program's place:
  the readings, and the run's ``correct`` and compared numbers."""
  got = {}

  def control(cell):
    got.update(swap_in_control(cell, cell.ctx.traffic["driver"]))

  out = io.StringIO()
  with contextlib.redirect_stdout(out):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0"],
                  control=control, **kw)
  line = json.loads(out.getvalue().strip().splitlines()[-1]) if rc == 0 \
      else {}
  return {"seed": seed, "rc": rc, **got, "correct": line.get("correct"),
          "compared": line.get("compared")}


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seeds", required=True)
  ap.add_argument("--seconds", type=float, default=2.0)
  args = ap.parse_args(argv)
  failed = False
  for seed in (int(s) for s in args.seeds.split(",")):
    res = control_run(args.workload, seed, args.seconds)
    failed |= res["correct"] is not False
    print(json.dumps(res), flush=True)
  return 1 if failed else 0


if __name__ == "__main__":
  sys.exit(main())
