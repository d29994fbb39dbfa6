#!/usr/bin/env python3
"""Derive the packaged default ExecutionPlan from measured BENCH sweeps.

  PYTHONPATH=src python tools/autotune.py \
      [--bench BENCH_runtime.json] [--bench-projection BENCH_projection.json] \
      [--out src/repro/plan/default_plan.json] [--run | --smoke] [--dry-run]

Turns the committed benchmark trajectory into the committed
``default_plan.json`` that ``auto`` dispatch resolves through
(``repro.plan``): for every measured ``(regularization, n, batch)`` cell
the winning backend (lowest end-to-end fwd+bwd time) becomes a plan-table
entry, bucketed by shape with boundaries at the geometric midpoints of the
measured grid and merged where adjacent buckets agree.  Every emitted rule
carries the BENCH row names that justify it (``evidence``), which
``tools/check_backends.py --plan`` re-verifies in CI — a plan entry no
timing row supports fails the build.

Derivation policy:

* Rules are keyed to the platform the artifact was measured on; on any
  other platform the packaged plan is silent and resolution falls through
  to the built-in plan (e.g. TPU -> scan stays untouched by a CPU-derived
  plan).
* ``pallas`` is excluded as a candidate off-TPU: interpret-mode timings at
  small n say nothing about TPU hardware and extrapolate catastrophically.
* A winning ``minimax`` rule always gets the built-in ``rows * n^2``
  memory cap (``max_elems``) — the O(n^2) closed form must never be chosen
  into an OOM regardless of how well it timed at a small measured cell.
* Backward: the sweep's ``fwd_bwd_us`` timings exercised the default
  ``segscan`` VJP, so the plan pins it with those rows as evidence.

By default the plan is derived *from the committed artifacts* (so the
committed plan and the committed bench rows can never disagree); pass
``--run`` / ``--smoke`` to re-run the sweeps on the current host first.
"""

from __future__ import annotations

import argparse
import math
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "src"))

import json  # noqa: E402

from repro import plan as plan_mod  # noqa: E402
from repro.obs import metrics as obs_metrics  # noqa: E402

DEFAULT_OUT = os.path.join("src", "repro", "plan", "default_plan.json")

REGS = ("l2", "kl")


def _load(path: str) -> dict:
  with open(path, encoding="utf-8") as f:
    return json.load(f)


def _finite(v) -> bool:
  return isinstance(v, (int, float)) and math.isfinite(v)


def _midpoint(lo: int, hi: int) -> int:
  """Geometric midpoint of two measured grid values (timings scale
  multiplicatively with size, so the crossover belongs on a log axis)."""
  return int(math.sqrt(lo * hi))


def _cells(results: list[dict], metric: str,
           exclude: set[str]) -> dict[tuple, dict[str, tuple]]:
  """{(reg, n, batch): {backend: (timing_us, row_name)}} for ran rows."""
  out: dict[tuple, dict[str, tuple]] = {}
  for r in results:
    if r.get("skipped") or not _finite(r.get(metric)):
      continue
    backend, reg = r.get("backend"), r.get("regularization")
    if backend in exclude or reg not in REGS:
      continue
    key = (reg, r.get("n"), r.get("batch"))
    if None in key:
      continue
    cell = out.setdefault(key, {})
    # Keep the best (lowest) timing if a backend appears twice.
    if backend not in cell or r[metric] < cell[backend][0]:
      cell[backend] = (r[metric], r["name"])
  return out


def _bounds(values: list[int], i_lo: int, i_hi: int):
  """(min, max) bucket bounds covering grid values[i_lo..i_hi] inclusive,
  with open outer edges (the first bucket extrapolates down, the last up)
  and geometric-midpoint inner edges."""
  lo = None if i_lo == 0 else _midpoint(values[i_lo - 1], values[i_lo]) + 1
  hi = (None if i_hi == len(values) - 1
        else _midpoint(values[i_hi], values[i_hi + 1]))
  return lo, hi


def _derive_rules(kind: str, op: str, cells: dict[tuple, dict[str, tuple]],
                  platform: str) -> list[plan_mod.PlanRule]:
  """Winner-per-cell -> merged shape-bucket rules, per regularization.

  For each reg, decide the winner of every measured (n, batch) cell, merge
  consecutive n grid values whose per-batch winner maps agree, then within
  each n-bucket merge consecutive batches (rows == batch in the sweep,
  inputs are (batch, n)) that agree.
  """
  rules: list[plan_mod.PlanRule] = []
  for reg in REGS:
    ns = sorted({n for (r, n, b) in cells if r == reg})
    batches = sorted({b for (r, n, b) in cells if r == reg})
    if not ns:
      continue
    # winner[n][batch] = (backend, evidence_row)
    winner: dict[int, dict[int, tuple]] = {}
    for n in ns:
      for b in batches:
        cell = cells.get((reg, n, b))
        if not cell:
          continue
        best = min(cell, key=lambda k: cell[k][0])
        winner.setdefault(n, {})[b] = (best, cell[best][1])

    # Merge consecutive n values with identical per-batch winner maps.
    groups: list[tuple[int, int]] = []  # (i_lo, i_hi) into ns
    for i, n in enumerate(ns):
      sig = {b: w[0] for b, w in winner.get(n, {}).items()}
      prev_sig = ({b: w[0] for b, w in winner.get(ns[groups[-1][0]], {})
                   .items()} if groups else None)
      if groups and sig == prev_sig:
        groups[-1] = (groups[-1][0], i)
      else:
        groups.append((i, i))

    for i_lo, i_hi in groups:
      min_n, max_n = _bounds(ns, i_lo, i_hi)
      group_ns = ns[i_lo:i_hi + 1]
      bmap = winner.get(group_ns[0], {})
      gbatches = sorted(bmap)
      # Merge consecutive batches with the same winning backend.
      bgroups: list[tuple[int, int]] = []
      for j, b in enumerate(gbatches):
        if bgroups and bmap[b][0] == bmap[gbatches[bgroups[-1][0]]][0]:
          bgroups[-1] = (bgroups[-1][0], j)
        else:
          bgroups.append((j, j))
      for j_lo, j_hi in bgroups:
        backend = bmap[gbatches[j_lo]][0]
        min_rows, max_rows = ((None, None) if len(bgroups) == 1
                              else _bounds(gbatches, j_lo, j_hi))
        evidence = tuple(
            winner[n][b][1] for n in group_ns
            for b in gbatches[j_lo:j_hi + 1] if b in winner.get(n, {}))
        rules.append(plan_mod.PlanRule(
            kind, backend, op=op, regularization=reg, platform=platform,
            min_n=min_n, max_n=max_n, min_rows=min_rows, max_rows=max_rows,
            max_elems=(plan_mod.BUILTIN_MINIMAX_MAX_ELEMS
                       if backend == "minimax" else None),
            evidence=evidence))
  return rules


def build_plan(runtime_payload: dict,
               projection_payload: dict) -> plan_mod.ExecutionPlan:
  platform = runtime_payload.get("meta", {}).get("platform", "cpu")
  exclude = {"pallas"} if platform != "tpu" else set()

  sweep = [r for r in runtime_payload.get("results", [])
           if r.get("name", "").startswith("backend_sweep/")]
  fwd_cells = _cells(sweep, "fwd_bwd_us", exclude)
  rules = _derive_rules("forward", "isotonic", fwd_cells, platform)

  # The sweep's fwd+bwd timings ran the default segscan VJP end to end:
  # pin it, evidenced by one winning row per (reg, n).
  bwd_evidence = tuple(dict.fromkeys(
      min(cell.values(), key=lambda v: v[0])[1]
      for key, cell in sorted(fwd_cells.items(), key=str)
      if key[2] == min(b for (_, _, b) in fwd_cells)))
  if bwd_evidence:
    rules.append(plan_mod.PlanRule(
        "backward", "segscan", platform=platform, evidence=bwd_evidence))

  proj_cells = _cells(projection_payload.get("results", []),
                      "e2e_fwd_bwd_us", exclude=set())
  rules.extend(_derive_rules("projection", "projection", proj_cells,
                             platform))

  meta = {
      "generated_by": "tools/autotune.py",
      "platform": platform,
      "derived_from": {
          "runtime": runtime_payload.get("meta", {}).get("git_sha", "?"),
          "projection": projection_payload.get("meta", {}).get(
              "git_sha", "?"),
      },
      "cells": {"runtime": len(fwd_cells), "projection": len(proj_cells)},
  }
  plan = plan_mod.ExecutionPlan(name=f"autotuned-{platform}",
                                rules=tuple(rules), meta=meta)
  for rule in plan.rules:
    obs_metrics.counter_inc("autotune_rule", kind=rule.kind,
                            backend=rule.backend)
  return plan


def main(argv: list[str]) -> int:
  ap = argparse.ArgumentParser(
      description="derive default_plan.json from BENCH sweep artifacts")
  ap.add_argument("--bench", default="BENCH_runtime.json")
  ap.add_argument("--bench-projection", default="BENCH_projection.json")
  ap.add_argument("--out", default=DEFAULT_OUT)
  ap.add_argument("--run", action="store_true",
                  help="re-run the full sweeps on this host first")
  ap.add_argument("--smoke", action="store_true",
                  help="re-run the reduced (smoke) sweeps first")
  ap.add_argument("--dry-run", action="store_true",
                  help="print the derived plan JSON without writing")
  args = ap.parse_args(argv)

  if args.run or args.smoke:
    from benchmarks.bench_projection import run as run_projection
    from benchmarks.bench_runtime import run_backend_sweep
    run_backend_sweep(smoke=args.smoke, out_path=args.bench)
    run_projection(smoke=args.smoke, out_path=args.bench_projection)

  plan = build_plan(_load(args.bench), _load(args.bench_projection))
  if args.dry_run:
    print(plan.to_json())
    return 0
  plan.save(args.out)
  plan_mod.invalidate_default_plan_cache()
  print(f"autotune: wrote {args.out} — {len(plan.rules)} rules, "
        f"hash {plan.plan_hash()}")
  return 0


if __name__ == "__main__":
  raise SystemExit(main(sys.argv[1:]))
