"""Run every benchmark. One module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig4_runtime,...] [--smoke]

Output: ``name,us_per_call,derived`` CSV on stdout, plus structured
``BENCH_*.json`` artifacts (schema ``repro.bench/v1``, see
docs/BENCHMARKS.md) in the working directory — CI validates and uploads
these:

* ``BENCH_runtime.json`` — the dispatch-backend sweep (fwd / fwd+bwd
  us/call per ``(regularization, backend, n, batch)`` cell), emitted by
  both the full run and ``--smoke``;
* ``BENCH_depth_curve.json`` — the O(n)-depth ("lax") vs O(log n)-depth
  ("scan") isotonic-solve curve across n with per-n speedups, emitted by
  both the full run and ``--smoke``;
* ``BENCH_projection.json`` — fused vs composed projection-pipeline
  e2e fwd / fwd+bwd timings with per-cell speedups and solver share,
  emitted by both the full run and ``--smoke``;
* ``BENCH_serving.json`` — the `repro.serving` engine vs per-request
  jit dispatch over the same mixed-size request stream (throughput,
  p50/p95/p99 latency, batch occupancy, shed demo), emitted by both the
  full run and ``--smoke``;
* ``BENCH_figures.json`` — every other paper-figure/table benchmark row,
  emitted by the full run.

Both artifacts embed the ``repro.obs`` metrics snapshot (per-backend
dispatch-resolution counters, shape buckets, trace-cache counts) taken at
write time, plus provenance meta (git sha, platform, jax version).

``--smoke`` runs only the backend sweep, depth curve, projection, and
serving suites at reduced sizes (n=1024 included so the scan-vs-lax and
fused-vs-composed speedup evidence survives the cut): a fast signal that
every registered backend still executes and emits schema-valid artifacts.
"""

from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (
    bench_label_ranking,
    bench_lts,
    bench_projection,
    bench_router,
    bench_runtime,
    bench_serving,
    bench_topk,
    common,
)
from repro import plan as plan_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import artifacts as obs_artifacts
from repro.obs import metrics as obs_metrics

BENCHES = {
    "fig4_runtime": bench_runtime.run,        # Figure 4 (right)
    "fig4_topk": bench_topk.run,              # Figure 4 (left/center)
    "table1_label_ranking": bench_label_ranking.run,  # Table 1 / Figure 5
    "fig6_fig7_lts": bench_lts.run,           # Figures 6-7
    "router": bench_router.run,               # framework hot path
    "backend_sweep": bench_runtime.run_backend_sweep,  # BENCH_runtime.json
    "depth_curve": bench_runtime.run_depth_curve,      # BENCH_depth_curve.json
    "projection": bench_projection.run,                # BENCH_projection.json
    "serving": bench_serving.run,                      # BENCH_serving.json
}


def main() -> None:
  ap = argparse.ArgumentParser()
  ap.add_argument("--only", default=None,
                  help="comma-separated subset of " + ",".join(BENCHES))
  ap.add_argument("--smoke", action="store_true",
                  help="tiny backend sweep + depth curve only; still writes "
                       "BENCH_*.json")
  args = ap.parse_args()
  enable_compile_cache()

  # Start each harness invocation from a clean registry so artifact metrics
  # describe exactly this run, not whatever imported us earlier.
  obs_metrics.reset()

  print("name,us_per_call,derived")
  if args.smoke:
    bench_runtime.run_backend_sweep(smoke=True)
    bench_runtime.run_depth_curve(smoke=True)
    bench_projection.run(smoke=True)
    bench_serving.run(smoke=True)
    return

  names = args.only.split(",") if args.only else list(BENCHES)
  failed = []
  for name in names:
    try:
      BENCHES[name]()
    except Exception:  # keep the harness going; report at the end
      failed.append(name)
      traceback.print_exc(file=sys.stderr)

  results = common.drain_results()
  if results:
    obs_artifacts.write_bench_artifact(
        "BENCH_figures.json", results,
        obs_artifacts.collect_meta(suite="figures", smoke=False,
                                   only=args.only or "all",
                                   **plan_mod.plan_provenance()))
  if failed:
    print(f"FAILED: {failed}", file=sys.stderr)
    raise SystemExit(1)


if __name__ == "__main__":
  main()
