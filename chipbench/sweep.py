"""Find the knee of a serving cell: the highest offered rate whose
backlog does not grow.

    python3 -m chipbench.sweep --config mslr-listwise --traffic mslr-steady --rates 100,200,400 --seed 1

One process, one engine warmed once; a window of ``--seconds`` at each
rate in turn, with the traffic mix otherwise unchanged.  The
configuration is named as in ``BENCHMARK.json``, the mix by its file
under ``chipbench/traffic``, so a mix can be swept before its cell
exists.  For each
rate it prints the latency percentiles, the requests served by the
window's close per second, the engine's queue depth as each request was
sent (its median over the first half of the window, its largest over the
last tenth, and at the close), and the mean latency of the last tenth of
the requests over that of the first tenth.  A rate is steady when
nearly all of its requests are back by the close, the queue of the last
tenth is no deeper than two batches plus the first half's, and the
latency does not climb through the window.  The last line names the
knee, the highest rate steady with every lower rate, and 0.8 of it.
The mix's file records both; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

from chipbench import harness


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--config", required=True)
  ap.add_argument("--traffic", required=True)
  ap.add_argument("--rates", required=True)
  ap.add_argument("--seed", type=int, default=1)
  ap.add_argument("--seconds", type=float, default=10.0)
  args = ap.parse_args(argv)
  os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  spec = harness.benchmark()
  configs = {c["name"]: c for c in spec["configs"]}
  if args.config not in configs:
    raise harness.BenchError(f"no configuration {args.config!r} in "
                             f"BENCHMARK.json; have {sorted(configs)}")
  ctx = harness.Context(
      seed=args.seed, seconds=args.seconds,
      workload={"name": f"{args.config}.{args.traffic}", "chips": 1},
      config=harness.load_json(harness.ROOT, configs[args.config]["file"]),
      traffic=harness.load_json(harness.HERE, "traffic",
                                f"{args.traffic}.json"))
  harness.tpu_devices(1)
  harness.use_program()
  import jax
  from repro.launch.compile_cache import enable_compile_cache
  enable_compile_cache()
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  cell = harness.load_module("drivers", ctx.traffic["driver"]).build(ctx)
  t0 = time.perf_counter()
  cell.setup()
  print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
  max_batch = ctx.traffic["max_batch"]
  knee, broken = None, False
  for rate in (float(r) for r in args.rates.split(",")):
    cell.requests = cell.make_requests(rate)
    depth = []
    real_submit = cell.engine.submit

    def submit(req, real_submit=real_submit):
      depth.append(len(cell.engine.queue))
      return real_submit(req)
    cell.engine.submit = submit
    w = cell.window(args.seconds)
    cell.engine.submit = real_submit
    lat = sorted(w.latencies_ms)
    tenth = max(1, len(lat) // 10)
    order = w.latencies_ms
    grow = (statistics.mean(order[-tenth:])
            / max(1e-9, statistics.mean(order[:tenth])))
    first_half = statistics.median(depth[:len(depth) // 2] or [0])
    last_tenth = max(depth[-tenth:] or [0])
    served = w.served / w.seconds
    steady = (w.failed == 0 and served >= 0.99 * w.attempted / w.seconds
              and last_tenth <= first_half + 2 * max_batch and grow <= 1.5)
    broken = broken or not steady
    if not broken:
      knee = rate
    print(json.dumps({
        "rate_per_s": rate, "requests": w.attempted, "failed": w.failed,
        "served_by_close_per_s": served,
        "p50_ms": lat[len(lat) // 2], "p95_ms": lat[int(0.95 * len(lat))],
        "queue_first_half_median": first_half,
        "queue_last_tenth_max": last_tenth,
        "queue_at_close": depth[-1] if depth else 0,
        "last_over_first_tenth": grow, "steady": steady,
        "gen_late_p95_ms": sorted(w.lateness_ms)[int(0.95 * len(lat))],
        **{k: v for k, v in w.histograms.items()}}), flush=True)
  print(json.dumps({"knee_per_s": knee,
                    "rate_per_s": None if knee is None else 0.8 * knee}),
        flush=True)
  cell.release()
  return 0


if __name__ == "__main__":
  sys.exit(main())
