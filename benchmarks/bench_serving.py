"""Serving engine vs per-request jit dispatch -> BENCH_serving.json.

The tentpole evidence for `repro.serving` (ISSUE 10): the same Zipf-ish
mixed-size request stream (n in [64, 4096], per-request eps) is served
three ways *in the same run*:

* ``serving/engine_stream`` — the micro-batching engine after
  plan-derived AOT warmup (shape buckets, dynamic batching, admission
  control), with p50/p95/p99 request latency, batch occupancy and
  padding-waste columns; ``aot_cache_miss_after_warmup`` must be 0 —
  the run *raises* otherwise, so CI can never upload an artifact whose
  warmup enumeration missed a bucket the stream hit;
* ``serving/per_request_jit_cold`` — one ``jax.jit`` dispatch per
  request, first pass: every novel (op, n) pays trace+compile on the
  request path (the status quo this subsystem replaces);
* ``serving/per_request_jit_warm`` — the same pass again with every
  shape already compiled: the strongest baseline (pure per-call
  dispatch + kernel time, no compiles).

The acceptance bar is the ``serving/speedup`` row: engine throughput
must be strictly higher than the *warm* per-request baseline
(``tools/check_backends.py --bench-serving`` gates this in CI).
``serving/shed_demo`` exercises both load-shedding paths (bounded-queue
rejection and deadline expiry in queue) so the `serving_shed` counters
land in the artifact's metrics snapshot.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro import plan as plan_mod
from repro.core import soft_rank, soft_sort
from repro.launch.compile_cache import compile_cache_off
from repro.obs import artifacts as obs_artifacts
from repro.obs import metrics
from repro.obs.timing import percentiles
from repro.serving import (
    EngineConfig,
    Request,
    ServingEngine,
    synthetic_stream,
)

OPS = ("soft_rank/l2/desc", "soft_sort/l2/desc")


@functools.lru_cache(maxsize=None)
def _baseline_fn(op_key: str):
  """One jitted unpadded operator per variant, stable identity so the
  warm pass reuses the jit cache (eps rides as a traced scalar)."""
  base = soft_rank if op_key.startswith("soft_rank") else soft_sort
  def fn(values, eps):
    return base(values, eps, "l2", "DESCENDING")
  return jax.jit(fn)


def _per_request_pass(requests) -> tuple[float, list[float]]:
  """Serve every request with one jit call each; (wall_s, latencies_us)."""
  lat = []
  t_pass = time.perf_counter()
  for req in requests:
    fn = _baseline_fn(req.op)
    t0 = time.perf_counter()
    jax.block_until_ready(
        fn(jnp.asarray(req.values)[None], jnp.float32(req.eps)))
    lat.append((time.perf_counter() - t0) * 1e6)
  return time.perf_counter() - t_pass, lat


def _hist_summary(name: str) -> dict:
  """Flatten one obs histogram family into avg/min/max columns."""
  out: dict = {}
  total_n, total_sum = 0, 0.0
  lo, hi = np.inf, -np.inf
  for h in metrics.histograms(name).values():
    total_n += h["count"]
    total_sum += h["sum"]
    if h["min"] is not None:
      lo, hi = min(lo, h["min"]), max(hi, h["max"])
  if total_n:
    out = {"avg": round(total_sum / total_n, 2),
           "min": round(float(lo), 2), "max": round(float(hi), 2),
           "count": total_n}
  return out


def _shed_demo() -> dict:
  """Exercise both shedding paths on a tiny engine (nothing executes,
  so no compiles); returns the typed-shed counts."""
  cfg = EngineConfig(ops=OPS, min_bucket=64, max_bucket=64, max_batch=4,
                     queue_capacity=4, max_wait_ms=1000.0)
  rng = np.random.default_rng(7)
  t0 = time.perf_counter()
  engine = ServingEngine(cfg)
  reqs = [Request(op=OPS[0], values=rng.standard_normal(33).astype(np.float32),
                  deadline_ms=0.0)
          for _ in range(8)]
  handles = [engine.submit(r) for r in reqs]
  queue_full = sum(1 for h in handles
                   if h.done() and h.result(0).status == "shed_queue_full")
  time.sleep(0.002)            # let the queued deadlines (0 ms) expire
  engine.step()
  deadline = sum(1 for h in handles
                 if h.done() and h.result(0).status == "shed_deadline")
  return {"wall_us": (time.perf_counter() - t0) * 1e6,
          "shed_queue_full": queue_full, "shed_deadline": deadline}


def run(smoke: bool = False, out_path: str = "BENCH_serving.json") -> dict:
  """Serve the stream three ways and write the schema-v1 artifact."""
  if smoke:
    n_max, max_batch, num_requests = 512, 8, 120
  else:
    n_max, max_batch, num_requests = 4096, 32, 600
  cfg = EngineConfig(ops=OPS, min_bucket=64, max_bucket=n_max,
                     max_batch=max_batch, max_wait_ms=2.0,
                     queue_capacity=max(num_requests, 256))
  engine = ServingEngine(cfg)

  # The warmup and the cold pass measure compilation: a persistent cache
  # populated by an earlier run must not answer for it.
  with compile_cache_off():
    t0 = time.perf_counter()
    compiled = engine.warmup()
    warmup_us = (time.perf_counter() - t0) * 1e6
  emit(f"serving/warmup/buckets={len(engine.policy.sizes)}"
       f"x{len(engine.policy.row_sizes)}", warmup_us,
       f"{compiled} executables AOT-compiled", collect=False)

  requests = synthetic_stream(num_requests, seed=0, ops=OPS,
                              n_min=64, n_max=n_max)
  t0 = time.perf_counter()
  results = engine.serve(requests)
  wall = time.perf_counter() - t0
  ok = [r for r in results if r.ok]
  if len(ok) != len(results):
    raise RuntimeError(f"engine shed {len(results) - len(ok)} requests in a "
                       f"no-deadline closed-loop run; expected none")
  p50, p95, p99 = percentiles([r.latency_us for r in ok])
  misses = sum(metrics.counters("aot_cache_miss").values())
  if misses:
    raise RuntimeError(
        f"aot_cache_miss={misses} after plan-derived warmup: the request "
        f"stream hit a bucket the warmup enumeration missed")
  engine_rps = len(ok) / max(wall, 1e-9)
  occupancy = _hist_summary("serving_batch_occupancy")
  waste = _hist_summary("serving_padding_waste")

  rows = [{
      "name": "serving/engine_stream",
      "wall_us": wall * 1e6, "req_per_s": round(engine_rps, 1),
      "requests": len(results), "ok": len(ok),
      "p50_us": p50, "p95_us": p95, "p99_us": p99,
      "max_batch": max_batch, "n_max": n_max, "ops": ",".join(OPS),
      "aot_cache_miss_after_warmup": misses,
      "warmup_compiles": compiled, "warmup_us": warmup_us,
  }, {
      "name": "serving/batch_occupancy",
      "wall_us": wall * 1e6,
      "occupancy_pct": occupancy, "padding_waste_pct": waste,
      "batches": occupancy.get("count", 0),
  }]
  emit("serving/engine_stream", wall * 1e6,
       f"{engine_rps:.0f} req/s; p50/p95/p99="
       f"{p50:.0f}/{p95:.0f}/{p99:.0f}us; "
       f"occupancy_avg={occupancy.get('avg', 0)}%", collect=False)

  # Per-request jit baselines over the identical stream.
  _baseline_fn.cache_clear()
  with compile_cache_off():
    cold_wall, _ = _per_request_pass(requests)
  warm_wall, warm_lat = _per_request_pass(requests)
  wp50, wp95, wp99 = percentiles(warm_lat)
  cold_rps = len(requests) / max(cold_wall, 1e-9)
  warm_rps = len(requests) / max(warm_wall, 1e-9)
  rows.append({"name": "serving/per_request_jit_cold",
               "wall_us": cold_wall * 1e6, "req_per_s": round(cold_rps, 1),
               "requests": len(requests)})
  rows.append({"name": "serving/per_request_jit_warm",
               "wall_us": warm_wall * 1e6, "req_per_s": round(warm_rps, 1),
               "requests": len(requests),
               "p50_us": wp50, "p95_us": wp95, "p99_us": wp99})
  emit("serving/per_request_jit_cold", cold_wall * 1e6,
       f"{cold_rps:.0f} req/s (trace+compile on the request path)",
       collect=False)
  emit("serving/per_request_jit_warm", warm_wall * 1e6,
       f"{warm_rps:.0f} req/s (all shapes precompiled)", collect=False)

  rows.append({
      "name": "serving/speedup",
      "wall_us": wall * 1e6,
      "engine_req_per_s": round(engine_rps, 1),
      "warm_req_per_s": round(warm_rps, 1),
      "cold_req_per_s": round(cold_rps, 1),
      "speedup_vs_warm_x": round(engine_rps / max(warm_rps, 1e-9), 3),
      "speedup_vs_cold_x": round(engine_rps / max(cold_rps, 1e-9), 3),
  })
  emit("serving/speedup", wall * 1e6,
       f"engine is {engine_rps / max(warm_rps, 1e-9):.2f}x warm per-request "
       f"jit ({engine_rps / max(cold_rps, 1e-9):.2f}x cold)", collect=False)

  shed = _shed_demo()
  rows.append({"name": "serving/shed_demo", **shed})
  emit("serving/shed_demo", shed["wall_us"],
       f"queue_full={shed['shed_queue_full']} "
       f"deadline={shed['shed_deadline']}", collect=False)

  meta = obs_artifacts.collect_meta(
      smoke=smoke, suite="serving", ops=",".join(OPS),
      max_batch=max_batch, n_max=n_max, requests=num_requests,
      **plan_mod.plan_provenance())
  return obs_artifacts.write_bench_artifact(out_path, rows, meta)


if __name__ == "__main__":
  run()
