"""Serving drivers: the soft-op engine and the LM prefill/decode loop.

Two modes share this entry point:

* ``--engine`` — the `repro.serving` micro-batching engine for the
  soft-sort/rank op family: a mixed-size synthetic request stream runs
  through plan-derived AOT warmup, shape-bucketed dynamic batching and
  admission control, and prints throughput/latency/occupancy (docs/
  SERVING.md).  ``--arch`` is not needed in this mode:

    PYTHONPATH=src python -m repro.launch.serve --engine \
        --engine-requests 500 --engine-max-batch 32

* LM mode (default, requires ``--arch``) — prefill the prompt batch
  once, then decode tokens autoregressively with a uniform position
  counter (continuous batching with per-row lengths is a documented
  extension — the cache layout already supports per-row fill levels):

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --smoke --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import plan as repro_plan
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import artifacts as obs_artifacts
from repro.obs.tracing import trace_annotation


def greedy(logits):
  # Last axis is the vocabulary for every head layout, including audio
  # codebook heads (B, K, V) — argmax(-1) keeps the per-codebook structure.
  return jnp.argmax(logits, -1)


def run_engine(args) -> None:
  """Drive the repro.serving engine over a synthetic mixed-n stream."""
  from repro.obs import metrics
  from repro.obs.timing import percentiles
  from repro.serving import EngineConfig, ServingEngine, synthetic_stream

  ops = tuple(args.engine_ops.split(","))
  cfg = EngineConfig(
      ops=ops,
      min_bucket=args.engine_min_n,
      max_bucket=args.engine_max_n,
      max_batch=args.engine_max_batch,
      max_wait_ms=args.engine_max_wait_ms,
      queue_capacity=args.engine_queue,
      default_deadline_ms=args.engine_deadline_ms,
      impl=args.impl,
  )
  engine = ServingEngine(cfg, plan=repro_plan.get_active_plan())
  t0 = time.time()
  compiled = engine.warmup()
  t_warm = time.time() - t0
  print(f"[engine] warmed {compiled} executables over "
        f"{len(engine.policy.sizes)} n-buckets x "
        f"{len(engine.policy.row_sizes)} row-buckets in {t_warm:.1f}s")

  requests = synthetic_stream(
      args.engine_requests, seed=args.engine_seed, ops=ops,
      n_min=args.engine_min_n, n_max=args.engine_max_n,
      deadline_ms=args.engine_deadline_ms)
  t0 = time.time()
  with trace_annotation("repro_serve_engine"):
    results = engine.serve(requests)
  wall = time.time() - t0
  ok = [r for r in results if r.ok]
  shed = [r for r in results if not r.ok]
  lat = sorted(r.latency_us for r in ok) if ok else [0.0]
  p50, p95, p99 = percentiles(lat, (50, 95, 99))
  misses = sum(metrics.counters("aot_cache_miss").values())
  print(f"[engine] served {len(ok)}/{len(results)} requests "
        f"({len(shed)} shed) in {wall:.3f}s "
        f"({len(ok) / max(wall, 1e-9):.0f} req/s); "
        f"p50/p95/p99 latency {p50:.0f}/{p95:.0f}/{p99:.0f} us; "
        f"aot_cache_miss={misses}")

  if args.bench_json:
    results_rows = [{
        "name": "serve/engine_stream",
        "wall_us": wall * 1e6,
        "req_per_s": len(ok) / max(wall, 1e-9),
        "requests": len(results), "ok": len(ok), "shed": len(shed),
        "p50_us": p50, "p95_us": p95, "p99_us": p99,
        "aot_cache_miss_after_warmup": misses,
    }]
    obs_artifacts.write_bench_artifact(
        args.bench_json, results_rows,
        obs_artifacts.collect_meta(
            suite="serve-engine", ops=",".join(ops),
            requests=args.engine_requests,
            max_batch=cfg.max_batch, max_wait_ms=cfg.max_wait_ms,
            **repro_plan.plan_provenance()))


def run_lm(args) -> None:
  from repro.configs.base import get_config, parse_overrides
  from repro.data.pipeline import pipeline_for_arch
  from repro.launch import steps as ST
  from repro.models import transformer as T

  if args.smoke:
    from repro.configs.smoke import smoke_config
    cfg = smoke_config(args.arch)
  else:
    cfg = get_config(args.arch)
  over = parse_overrides(args.overrides)
  if over:
    cfg = dataclasses.replace(cfg, **over)
  if cfg.frontend == "audio":
    raise SystemExit("audio decode takes frame embeddings; use the "
                     "examples/ drivers for musicgen")

  max_len = args.prompt_len + args.gen
  params = T.init_params(cfg, jax.random.PRNGKey(0))
  pipe = pipeline_for_arch(cfg, args.batch, args.prompt_len)
  batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()
           if k in ("tokens", "image_embeds")}

  prefill = jax.jit(ST.make_prefill_step(cfg, max_len))
  # Donate the KV caches (positional arg 1): each decode step writes the
  # caches in place instead of copying the full cache pytree per token.
  decode = jax.jit(ST.make_decode_step(cfg), donate_argnums=(1,))

  t0 = time.time()
  with trace_annotation("repro_serve_prefill"):
    logits, caches = prefill(params, batch)
    jax.block_until_ready(logits)
  t_prefill = time.time() - t0

  pos0 = args.prompt_len + (cfg.num_patches if cfg.frontend == "vision"
                            else 0)
  tok = greedy(logits)
  out_tokens = [np.asarray(tok)]
  t0 = time.time()
  with trace_annotation("repro_serve_decode"):
    for i in range(args.gen - 1):
      logits, caches = decode(params, caches, tok, jnp.int32(pos0 + i))
      tok = greedy(logits)
      out_tokens.append(np.asarray(tok))
    jax.block_until_ready(logits)
  t_decode = time.time() - t0

  gen = np.stack(out_tokens, axis=1)
  print(f"[serve] prefill {args.batch}x{args.prompt_len} in "
        f"{t_prefill*1e3:.0f} ms; {args.gen - 1} decode steps in "
        f"{t_decode*1e3:.0f} ms "
        f"({(args.gen - 1) * args.batch / max(t_decode, 1e-9):.1f} tok/s)")
  print("[serve] sample generations (first 2 rows):")
  for row in gen[:2]:
    print("  ", row.reshape(row.shape[0], -1)[:, 0].tolist())

  if args.bench_json:
    decode_steps = max(args.gen - 1, 1)
    results = [
        {"name": "serve/prefill", "wall_us": t_prefill * 1e6,
         "batch": args.batch, "prompt_len": args.prompt_len},
        {"name": "serve/decode_step",
         "wall_us": t_decode / decode_steps * 1e6,
         "batch": args.batch, "decode_steps": decode_steps,
         "tok_per_s": decode_steps * args.batch / max(t_decode, 1e-9)},
    ]
    obs_artifacts.write_bench_artifact(
        args.bench_json, results,
        obs_artifacts.collect_meta(
            suite="serve", arch=args.arch, smoke=bool(args.smoke),
            batch=args.batch, prompt_len=args.prompt_len, gen=args.gen,
            **repro_plan.plan_provenance()))


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--arch", default=None,
                  help="LM architecture (required unless --engine)")
  ap.add_argument("--smoke", action="store_true")
  ap.add_argument("--batch", type=int, default=4)
  ap.add_argument("--prompt-len", type=int, default=32)
  ap.add_argument("--gen", type=int, default=16)
  ap.add_argument("--bench-json", default=None, metavar="PATH",
                  help="write a schema-v1 BENCH artifact (prefill/decode "
                       "walls + dispatch metrics) on exit")
  ap.add_argument("--plan", default=None, metavar="PLAN_JSON",
                  help="install an ExecutionPlan (repro.plan JSON) as the "
                       "active plan for every dispatch decision")
  ap.add_argument("--set", action="append", dest="overrides")
  # Soft-op serving engine mode (repro.serving).
  ap.add_argument("--engine", action="store_true",
                  help="serve the soft-op family through the repro.serving "
                       "micro-batching engine instead of the LM loop")
  ap.add_argument("--engine-ops",
                  default="soft_rank/l2/desc,soft_sort/l2/desc",
                  help="comma-separated repro.serving.SERVING_OPS keys")
  ap.add_argument("--engine-requests", type=int, default=500)
  ap.add_argument("--engine-seed", type=int, default=0)
  ap.add_argument("--engine-min-n", type=int, default=64)
  ap.add_argument("--engine-max-n", type=int, default=4096)
  ap.add_argument("--engine-max-batch", type=int, default=32)
  ap.add_argument("--engine-max-wait-ms", type=float, default=2.0)
  ap.add_argument("--engine-queue", type=int, default=1024)
  ap.add_argument("--engine-deadline-ms", type=float, default=None)
  ap.add_argument("--impl", default=None,
                  help="pin the isotonic backend for --engine mode")
  args = ap.parse_args()
  enable_compile_cache()

  if args.plan:
    repro_plan.set_active_plan(repro_plan.load_plan(args.plan))

  if args.engine:
    run_engine(args)
    return
  if not args.arch:
    raise SystemExit("--arch is required unless --engine is given")
  run_lm(args)


if __name__ == "__main__":
  main()
