"""Run one cell of the chip benchmark once.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (building the program, warming every shape the cell uses, from
the persistent compile cache in ``<checkout>/.jax_cache`` after the first
run) is timed from process start as ``setup_s``; then the cell's loop
runs for ``--seconds``.  After the window the device's peak memory is
read, the program's state is freed and what the window produced is
compared with the plain reference.  The last line of standard output is
one JSON object; the numbers compared, each beside its limit, are the
last lines of standard error.  With ``--trace 1`` the window runs under
the profiler and the per-layer metrics are reported instead of the
end-to-end ones.  Exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

from chipbench import harness  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def peak_bytes(device) -> int:
  """The device's peak memory: the most its buffers held, plus the most
  the runtime reserved for programs' temporaries, which the TPU keeps
  apart from ``peak_bytes_in_use``."""
  stats = device.memory_stats() or {}
  return (stats.get("peak_bytes_in_use", 0)
          + stats.get("peak_bytes_reserved", 0))


def parse(argv):
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--seconds", type=float, required=True)
  ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
  return ap.parse_args(argv)


def main(argv=None, *, allow_cpu: bool = False, tweak=None,
         control=None) -> int:
  """Run one cell.  ``allow_cpu`` and ``tweak`` (a function that edits
  the cell's context, to shrink it) are for the CPU tests only;
  ``control`` (a function given the cell once its state is freed, which
  puts the control's outputs in the program's place) is for
  ``chipbench.control``."""
  args = parse(argv)
  if not allow_cpu:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  try:
    spec = harness.benchmark()
    ctx = harness.resolve(spec, args.workload)
    ctx.seed, ctx.seconds = args.seed, args.seconds
    if tweak is not None:
      tweak(ctx)
    import jax
    if allow_cpu:
      devices = jax.devices()[:ctx.workload["chips"]]
      peaks = harness.device_peaks("TPU v5 lite")
    else:
      devices = harness.tpu_devices(ctx.workload["chips"])
      peaks = harness.device_peaks(devices[0].device_kind)
    harness.use_program()
    if not allow_cpu:
      from repro.launch.compile_cache import enable_compile_cache
      enable_compile_cache()
      jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    driver = harness.load_module("drivers", ctx.traffic["driver"])
  except harness.BenchError as e:
    print(f"chipbench: {e}", file=sys.stderr)
    return 2

  compiles = []
  jax.monitoring.register_event_duration_secs_listener(
      lambda event, secs, **_: compiles.append(secs)
      if event == COMPILE_EVENT else None)
  cell = driver.build(ctx)
  cell.setup()
  setup_s = time.perf_counter() - T0

  summary = None
  before = len(compiles)
  if args.trace:
    from chipbench import trace
    summary, window = trace.traced(
        cell.window, args.seconds, getattr(cell, "hlo_texts", lambda: ()))
  else:
    window = cell.window(args.seconds)
  print(f"chipbench: {len(compiles) - before} compiles in the window, "
        f"{before} in set-up", file=sys.stderr)
  peak = max(peak_bytes(d) for d in devices)
  cell.release()
  if control is not None:
    control(cell)

  try:
    compared = cell.compare()
  except Exception:  # a reference that crashes decides nothing
    traceback.print_exc()
    compared = [harness.Compared("reference_ran", math.inf, 0.0)]
  correct = (bool(compared) and all(c.ok for c in compared)
             and window.attempted > 0)

  record = harness.Record(workload=ctx.workload, config=ctx.config,
                          traffic=ctx.traffic, window=window, peaks=peaks,
                          trace=summary)
  metrics = {}
  for m in harness.cell_metrics(spec, args.workload, bool(args.trace)):
    if m["name"] == "setup_s":
      value = setup_s
    else:
      value = harness.load_module("metrics", m["name"]).read(record)
    if value is not None:
      metrics[m["name"]] = {"value": value, "unit": m["unit"]}

  dev = devices[0]
  device = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
  breakdown = None
  if summary is not None:
    device["busy_s"] = summary.busy_s
    device["window_s"] = summary.window_s
    breakdown = summary.breakdown()
  print(harness.result_line(correct=correct, window=window, metrics=metrics,
                            device=device, compared=compared,
                            breakdown=breakdown), flush=True)
  for c in compared:
    print(f"compared {c.name} {c.value!r} limit {c.limit!r} "
          f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
