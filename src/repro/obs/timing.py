"""Wall-clock timing: the one implementation the benchmarks and drivers use.

All timings are *eager* ``block_until_ready`` walls — device work is forced
to completion inside the measured region, so the numbers are end-to-end
per-call latencies, not async-dispatch artifacts.  When metrics are enabled
each measurement is also recorded into the ``obs`` histogram registry so
artifacts carry the full distribution, not just the median.
"""

from __future__ import annotations

import math
import time
from typing import Callable

import jax

from repro.obs import metrics
from repro.obs.tracing import span


def timed(fn: Callable, *args) -> tuple[object, float]:
  """Run ``fn(*args)``, block until device-complete; (result, seconds)."""
  t0 = time.perf_counter()
  out = jax.block_until_ready(fn(*args))
  return out, time.perf_counter() - t0


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 5,
            name: str | None = None) -> float:
  """Median wall time per call in microseconds (jit-compiled ``fn``).

  ``warmup`` calls (compilation + cache effects) are excluded from the
  measurement.  When ``name`` is given and metrics are enabled, every
  measured iteration is observed into histogram ``bench_us{name=...}``.
  """
  for _ in range(warmup):
    jax.block_until_ready(fn(*args))
  times = []
  with span(f"repro_bench_{name}" if name else "repro_bench"):
    for _ in range(iters):
      _, dt = timed(fn, *args)
      times.append(dt)
  if name is not None:
    for dt in times:
      metrics.observe("bench_us", dt * 1e6, name=name)
  times.sort()
  return times[len(times) // 2] * 1e6


def percentiles(samples, qs=(50, 95, 99)) -> tuple[float, ...]:
  """Nearest-rank percentiles of a sample list (sorted or not).

  The serving benchmarks report p50/p95/p99 request latencies with this
  — nearest-rank (no interpolation) so the values are actual observed
  latencies.  Returns one float per ``q``; empty input gives zeros.
  """
  if not samples:
    return tuple(0.0 for _ in qs)
  ordered = sorted(samples)
  out = []
  for q in qs:
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    out.append(float(ordered[min(rank, len(ordered)) - 1]))
  return tuple(out)


class wall_timer:
  """Context manager: ``with wall_timer() as t: ...; t.seconds / t.us``."""

  def __enter__(self):
    self._t0 = time.perf_counter()
    self.seconds = 0.0
    return self

  def __exit__(self, *exc):
    self.seconds = time.perf_counter() - self._t0
    return False

  @property
  def us(self) -> float:
    return self.seconds * 1e6
