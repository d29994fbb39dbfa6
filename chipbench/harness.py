"""Run one cell of the chip benchmark once; the pieces ``run.py`` joins.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* the cell's configuration in ``chipbench/configs/<config>.json``;
* its traffic mix in ``chipbench/traffic/<traffic>.json``, whose
  ``driver`` names the loop in ``chipbench/drivers/<driver>.py``;
* each metric's reader in ``chipbench/metrics/<metric>.py``, a module
  with ``read(record) -> float | None``.

A driver module has ``build(ctx)``, returning an object with
``setup()``, ``window(seconds) -> Window``, ``release()`` and
``compare() -> list[Compared]``.  Adding a cell, a mix or a metric adds
files and entries; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class BenchError(RuntimeError):
  """The benchmark cannot run here (no chip, missing files)."""


@dataclasses.dataclass
class Compared:
  """One number that decides ``correct``: it must be finite and at most
  ``limit``."""
  name: str
  value: float
  limit: float

  @property
  def ok(self) -> bool:
    return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Window:
  """What one measured window did, as the metric readers see it."""
  seconds: float                       # host clock, first dispatch to last result
  attempted: int                       # steps or requests due in the window
  failed: int = 0                      # requests not served ok
  latencies_ms: list | None = None     # per request, from when it was due
  lateness_ms: list | None = None      # per request, send time - due time
  served: int = 0                      # requests served ok by the close
  histograms: dict = dataclasses.field(default_factory=dict)  # obs deltas
  flops_per_step: float | None = None  # model FLOPs per step, from shapes
  op_bytes_per_step: float | None = None  # soft operator's least HBM bytes


@dataclasses.dataclass
class Record:
  """Handed to every metric reader."""
  workload: dict
  config: dict
  traffic: dict
  window: Window
  peaks: dict                          # this device kind's row of peaks.json
  trace: object = None                 # chipbench.trace.Summary or None


@dataclasses.dataclass
class Context:
  """Handed to a driver's ``build``."""
  seed: int
  workload: dict
  config: dict
  traffic: dict
  seconds: float = 0.0                 # the window's length


def load_json(*parts: str) -> dict:
  path = os.path.join(*parts)
  if not os.path.isfile(path):
    raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
  with open(path) as f:
    return json.load(f)


def load_module(kind: str, name: str):
  """``chipbench/<kind>/<name>.py`` as a module (names may hold '.' and
  '-', so they are loaded by path)."""
  path = os.path.join(HERE, kind, f"{name}.py")
  if not os.path.isfile(path):
    raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
  spec = importlib.util.spec_from_file_location(
      f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
  mod = importlib.util.module_from_spec(spec)
  sys.modules[spec.name] = mod
  spec.loader.exec_module(mod)
  return mod


def benchmark() -> dict:
  return load_json(ROOT, "BENCHMARK.json")


def resolve(spec: dict, workload: str) -> Context:
  """The cell ``workload`` with its configuration and traffic mix."""
  cells = {w["name"]: w for w in spec["workloads"]}
  if workload not in cells:
    raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                     f"have {sorted(cells)}")
  cell = cells[workload]
  configs = {c["name"]: c for c in spec["configs"]}
  config = load_json(ROOT, configs[cell["config"]]["file"])
  traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
  return Context(seed=0, workload=cell, config=config, traffic=traffic)


def _applies(metric: dict, workload: str) -> bool:
  return "workloads" not in metric or workload in metric["workloads"]


def cell_metrics(spec: dict, workload: str, trace: bool) -> list[dict]:
  """The metrics a run of ``workload`` reports: its end-to-end metrics,
  or with ``trace`` its per-layer ones (each reported only where its
  end-to-end metric is)."""
  e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
  if not trace:
    return e2e
  mine = {m["name"] for m in e2e}
  return [m for m in spec["per_layer"]
          if _applies(m, workload) and m["moves"] in mine]


def device_peaks(kind: str) -> dict:
  table = load_json(HERE, "peaks.json")["devices"]
  if kind not in table:
    raise BenchError(f"device kind {kind!r} is not in chipbench/peaks.json; "
                     f"have {sorted(table)}")
  return table[kind]


def tpu_devices(chips: int):
  """The first ``chips`` TPU devices; raises when there are fewer."""
  import jax
  try:
    devices = jax.devices()
  except RuntimeError as e:
    raise BenchError(f"JAX finds no accelerator: {e}") from None
  if devices[0].platform != "tpu":
    raise BenchError(f"JAX reports platform {devices[0].platform!r}, not "
                     f"tpu; the benchmark has no CPU fallback")
  if len(devices) < chips:
    raise BenchError(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devices)}")
  return devices[:chips]


def use_program() -> None:
  """Put the checkout's ``src`` first on the path (the system under
  test) and give it the benchmark's compile cache directory."""
  src = os.path.join(ROOT, "src")
  if not os.path.isdir(os.path.join(src, "repro")):
    raise BenchError(f"no src/repro in {ROOT}: the program is missing")
  if src not in sys.path:
    sys.path.insert(0, src)


def finite_or_none(x):
  return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def result_line(*, correct: bool, window: Window, metrics: dict,
                device: dict, compared: list[Compared],
                breakdown: dict | None = None) -> str:
  out = {"correct": correct, "attempted": window.attempted,
         "failed": window.failed, "metrics": metrics, "device": device}
  if breakdown is not None:
    out["breakdown"] = breakdown
  out["compared"] = {c.name: {"value": finite_or_none(c.value),
                              "limit": c.limit} for c in compared}
  return json.dumps(out)
