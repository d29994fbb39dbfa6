"""Host spans and program build records (``repro.obs.tracing``), and the
benchmark's two set-up metrics that read them."""

import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.obs import metrics, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


@pytest.fixture(autouse=True)
def clean_registry():
  metrics.set_enabled(True)
  metrics.reset()
  yield
  metrics.set_enabled(None)
  metrics.reset()


def records(prefix="build_s{"):
  """``{(phase, program, span, cache): count}`` of the build records."""
  out = {}
  for key, h in metrics.histograms(prefix).items():
    lab = tracing.labels(key)
    out[lab["phase"], lab["program"], lab["span"], lab["cache"]] = h["count"]
  return out


def of(program):
  return {k: n for k, n in records().items() if k[1] == program}


# -- spans --------------------------------------------------------------------


def test_span_records_its_duration_and_parent():
  with tracing.span("repro_outer") as outer:
    with tracing.span("repro_inner") as inner:
      assert tracing.current_span() == "repro_inner"
    assert tracing.current_span() == "repro_outer"
  assert tracing.current_span() == "none"
  h = metrics.histograms("span_s")
  assert set(h) == {"span_s{name=repro_outer,parent=none}",
                    "span_s{name=repro_inner,parent=repro_outer}"}
  assert h["span_s{name=repro_inner,parent=repro_outer}"]["sum"] == \
      inner.seconds
  assert 0 < inner.seconds <= outer.seconds


def test_span_and_builds_record_nothing_with_metrics_off(monkeypatch):
  metrics.set_enabled(None)
  monkeypatch.setenv("REPRO_METRICS", "0")
  with tracing.span("repro_off") as s:
    assert tracing.current_span() == "none"
    jax.jit(lambda x: x * 5.0 - 1.0)(np.ones(5, np.float32))
  assert s.seconds > 0          # still measured for the caller
  assert metrics.snapshot()["histograms"] == {}


def test_span_is_a_host_event_in_the_profiler_trace(tmp_path):
  jax.profiler.start_trace(str(tmp_path))
  try:
    with tracing.span("repro_traced_region"):
      jax.block_until_ready(jnp.arange(8.0) * 2)
  finally:
    jax.profiler.stop_trace()
  path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
  data = jax.profiler.ProfileData.from_file(path)
  names = {e.name for plane in data.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events}
  assert "repro_traced_region" in names


# -- build records ------------------------------------------------------------


def test_nested_jit_gives_one_trace_record():
  @jax.jit
  def nested_inner(x):
    return jnp.sin(x) * 2

  def nested_outer(x):
    return jnp.sum(nested_inner(x) ** 2)

  jax.jit(jax.value_and_grad(nested_outer))(np.ones(3, np.float32))
  got = records()
  assert [k for k in got if k[0] == "trace"] == [
      ("trace", "nested_outer", "none", "none")]
  assert got["trace", "nested_outer", "none", "none"] == 1
  assert {k[:2] for k in got} == {("trace", "nested_outer"),
                                  ("lower", "nested_outer"),
                                  ("compile", "nested_outer")}


def test_shape_change_builds_the_program_again():
  f = jax.jit(lambda x: jnp.cumsum(x) * 3.0)
  with tracing.span("repro_build_twice"):
    f(np.ones(4, np.float32))
    f(np.ones(4, np.float32))           # same shape: no build
    f(np.ones(6, np.float32))
  got = of("lambda")
  for phase in ("trace", "lower", "compile"):
    assert got[phase, "lambda", "repro_build_twice", "none"] == 2
  line, = tracing.build_lines()
  assert line.startswith("built lambda x2: trace ") and "(2 none)" in line


def test_persistent_cache_hit_on_rebuild(tmp_path):
  names = ("jax_compilation_cache_dir",
           "jax_persistent_cache_min_compile_time_secs",
           "jax_persistent_cache_min_entry_size_bytes")
  saved = {name: getattr(jax.config, name) for name in names}

  def build():
    def cached_prog(x):                 # a new function: traced anew
      return jnp.tanh(x) @ x.T
    jax.jit(cached_prog)(np.ones((4, 4), np.float32))

  try:
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    build()
    jax.clear_caches()
    build()
  finally:
    for name, value in saved.items():
      jax.config.update(name, value)
    compilation_cache.reset_cache()
  got = of("cached_prog")
  assert got["compile", "cached_prog", "none", "miss"] == 1
  assert got["compile", "cached_prog", "none", "hit"] == 1
  assert got["trace", "cached_prog", "none", "none"] == 2


# -- the trainer --------------------------------------------------------------


def test_trainer_step_times_are_its_spans(capsys):
  import dataclasses

  from repro.configs.smoke import smoke_config
  from repro.launch.train import Trainer
  from repro.optim import adamw
  cfg = dataclasses.replace(smoke_config("llama3.2-1b"), num_layers=1)
  trainer = Trainer(cfg, adamw.AdamWConfig(), batch=2, seq=16,
                    ckpt_dir=None, total_steps=10)
  state = trainer.init_or_restore()
  metrics.reset()
  trainer.run(state, 3)
  h = metrics.histograms("span_s")["span_s{name=repro_train_step,parent=none}"]
  assert h["count"] == len(trainer._step_times) == 3
  assert h["sum"] == pytest.approx(sum(trainer._step_times))
  assert not metrics.histograms("train_step_us")
  built = [k for k in records() if k[2] == "repro_train_step"]
  assert ("compile", "train_step", "repro_train_step", "none") in built
  out = capsys.readouterr().out
  assert out.count("[build] built train_step x1: ") == 1


# -- the benchmark's set-up metrics ---------------------------------------------


@pytest.mark.parametrize("workload", ["rank-loss.mslr", "lts-loss.stablelm-3b",
                                      "lts-step.stablelm-3b"])
def test_traced_run_reports_build_metrics(workload):
  from chipbench.tests import small
  got = small.run_small(workload, trace=1)["metrics"]
  build = got["build_s.setup"]["value"]
  trace_lower = got["trace_lower_s.setup"]["value"]
  assert 0 < trace_lower <= build
  mine = {k: h for k, h in metrics.histograms("build_s{").items()}
  if workload.startswith("lts-step"):
    # The benchmark's own jits and the reference build outside the step.
    inside = sum(h["sum"] for k, h in mine.items()
                 if tracing.labels(k)["span"] == "repro_train_step")
    outside = [k for k in mine
               if tracing.labels(k)["span"] != "repro_train_step"]
    assert outside
    assert build == pytest.approx(inside)
  else:
    assert build == pytest.approx(sum(h["sum"] for h in mine.values()))


def test_build_readers_read_nothing_without_records():
  from chipbench import harness
  from chipbench.harness import Record, Window
  for traffic in ({"driver": "loss_loop"}, {"driver": "train_loop"}):
    record = Record(workload={}, config={}, traffic=traffic,
                    window=Window(seconds=1.0, attempted=1), peaks={})
    for name in ("build_s.setup", "trace_lower_s.setup"):
      assert harness.load_module("metrics", name).read(record) is None
