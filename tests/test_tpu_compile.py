"""Compile the main path's programs for one chip of a described TPU v5e.

Nothing runs: each case lowers a program at its real width and compiles
it for a v5e that is described, not attached, so whatever the chip's
compiler refuses fails here at no chip time.  ``python chip_smoke.py``
runs the same path on the chip itself.

The code takes its TPU branches (the comparator argsort, the serving
engine's route labels) from ``jax.default_backend()``, which answers "cpu" during such a compile, so
every case steers that answer to "tpu".  The topology is described only
inside a fixture: a module that touched the TPU library while being
imported would hold its lock in every test worker.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import core
from repro.core.losses import soft_trimmed_token_loss
from repro.kernels import dispatch
from repro.obs import metrics
from repro.serving.ops import bound_op


@pytest.fixture(scope="module")
def topo():
  from jax.experimental import topologies
  os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
  try:
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # no TPU compiler in this installation
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
  return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
  """Trace as the chip would; drop the traces again afterwards so no CPU
  test in this worker reuses a TPU-branch trace."""
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  jax.clear_caches()
  metrics.reset()
  yield
  jax.clear_caches()


def _fwd_grad(fn):
  def program(x, u):
    def objective(t):
      y = fn(t)
      return jnp.sum(y * u), y
    g, y = jax.grad(objective, has_aux=True)(x)
    return y, g
  return program


def _operator(op, reg, shape):
  eps = {"soft_rank": 4.0 / shape[-1], "soft_sort": shape[-1] / 4.0}[op]
  fn = lambda t: getattr(core, op)(t, eps, reg)
  return _fwd_grad(fn), [shape, shape], [jnp.float32, jnp.float32]


def _soft_lts(n):
  fn = jax.grad(lambda t: soft_trimmed_token_loss(t, 0.1))
  return fn, [(n,)], [jnp.float32]


def _serving_cell(rows, n):
  fn = bound_op("soft_rank/l2/desc")
  return fn, [(rows, n), (rows,), (rows,)], [jnp.float32, jnp.int32,
                                              jnp.float32]


CASES = {
    "soft_rank-l2-256x4096": lambda: _operator("soft_rank", "l2", (256, 4096)),
    "soft_rank-kl-256x4096": lambda: _operator("soft_rank", "kl", (256, 4096)),
    "soft_sort-l2-256x4096": lambda: _operator("soft_sort", "l2", (256, 4096)),
    "soft_sort-kl-256x4096": lambda: _operator("soft_sort", "kl", (256, 4096)),
    "soft_rank-l2-1x131072": lambda: _operator("soft_rank", "l2",
                                               (1, 131072)),
    "soft_lts-grad-16384": lambda: _soft_lts(16384),
    "serving-soft_rank-32x4096": lambda: _serving_cell(32, 4096),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e_on_the_default_route(case, one_chip, on_tpu):
  fn, shapes, dtypes = CASES[case]()
  structs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in zip(shapes, dtypes)]
  compiled = jax.jit(fn).lower(*structs).compile()

  # The default route holds no Pallas kernel and fits one chip.
  assert "tpu_custom_call" not in compiled.as_text()
  mem = compiled.memory_analysis()
  assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 2**30
  # It resolved as the builtin TPU rules say: dense / segscan / sortperm.
  decided = metrics.counters("plan_decide")
  want = {"forward": "dense", "backward": "segscan",
          "projection": "sortperm"}
  assert decided
  for key in decided:
    labels = dict(kv.split("=", 1)
                  for kv in key[key.index("{") + 1:-1].split(","))
    assert labels["backend"] == want[labels["kind"]], key


@pytest.mark.parametrize("shape", [(1024, 256), (1, 131072)])
def test_isotonic_solve_holds_no_gather_on_v5e(shape, one_chip, on_tpu):
  """The TPU route solves without element-wise gathers (each costs about
  10 ns an element there), and the long row's temporaries stay small."""
  spec = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
  solve = lambda y: dispatch.dispatch("isotonic", "l2", None, y)
  compiled = jax.jit(solve).lower(spec).compile()
  text = compiled.as_text()
  assert "repro_isotonic_l2_dense" in text
  assert not re.findall(r"= \S+ gather\(", text)
  if shape == (1, 131072):
    assert compiled.memory_analysis().temp_size_in_bytes <= 512 * 2**20


def _topk(shape, k):
  fn = lambda t: core.soft_topk_mask(t, k, 1.0)
  return _fwd_grad(fn), [shape, shape], [jnp.float32, jnp.float32]


# The three benchmark cells' operator programs, forward and gradient.
PERMUTATION_CASES = {
    "soft_rank-1024x256": lambda: _operator("soft_rank", "l2", (1024, 256)),
    "soft_topk_mask-8192x64-k6": lambda: _topk((8192, 64), 6),
    "soft_lts-grad-131072": lambda: _soft_lts(131072),
}


@pytest.mark.parametrize("case", sorted(PERMUTATION_CASES))
def test_projection_holds_no_permutation_gather_on_v5e(case, one_chip,
                                                       on_tpu):
  """On the TPU route every permutation is a sort that carries the data:
  the only gathers left are the backward's block-end reads (under a
  ``_bwd_`` scope), and nothing scatters (no inverse permutation)."""
  fn, shapes, dtypes = PERMUTATION_CASES[case]()
  structs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in zip(shapes, dtypes)]
  text = jax.jit(fn).lower(*structs).compile().as_text()
  assert "repro_projection_l2_sortperm" in text
  moves = [line for line in text.splitlines()
           if re.search(r"= \S+ (gather|scatter)\(", line)]
  assert moves
  for line in moves:
    op_name = re.search(r'op_name="([^"]*)"', line)
    assert op_name and "_bwd_" in op_name.group(1), line
