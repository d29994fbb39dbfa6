"""Device time of the ops under one named scope of the program, from a
traced window (``chipbench.trace.Summary``): every op whose ``op_name``
holds a ``repro_*`` scope that starts with the given prefix, its backward
(``transpose(jvp(...))``) and its recomputation included, and every op
whose instruction name starts with one of ``instructions``."""

from __future__ import annotations

from chipbench import trace

# The TPU compiler lowers ``lax.ragged_dot`` to custom calls named
# ``ragged-dot-*`` and gives them that name as their ``op_name``, dropping
# the scope they were traced under; in this program they are the held
# experts' grouped products.
EXPERT_PRODUCTS = ("ragged-dot",)


def seconds(summary, prefix: str, instructions=()) -> float:
  return sum(o.dur_ns for o in summary.ops
             if any(s.startswith(prefix) for s in trace.scopes(o.scope))
             or o.name.startswith(instructions)) * 1e-9


def ms_per_step(record, prefix: str, instructions=()):
  """Device ms per step of the window under ``prefix``; None where the
  window was not traced or nothing ran under it."""
  t, w = record.trace, record.window
  if t is None or not w.attempted:
    return None
  s = seconds(t, prefix, instructions)
  return 1e3 * s / w.attempted if s > 0 else None
