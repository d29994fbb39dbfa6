"""Attribution and build records: named scopes, host spans, program builds.

Three mechanisms:

* ``backend_scope`` — a ``jax.named_scope`` pushed around the backend
  forward call at *trace* time (used by ``repro.kernels.dispatch``).  The
  scope name lands on every primitive the backend emits, so jaxprs,
  lowered StableHLO and compiled-HLO ``op_name`` metadata (and therefore
  ``jax.profiler`` / XLA trace viewers) all attribute kernel time to
  ``repro_<op>_<reg>_<backend>`` instead of an anonymous soup of
  ``while``/``scatter`` ops.
* ``span`` — a host-side region of the program (a train step, a serve
  prefill, a benchmark timing loop).  It opens a
  ``jax.profiler.TraceAnnotation`` of its name, so inside a profiled
  window it sits on the trace's clock; it keeps a per-thread stack of
  open spans, so whatever starts inside one (a nested span, a program
  build) names it as its parent; and it records its duration as
  histogram ``span_s{name=...,parent=...}``.
* the build listener — installed on ``jax.monitoring`` once per process
  by ``install_build_listener`` (``repro.obs`` calls it on import).  Each
  outermost trace, lower or backend-compile event becomes one observation
  of ``build_s{phase=trace|lower|compile,program=...,span=...,
  cache=hit|miss|none}``.  An event that lies inside another build event
  on its thread (an inner jit traced inside its caller, a helper traced
  while lowering, an eager op compiled while tracing) is dropped, so the
  sum over records counts each second once.  A program built twice has a
  count of 2; a compile served by the persistent cache reads ``hit`` and
  its seconds are the cache load.

Spans and build records are written only while ``repro.obs.metrics`` is
enabled (``REPRO_METRICS``); with metrics off a span keeps only its
profiler annotation.  Names are ``[a-z0-9_]`` only: every consumer (HLO
metadata, TensorBoard trace viewer, pprof, the flattened metric keys)
treats ``/``, ``,`` and ``=`` as structure.
"""

from __future__ import annotations

import re
import threading
import time

import jax

from repro.obs import metrics

_SANITIZE = re.compile(r"[^a-z0-9_]+")


def _clean(part: str) -> str:
  return _SANITIZE.sub("_", str(part).lower()).strip("_") or "unknown"


def scope_name(op: str, regularization: str, backend: str) -> str:
  """Canonical name-stack entry for a dispatched backend call."""
  return f"repro_{_clean(op)}_{_clean(regularization)}_{_clean(backend)}"


def backend_scope(op: str, regularization: str, backend: str):
  """``jax.named_scope`` labeling every primitive a backend emits."""
  return jax.named_scope(scope_name(op, regularization, backend))


# -- host spans ---------------------------------------------------------------

_local = threading.local()


def current_span() -> str:
  """The innermost span open on this thread, or ``"none"``."""
  stack = getattr(_local, "spans", None)
  return stack[-1] if stack else "none"


class span:
  """``with span("repro_train_step") as s: ...``; ``s.seconds`` after.

  The duration is measured for the caller whether or not metrics are on;
  only the stack and the ``span_s`` record depend on ``REPRO_METRICS``.
  """

  __slots__ = ("name", "seconds", "_annotation", "_parent", "_t0")

  def __init__(self, name: str):
    self.name = _clean(name)
    self.seconds = 0.0

  def __enter__(self):
    self._annotation = jax.profiler.TraceAnnotation(self.name)
    self._annotation.__enter__()
    self._parent = None
    if metrics.enabled():
      stack = getattr(_local, "spans", None)
      if stack is None:
        stack = _local.spans = []
      self._parent = stack[-1] if stack else "none"
      stack.append(self.name)
    self._t0 = time.perf_counter()
    return self

  def __exit__(self, *exc):
    self.seconds = time.perf_counter() - self._t0
    if self._parent is not None:
      _local.spans.pop()
      metrics.observe("span_s", self.seconds, name=self.name,
                      parent=self._parent)
    self._annotation.__exit__(*exc)
    return False


# -- program builds -----------------------------------------------------------

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
# Fired inside a backend compile: the persistent cache was consulted (a
# miss unless a hit follows; JAX consults it even with no directory set,
# which is no cache), then found the executable.
_CONSULTED = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"
_PROGRAM = re.compile(r"^(?:jit|pmap)\((.*)\)$")
_LABEL = re.compile(r"(\w+)=([^,}]*)")

_installed = False


def _program_name(fun_name: str) -> str:
  """``jit(objective)`` (lower, compile) and ``objective`` (trace) both
  name program ``objective``."""
  m = _PROGRAM.match(fun_name)
  return _clean(m.group(1) if m else fun_name)


def _on_start(event: str, value, **_):
  # JAX reports the start of each timed build event as a scalar.
  if event not in _PHASES:
    return
  _local.open_builds = getattr(_local, "open_builds", 0) + 1
  if _local.open_builds == 1:
    _local.cache = "none"


def _on_cache(event: str, **_):
  if event == _CONSULTED and jax.config.jax_compilation_cache_dir:
    _local.cache = "miss"
  elif event == _HIT:
    _local.cache = "hit"


def _on_span(event: str, start: float, end: float, fun_name: str = "", **_):
  phase = _PHASES.get(event)
  if phase is None:
    return
  depth = getattr(_local, "open_builds", 0)
  _local.open_builds = max(depth - 1, 0)
  if depth > 1:                      # inside another build event
    return
  cache = getattr(_local, "cache", "none") if phase == "compile" else "none"
  metrics.observe("build_s", end - start, phase=phase,
                  program=_program_name(fun_name), span=current_span(),
                  cache=cache)


def install_build_listener() -> None:
  """Install the build listener on ``jax.monitoring`` (once per process)."""
  global _installed
  if not _installed:
    jax.monitoring.register_scalar_listener(_on_start)
    jax.monitoring.register_event_listener(_on_cache)
    jax.monitoring.register_event_time_span_listener(_on_span)
    _installed = True


def labels(key: str) -> dict[str, str]:
  """``name{k=v,...}`` -> ``{k: v}`` (a flattened registry key)."""
  return dict(_LABEL.findall(key[key.find("{"):])) if "{" in key else {}


def build_lines() -> list[str]:
  """One line per program built, from the ``build_s`` records: how many
  times it was built, seconds per phase, its compiles' cache outcomes."""
  progs: dict[str, dict] = {}
  for key, h in metrics.histograms("build_s{").items():
    lab = labels(key)
    p = progs.setdefault(lab["program"], {"trace": 0.0, "lower": 0.0,
                                          "compile": 0.0, "counts": {},
                                          "cache": {}})
    p[lab["phase"]] += h["sum"]
    p["counts"][lab["phase"]] = p["counts"].get(lab["phase"], 0) + h["count"]
    if lab["phase"] == "compile":
      p["cache"][lab["cache"]] = p["cache"].get(lab["cache"], 0) + h["count"]
  lines = []
  for name, p in sorted(progs.items()):
    cache = ", ".join(f"{n} {k}" for k, n in sorted(p["cache"].items()))
    lines.append(f"built {name} x{max(p['counts'].values())}: "
                 f"trace {p['trace']:.3f} s, lower {p['lower']:.3f} s, "
                 f"compile {p['compile']:.3f} s"
                 f"{f' ({cache})' if cache else ''}")
  return lines
