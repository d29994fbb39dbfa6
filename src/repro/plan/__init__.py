"""Serializable execution plans: every dispatch decision in one object.

Before this layer, "which implementation runs" was smeared across three
parallel precedence chains (forward backend, backward backend, projection
path), three environment variables, three per-call kwargs, a process
default, and two hardcoded ``auto`` cutoffs inside
``repro.kernels.dispatch``.  An :class:`ExecutionPlan` captures all of it
in one serializable, hashable object:

* an ordered table of :class:`PlanRule` entries, each mapping a
  ``(kind, op, regularization, platform, dtype, shape-bucket)`` regime to
  a concrete backend, where ``kind`` is one of ``"forward"`` (isotonic
  solver), ``"backward"`` (Lemma-2 VJP formulation) or ``"projection"``
  (fused, sortperm or composed pipeline);
* JSON round-tripping under schema ``repro.plan/v1`` with strict
  unknown-field and version-mismatch rejection, so a committed plan file
  can be trusted byte-for-byte;
* a content hash (:meth:`ExecutionPlan.plan_hash`) that BENCH artifacts
  embed so every perf row is attributable to the selection that produced
  it.

Resolution (in ``repro.kernels.dispatch``) walks a single chain for all
three decision kinds::

    explicit argument  >  environment variable  >  active plan
                       >  packaged default plan  >  built-in plan

The *active* plan is installed per-process (:func:`set_active_plan`, the
``--plan plan.json`` launch flag) or per-scope (:func:`use_plan`); the
*packaged default plan* is ``src/repro/plan/default_plan.json``, emitted
by ``tools/autotune.py`` from measured ``BENCH_*.json`` sweeps (every
rule carries the timing-row names that justify it — validated in CI by
``tools/check_backends.py --plan``); the *built-in* plan is the
shape-oblivious safety net (TPU -> dense, small-n -> minimax under a
memory cap, otherwise scan; segscan backward; TPU -> sortperm projection,
fused elsewhere) and is total — some rule always matches.

This module is deliberately light: stdlib + ``repro.obs.metrics`` only
(no jax), so tools can load and validate plans without pulling in the
accelerator stack.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
from typing import Iterable

SCHEMA_VERSION = "repro.plan/v1"

KINDS = ("forward", "backward", "projection")

# Shape-regime constants for the built-in plan (formerly hardcoded in
# repro.kernels.dispatch as AUTO_MINIMAX_MAX_N / AUTO_MINIMAX_MAX_ELEMS):
# n at or below which the O(n^2) closed form is allowed to win, and the
# rows * n^2 f32-element cap (~64 MB) past which it must not be picked
# regardless of n (the large-flattened-batch MoE-router regime).
BUILTIN_MINIMAX_MAX_N = 64
BUILTIN_MINIMAX_MAX_ELEMS = 16_000_000

_RULE_FIELDS = ("kind", "backend", "op", "regularization", "platform",
                "dtype", "min_n", "max_n", "min_rows", "max_rows",
                "max_elems", "evidence")
_PLAN_FIELDS = ("schema", "name", "rules", "meta")


@dataclasses.dataclass(frozen=True)
class PlanRule:
  """One regime -> backend entry of an execution plan.

  A rule *matches* a decision query when every constraint holds; ``"*"``
  (the default for the categorical keys) matches anything.  The shape
  bucket is expressed as optional inclusive bounds on ``n`` (last-axis
  problem size), ``rows`` (flattened batch rows) and ``rows * n^2``
  (``max_elems``, the minimax memory bill).  A rule with any shape
  constraint never matches a shapeless query — so a plan can never route
  an unknown-size problem to a size-gated backend (the old
  shape=None -> minimax bug class is unrepresentable).
  """

  kind: str
  backend: str
  op: str = "*"
  regularization: str = "*"
  platform: str = "*"
  dtype: str = "*"
  min_n: int | None = None
  max_n: int | None = None
  min_rows: int | None = None
  max_rows: int | None = None
  max_elems: int | None = None
  evidence: tuple[str, ...] = ()

  def __post_init__(self):
    if self.kind not in KINDS:
      raise ValueError(f"rule kind must be one of {KINDS}, got {self.kind!r}")
    if not self.backend or not isinstance(self.backend, str):
      raise ValueError(f"rule backend must be a non-empty string, "
                       f"got {self.backend!r}")
    object.__setattr__(self, "evidence", tuple(self.evidence))

  def shape_constrained(self) -> bool:
    return any(v is not None for v in (self.min_n, self.max_n,
                                       self.min_rows, self.max_rows,
                                       self.max_elems))

  def matches(self, kind: str, op: str, regularization: str, *,
              platform: str, dtype: str,
              shape: tuple[int, ...] | None) -> bool:
    if self.kind != kind:
      return False
    for want, have in ((self.op, op), (self.regularization, regularization),
                       (self.platform, platform), (self.dtype, dtype)):
      if want != "*" and have is not None and want != have:
        return False
    if not self.shape_constrained():
      return True
    if shape is None:
      # Unknown shape must not satisfy a size-gated rule.
      return False
    n = shape[-1]
    rows = 1
    for d in shape[:-1]:
      rows *= d
    if self.min_n is not None and n < self.min_n:
      return False
    if self.max_n is not None and n > self.max_n:
      return False
    if self.min_rows is not None and rows < self.min_rows:
      return False
    if self.max_rows is not None and rows > self.max_rows:
      return False
    if self.max_elems is not None and rows * n * n > self.max_elems:
      return False
    return True

  def to_dict(self) -> dict:
    out = {"kind": self.kind, "backend": self.backend}
    for k in ("op", "regularization", "platform", "dtype"):
      v = getattr(self, k)
      if v != "*":
        out[k] = v
    for k in ("min_n", "max_n", "min_rows", "max_rows", "max_elems"):
      v = getattr(self, k)
      if v is not None:
        out[k] = v
    if self.evidence:
      out["evidence"] = list(self.evidence)
    return out

  @classmethod
  def from_dict(cls, d: dict) -> "PlanRule":
    if not isinstance(d, dict):
      raise ValueError(f"plan rule must be an object, got {type(d).__name__}")
    unknown = sorted(set(d) - set(_RULE_FIELDS))
    if unknown:
      raise ValueError(f"plan rule has unknown field(s) {unknown}; "
                       f"known fields: {sorted(_RULE_FIELDS)}")
    for k in ("kind", "backend"):
      if k not in d:
        raise ValueError(f"plan rule missing required field {k!r}")
    kwargs = dict(d)
    if "evidence" in kwargs:
      ev = kwargs["evidence"]
      if (not isinstance(ev, (list, tuple))
          or not all(isinstance(e, str) for e in ev)):
        raise ValueError("plan rule 'evidence' must be a list of strings")
      kwargs["evidence"] = tuple(ev)
    return cls(**kwargs)


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
  """An ordered, serializable backend-selection table (first match wins).

  Hashable (``meta`` is excluded from equality/hash), so a plan can ride
  through ``jax.custom_vjp`` non-differentiable arguments and jit static
  arguments without ceremony.
  """

  name: str = "unnamed"
  rules: tuple[PlanRule, ...] = ()
  meta: dict = dataclasses.field(default_factory=dict, compare=False)

  def __post_init__(self):
    object.__setattr__(self, "rules", tuple(self.rules))

  def decide(self, kind: str, op: str, regularization: str, *,
             platform: str, dtype: str = "*",
             shape: tuple[int, ...] | None = None) -> PlanRule | None:
    """First rule matching the query, or None when the plan is silent."""
    for rule in self.rules:
      if rule.matches(kind, op, regularization, platform=platform,
                      dtype=dtype, shape=shape):
        return rule
    return None

  # -- serialization --------------------------------------------------------

  def to_dict(self) -> dict:
    return {
        "schema": SCHEMA_VERSION,
        "name": self.name,
        "rules": [r.to_dict() for r in self.rules],
        "meta": dict(self.meta),
    }

  def to_json(self, indent: int | None = 2) -> str:
    return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

  @classmethod
  def from_dict(cls, d: dict) -> "ExecutionPlan":
    if not isinstance(d, dict):
      raise ValueError(f"plan must be an object, got {type(d).__name__}")
    schema = d.get("schema")
    if schema != SCHEMA_VERSION:
      raise ValueError(f"plan schema mismatch: expected {SCHEMA_VERSION!r}, "
                       f"got {schema!r}")
    unknown = sorted(set(d) - set(_PLAN_FIELDS))
    if unknown:
      raise ValueError(f"plan has unknown field(s) {unknown}; "
                       f"known fields: {sorted(_PLAN_FIELDS)}")
    rules = d.get("rules", [])
    if not isinstance(rules, list):
      raise ValueError("plan 'rules' must be a list")
    meta = d.get("meta", {})
    if not isinstance(meta, dict):
      raise ValueError("plan 'meta' must be an object")
    return cls(name=d.get("name", "unnamed"),
               rules=tuple(PlanRule.from_dict(r) for r in rules),
               meta=dict(meta))

  @classmethod
  def from_json(cls, text: str) -> "ExecutionPlan":
    try:
      d = json.loads(text)
    except json.JSONDecodeError as e:
      raise ValueError(f"plan is not valid JSON: {e}") from e
    return cls.from_dict(d)

  def save(self, path: str) -> None:
    with open(path, "w") as f:
      f.write(self.to_json())
      f.write("\n")

  def plan_hash(self) -> str:
    """Content hash over (schema, name, rules) — stable across re-emits
    with identical decisions (``meta`` provenance is excluded)."""
    canonical = json.dumps(
        {"schema": SCHEMA_VERSION, "name": self.name,
         "rules": [r.to_dict() for r in self.rules]},
        sort_keys=True, separators=(",", ":"))
    return hashlib.sha1(canonical.encode()).hexdigest()[:12]


def load_plan(path: str) -> ExecutionPlan:
  """Load and strictly validate a plan file (raises ValueError on any
  schema/shape problem, OSError if unreadable)."""
  with open(path) as f:
    return ExecutionPlan.from_json(f.read())


# ---------------------------------------------------------------------------
# Built-in plan: the shape-oblivious safety net (total coverage).
# ---------------------------------------------------------------------------


def builtin_plan() -> ExecutionPlan:
  """The constants-derived fallback plan, matching every possible query.

  TPU -> the gather-free ``dense`` divide-and-conquer PAV at every shape
  (element-wise gathers are slow there; the ``pallas`` PAV kernel does
  not compile for v5e and is reachable only through an explicit
  ``impl="pallas"``); off-TPU the O(n^2) ``minimax`` closed form
  for small n under its memory cap, ``scan`` otherwise (including all
  shapeless queries); ``segscan`` backward; the ``sortperm`` projection
  on TPU (every permutation a sort carrying the data: gathers are slow
  there, sorts are not), ``fused`` elsewhere.
  """
  return _BUILTIN


_BUILTIN = ExecutionPlan(
    name="builtin",
    rules=(
        PlanRule("forward", "dense", op="isotonic", platform="tpu"),
        PlanRule("forward", "minimax", op="isotonic",
                 max_n=BUILTIN_MINIMAX_MAX_N,
                 max_elems=BUILTIN_MINIMAX_MAX_ELEMS),
        PlanRule("forward", "scan", op="isotonic"),
        PlanRule("backward", "segscan"),
        PlanRule("projection", "sortperm", op="projection", platform="tpu"),
        PlanRule("projection", "fused", op="projection"),
    ),
)


# ---------------------------------------------------------------------------
# Packaged default plan (emitted by tools/autotune.py, committed).
# ---------------------------------------------------------------------------

DEFAULT_PLAN_PATH = os.path.join(os.path.dirname(__file__),
                                 "default_plan.json")

_default_cache: list = []  # [plan-or-None] once loaded


def default_plan() -> ExecutionPlan | None:
  """The committed autotuned plan, or None when absent/invalid.

  Loaded once per process; a missing or unparsable file silently falls
  back to :func:`builtin_plan` at resolution time (CI separately *fails*
  on an invalid committed plan via ``tools/check_backends.py --plan`` —
  runtime just refuses to crash the import path over it).
  """
  if not _default_cache:
    try:
      _default_cache.append(load_plan(DEFAULT_PLAN_PATH))
    except (OSError, ValueError):
      _default_cache.append(None)
  return _default_cache[0]


def invalidate_default_plan_cache() -> None:
  """Forget the cached packaged plan (tests / after re-autotuning)."""
  _default_cache.clear()


# ---------------------------------------------------------------------------
# Active plan: process-wide slot + scoped override.
# ---------------------------------------------------------------------------

_ACTIVE: list[ExecutionPlan | None] = [None]


def get_active_plan() -> ExecutionPlan | None:
  return _ACTIVE[0]


def set_active_plan(plan: ExecutionPlan | None) -> None:
  """Install ``plan`` as the process-wide active plan (None clears it).

  This is what ``launch/{train,serve}.py --plan plan.json`` calls; plan
  consultation happens at Python trace time, so an installed plan governs
  everything traced afterwards.
  """
  if plan is not None and not isinstance(plan, ExecutionPlan):
    raise TypeError(f"expected ExecutionPlan or None, got {type(plan)}")
  _ACTIVE[0] = plan


@contextlib.contextmanager
def use_plan(plan: ExecutionPlan | None):
  """Scoped :func:`set_active_plan` (trace-time only: like the old
  ``use_backend``, lazily-traced custom_vjp rules may fire after the
  scope exits — pass ``plan=`` / ``impl=`` explicitly under jit)."""
  prev = _ACTIVE[0]
  set_active_plan(plan)
  try:
    yield
  finally:
    _ACTIVE[0] = prev


def resolve_via_plans(
    kind: str, op: str, regularization: str, *, platform: str,
    dtype: str = "*", shape: tuple[int, ...] | None = None,
    plan: ExecutionPlan | None = None,
) -> tuple[str, str, PlanRule]:
  """Walk the plan chain for one decision: (backend, source, rule).

  Chain: the explicit per-call ``plan`` (else the active plan, source
  ``"plan"``) > the packaged default plan (``"default_plan"``) > the
  built-in plan (``"builtin"``).  The built-in plan is total, so this
  always returns.
  """
  chain: Iterable[tuple[str, ExecutionPlan | None]] = (
      ("plan", plan if plan is not None else get_active_plan()),
      ("default_plan", default_plan()),
      ("builtin", builtin_plan()),
  )
  for source, candidate in chain:
    if candidate is None:
      continue
    rule = candidate.decide(kind, op, regularization, platform=platform,
                            dtype=dtype, shape=shape)
    if rule is not None:
      _plan_decide_note(kind, rule.backend, source, candidate.name)
      return rule.backend, source, rule
  raise AssertionError(
      f"builtin plan failed to cover kind={kind!r} op={op!r} "
      f"regularization={regularization!r} platform={platform!r}")


def _plan_decide_note(kind: str, backend: str, source: str,
                      plan_name: str) -> None:
  from repro.obs import metrics as _metrics  # lazy: keep import cheap
  _metrics.counter_inc("plan_decide", kind=kind, backend=backend,
                       source=source, plan=plan_name)


def _governing_plans(plan: ExecutionPlan | None) -> tuple[ExecutionPlan, ...]:
  """The plan chain as it would be consulted right now, most-specific
  first (explicit/active > packaged default > builtin), Nones dropped."""
  chain = (plan if plan is not None else get_active_plan(),
           default_plan(), builtin_plan())
  return tuple(p for p in chain if p is not None)


def shape_breakpoints(plan: ExecutionPlan | None = None, *,
                      platform: str) -> tuple[int, ...]:
  """Sorted unique n-edges at which some rule's applicability flips.

  For every shape-constrained rule in the governing plan chain that can
  match on ``platform``, the inclusive bounds ``max_n`` and ``min_n - 1``
  are bucket edges: a serving bucket whose width crosses one would pad
  requests from one backend regime into another.
  ``repro.serving.BucketPolicy.from_plan`` splices these into its size
  ladder.
  """
  edges: set[int] = set()
  for candidate in _governing_plans(plan):
    for rule in candidate.rules:
      if rule.platform not in ("*", platform):
        continue
      if rule.max_n is not None:
        edges.add(rule.max_n)
      if rule.min_n is not None and rule.min_n > 1:
        edges.add(rule.min_n - 1)
  return tuple(sorted(e for e in edges if e >= 1))


def resolve_grid(
    kind: str,
    ops: Iterable[str],
    regularizations: Iterable[str],
    shapes: Iterable[tuple[int, ...]],
    *,
    platform: str,
    dtype: str = "*",
    plan: ExecutionPlan | None = None,
) -> list[dict]:
  """Enumerate plan decisions over an (op x regularization x shape) grid.

  The serving engine's warmup uses this to know, ahead of any traffic,
  which backend each AOT-compiled bucket will embed — one entry per grid
  cell: ``{kind, op, regularization, shape, backend, source, plan}``.
  Unlike :func:`resolve_via_plans` this never records ``plan_decide``
  counters (it is an enumeration, not a dispatch decision).
  """
  shapes = [tuple(s) for s in shapes]
  out: list[dict] = []
  for op in ops:
    for reg in regularizations:
      for shape in shapes:
        for source_name, candidate in (
            ("plan", plan if plan is not None else get_active_plan()),
            ("default_plan", default_plan()),
            ("builtin", builtin_plan())):
          if candidate is None:
            continue
          rule = candidate.decide(kind, op, reg, platform=platform,
                                  dtype=dtype, shape=shape)
          if rule is not None:
            out.append({"kind": kind, "op": op, "regularization": reg,
                        "shape": shape, "backend": rule.backend,
                        "source": source_name, "plan": candidate.name})
            break
  return out


def plan_provenance(plan: ExecutionPlan | None = None) -> dict:
  """Attribution block for BENCH artifact ``meta``: which plan governs
  dispatch right now (explicit > active > packaged default > builtin)
  and its content hash, so perf rows are attributable to the selection
  that produced them."""
  for source, candidate in (
      ("arg", plan), ("plan", get_active_plan()),
      ("default_plan", default_plan()), ("builtin", builtin_plan())):
    if candidate is not None:
      return {"plan_name": candidate.name,
              "plan_hash": candidate.plan_hash(),
              "plan_source": source}
  raise AssertionError("builtin plan is always available")


__all__ = [
    "SCHEMA_VERSION",
    "KINDS",
    "BUILTIN_MINIMAX_MAX_N",
    "BUILTIN_MINIMAX_MAX_ELEMS",
    "DEFAULT_PLAN_PATH",
    "PlanRule",
    "ExecutionPlan",
    "load_plan",
    "builtin_plan",
    "default_plan",
    "invalidate_default_plan_cache",
    "get_active_plan",
    "set_active_plan",
    "use_plan",
    "resolve_via_plans",
    "resolve_grid",
    "shape_breakpoints",
    "plan_provenance",
]
