"""Backend dispatch for the batched isotonic/projection stack.

Single choke point through which every soft-sort/rank pass routes: a
*forward* registry mapping ``(op, regularization, backend)`` ->
implementation, and a *backward* registry mapping
``(op, regularization, backward_backend)`` -> VJP implementation.  All
registered implementations share the same contract — they take f32-safe
arrays whose *last* axis is the problem dimension, flattened here to
``(rows, n)``, and return the same shape.  The promote-compute-demote
dtype contract is enforced *here*, uniformly: half-precision floating
inputs (bf16/f16) are promoted to f32 before any backend sees them and
the result is cast back, for every backend and both directions — no
backend carries its own casting wrapper.

Forward backends
----------------
* ``"lax"``      reference ``lax.fori_loop`` stack machine, natively batched
                 (``repro.kernels.pav.pav_l2_lax`` / ``pav_kl_lax``);
                 O(n) work per row but O(n) *sequential depth*.
* ``"scan"``     divide-and-conquer PAV (``repro.kernels.pav_scan``):
                 log2(n) vectorized merge levels — O(n log n) work at
                 O(log n) depth, the paper's complexity claim realized on
                 depth-dominated hardware (CPU/GPU).
* ``"dense"``    the same divide-and-conquer PAV with no element-wise
                 gather (``repro.kernels.pav_dense``), the same merges as
                 ``scan``: the TPU route, where gathers are slow.
* ``"pallas"``   tiled kernel (``repro.kernels.pav``); interpret mode
                 off-TPU.  It does not compile for TPU v5e, so no plan
                 routes to it: only an explicit ``impl="pallas"`` does.
* ``"minimax"``  O(n^2) vectorized closed form (``repro.kernels.ref``) with
                 zero data-dependent control flow — the right trade for
                 small n and under SPMD.
* ``"auto"``     defers the choice to the execution-plan chain (below).

Backward backends
-----------------
The exact O(n) segment-algebra VJP (paper Lemma 2) has two registered
formulations (``repro.kernels.segment_vjp``): ``"segscan"`` (default;
segmented prefix scans + block-end gathers, scatter-free) and
``"scatter"`` (the original ``segment_sum`` over globally-offset ids).

Selection: ONE precedence chain for all three decision kinds (forward
backend, backward backend, projection path)::

    explicit argument (``impl=`` / ``backend=`` / ``path=``)
      > environment (REPRO_BACKEND / REPRO_BACKWARD / REPRO_PROJECTION)
      > execution plan (per-call ``plan=`` or the active ``use_plan`` /
        ``set_active_plan`` plan)
      > packaged default plan (src/repro/plan/default_plan.json,
        emitted by tools/autotune.py from measured BENCH sweeps)
      > built-in plan (repro.plan.builtin_plan: TPU -> dense, small-n
        minimax under a memory cap, scan otherwise; segscan; TPU ->
        sortperm, fused otherwise)

``"auto"`` — as an argument or environment value — means "fall through
to the plan chain".  Resolution is deterministic given (request,
environment, plans, platform, dtype, shape): the same inputs always pick
the same implementation, so a jit cache entry never flips backends
between traces.  The legacy ``use_backend`` / ``use_backward`` /
``set_default_backend`` entry points survive as thin shims that install
an overriding rule on the active plan.

Observability: every resolution and every dispatched call (forward and
backward) is recorded into ``repro.obs.metrics`` (counters keyed by
``(op, regularization, backend)``, shape buckets, per-plan decision
counters ``plan_decide{kind,backend,source,plan}``, and bounded
trace-cache hit/miss/eviction counts), and every backend call runs under
a ``jax.named_scope`` so kernels are attributable in jaxprs / HLO
metadata / ``jax.profiler`` traces.  All of this happens at Python trace
time only, and is a no-op when metrics are disabled (``REPRO_METRICS=0``).
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Callable

import jax
import jax.numpy as jnp

from repro import plan as _plan
from repro.obs import metrics as _metrics
from repro.obs import tracing as _tracing

Array = jax.Array
ExecutionPlan = _plan.ExecutionPlan

ENV_VAR = "REPRO_BACKEND"
BWD_ENV_VAR = "REPRO_BACKWARD"
PROJECTION_ENV_VAR = "REPRO_PROJECTION"

BACKENDS = ("auto", "lax", "scan", "dense", "pallas", "minimax")
BWD_BACKENDS = ("auto", "segscan", "scatter")
PROJECTION_PATHS = ("auto", "fused", "sortperm", "composed")

# Backwards-compatible aliases for the (former) hardcoded auto cutoffs;
# the authoritative values now live in the built-in plan
# (repro.plan.builtin_plan) as ordinary shape-bucket rule entries.
AUTO_MINIMAX_MAX_N = _plan.BUILTIN_MINIMAX_MAX_N
AUTO_MINIMAX_MAX_ELEMS = _plan.BUILTIN_MINIMAX_MAX_ELEMS

_REGISTRY: dict[tuple[str, str, str], Callable[..., Array]] = {}
_BWD_REGISTRY: dict[tuple[str, str, str], Callable[..., tuple]] = {}

# One spec per decision kind: env var, allowed request values, and the
# metrics counter each resolution records under.
_KIND_SPECS = {
    "forward": (ENV_VAR, BACKENDS, "dispatch_resolve"),
    "backward": (BWD_ENV_VAR, BWD_BACKENDS, "dispatch_bwd_resolve"),
    "projection": (PROJECTION_ENV_VAR, PROJECTION_PATHS,
                   "projection_resolve"),
}

_HALF_DTYPES = (jnp.bfloat16, jnp.float16)


def register(op: str, regularization: str, backend: str):
  """Decorator: register ``fn`` as the (op, regularization, backend) impl."""

  def deco(fn: Callable[..., Array]) -> Callable[..., Array]:
    _REGISTRY[(op, regularization, backend)] = fn
    return fn

  return deco


def register_backward(op: str, regularization: str, backend: str):
  """Decorator: register a VJP impl under (op, regularization, backend)."""

  def deco(fn: Callable[..., tuple]) -> Callable[..., tuple]:
    _BWD_REGISTRY[(op, regularization, backend)] = fn
    return fn

  return deco


def registered_backends(op: str, regularization: str) -> tuple[str, ...]:
  """Concrete (non-auto) backends registered for an (op, regularization)."""
  return tuple(b for (o, r, b) in _REGISTRY
               if o == op and r == regularization)


def registered_backward_backends(
    op: str, regularization: str) -> tuple[str, ...]:
  """Concrete backward backends registered for an (op, regularization)."""
  return tuple(b for (o, r, b) in _BWD_REGISTRY
               if o == op and r == regularization)


# ---------------------------------------------------------------------------
# Plan-based selection state + legacy shims.
# ---------------------------------------------------------------------------

# Re-exported so callers can keep importing selection tools from the
# dispatch choke point.
use_plan = _plan.use_plan
set_active_plan = _plan.set_active_plan
get_active_plan = _plan.get_active_plan
load_plan = _plan.load_plan


def _override_plan(kind: str, backend: str) -> ExecutionPlan:
  """Active plan with an unconditional ``kind -> backend`` rule prepended
  (``"auto"`` instead *removes* any unconditional override of that kind,
  restoring fall-through to the default plans)."""
  base = _plan.get_active_plan()
  base_rules = base.rules if base is not None else ()
  if backend == "auto":
    rules = tuple(r for r in base_rules
                  if not (r.kind == kind and not r.shape_constrained()
                          and r.op == "*" and r.regularization == "*"
                          and r.platform == "*" and r.dtype == "*"))
  else:
    rules = (_plan.PlanRule(kind, backend),) + tuple(base_rules)
  name = f"{base.name if base is not None else 'override'}+{kind}={backend}"
  return ExecutionPlan(name=name, rules=rules)


def _unconditional_choice(kind: str) -> str:
  """Backend of the first fully-unconditional active-plan rule of ``kind``
  (the legacy 'process default'), or ``"auto"`` when none is installed."""
  base = _plan.get_active_plan()
  for r in (base.rules if base is not None else ()):
    if (r.kind == kind and not r.shape_constrained() and r.op == "*"
        and r.regularization == "*" and r.platform == "*"
        and r.dtype == "*"):
      return r.backend
  return "auto"


def get_default_backend() -> str:
  """Deprecated shim: the active plan's unconditional forward override."""
  return _unconditional_choice("forward")


def set_default_backend(backend: str) -> None:
  """Deprecated shim over ``set_active_plan``: installs an unconditional
  forward-backend rule on the active plan (``"auto"`` removes it)."""
  if backend not in BACKENDS:
    raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
  _plan.set_active_plan(_override_plan("forward", backend))


@contextlib.contextmanager
def use_backend(backend: str):
  """Deprecated shim over ``use_plan``: scoped unconditional forward rule
  (trace-time only: custom_vjp fwd rules are traced lazily, so pass
  ``backend=`` explicitly under jit)."""
  if backend not in BACKENDS:
    raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
  with _plan.use_plan(_override_plan("forward", backend)):
    yield


def get_default_backward() -> str:
  """Deprecated shim: the active plan's unconditional backward override."""
  return _unconditional_choice("backward")


def set_default_backward(backend: str) -> None:
  """Deprecated shim: unconditional backward rule on the active plan."""
  if backend not in BWD_BACKENDS:
    raise ValueError(
        f"backward backend must be one of {BWD_BACKENDS}, got {backend!r}")
  _plan.set_active_plan(_override_plan("backward", backend))


@contextlib.contextmanager
def use_backward(backend: str):
  """Deprecated shim over ``use_plan`` for the backward (VJP) formulation
  (trace-time only: like ``use_backend``, custom_vjp bwd rules are traced
  lazily under jit — eager/top-level ``jax.grad`` calls are the reliable
  use)."""
  if backend not in BWD_BACKENDS:
    raise ValueError(
        f"backward backend must be one of {BWD_BACKENDS}, got {backend!r}")
  with _plan.use_plan(_override_plan("backward", backend)):
    yield


def _env_choice(env_var: str, allowed: tuple[str, ...]) -> str | None:
  """Validated environment backend value, or None when unset/empty.

  Validated at read time: an unknown value would otherwise surface much
  later as a confusing registry KeyError deep inside a traced call.
  """
  raw = os.environ.get(env_var)
  if not raw:
    return None
  if raw not in allowed:
    raise ValueError(
        f"{env_var}={raw!r} is not a known backend; "
        f"expected one of {allowed}")
  return raw


def resolve(
    kind: str,
    op: str,
    regularization: str,
    request: str | None = None,
    *,
    shape: tuple[int, ...] | None = None,
    platform: str | None = None,
    dtype: str | None = None,
    plan: ExecutionPlan | None = None,
) -> str:
  """THE precedence chain, shared by all three decision kinds.

  ``explicit request > environment > plan (arg/active) > packaged
  default plan > built-in plan``; a request or environment value of
  ``"auto"`` falls through to the plan chain.  Deterministic given its
  inputs, so a jit cache entry never flips backends between traces.
  """
  env_var, allowed, counter = _KIND_SPECS[kind]
  if request and request != "auto":
    if request not in allowed:
      # Tolerate registered-but-unlisted names (an out-of-tree backend
      # registered via ``register``): the registry check below is the
      # real gate; ``allowed`` only vets the built-in spelling set.
      known = _registered_for(kind, op, regularization)
      if request not in known:
        raise ValueError(
            f"no {kind} backend {request!r} for op={op!r}, "
            f"regularization={regularization!r}; have {known}")
    b, source = request, "arg"
  else:
    env = _env_choice(env_var, allowed)
    if env and env != "auto":
      b, source = env, "env"
    else:
      platform = platform or jax.default_backend()
      b, source, _ = _plan.resolve_via_plans(
          kind, op, regularization, platform=platform,
          dtype=dtype or "*", shape=shape, plan=plan)
  _check_registered(kind, op, regularization, b)
  _metrics.counter_inc(counter, op=op, regularization=regularization,
                       backend=b, source=source)
  return b


def _registered_for(kind: str, op: str,
                    regularization: str) -> tuple[str, ...]:
  if kind == "backward":
    return registered_backward_backends(op, regularization)
  return registered_backends(op, regularization)


def _check_registered(kind: str, op: str, regularization: str,
                      backend: str) -> None:
  if kind == "projection":
    # The projection registry is populated on repro.core.projection
    # import; dispatch_projection does its own lookup with a pointer to
    # that import, and reg-less queries (bench meta) have no key to check.
    return
  reg_map = _BWD_REGISTRY if kind == "backward" else _REGISTRY
  if (op, regularization, backend) not in reg_map:
    raise ValueError(
        f"no {kind} backend {backend!r} registered for op={op!r}, "
        f"regularization={regularization!r}; have "
        f"{_registered_for(kind, op, regularization)}")


def resolve_backend(
    op: str,
    regularization: str,
    backend: str | None = None,
    *,
    shape: tuple[int, ...] | None = None,
    platform: str | None = None,
    dtype: str | None = None,
    plan: ExecutionPlan | None = None,
) -> str:
  """Resolve a forward-backend request through the unified chain."""
  return resolve("forward", op, regularization, backend, shape=shape,
                 platform=platform, dtype=dtype, plan=plan)


def resolve_backward(
    op: str,
    regularization: str,
    backend: str | None = None,
    *,
    shape: tuple[int, ...] | None = None,
    platform: str | None = None,
    dtype: str | None = None,
    plan: ExecutionPlan | None = None,
) -> str:
  """Resolve a backward (VJP) backend request through the unified chain."""
  return resolve("backward", op, regularization, backend, shape=shape,
                 platform=platform, dtype=dtype, plan=plan)


def resolve_projection(
    path: str | None = None,
    regularization: str | None = None,
    *,
    shape: tuple[int, ...] | None = None,
    platform: str | None = None,
    dtype: str | None = None,
    plan: ExecutionPlan | None = None,
) -> str:
  """Resolve a projection-path request through the unified chain.

  The projection registry (``("projection", reg, path)`` keys, populated
  on ``repro.core.projection`` import) holds whole-pipeline
  implementations: ``"fused"`` — single custom VJP around sort + isotonic
  solve + gather, packed integer sorts, gather-only backward;
  ``"sortperm"`` — the same custom VJP with every permutation a key-value
  sort that carries the data, no gather (the TPU route);
  ``"composed"`` — the reference chain of four differentiable primitives,
  kept reachable (env/plan ``composed``) for differential testing.
  """
  return resolve("projection", "projection", regularization, path,
                 shape=shape, platform=platform, dtype=dtype, plan=plan)


# Trace-key cache: (op, reg, backend, flat shape, dtype) tuples already seen
# by ``dispatch``.  A repeated key means jit served the call from its
# compile cache (or re-traced an identical signature); a new key is a fresh
# trace/compile.  Only mutated while metrics are enabled, and cleared with
# the registry, so disabled mode retains no state.  Bounded: a long-running
# server seeing unboundedly many distinct shapes (launch/serve.py ragged
# batches) must not leak one tuple per shape forever, so insertion order is
# tracked and the oldest key is evicted at the cap (the eviction count is
# itself a metric — a hot eviction counter means the cache is thrashing and
# hit/miss ratios undercount true jit cache hits).
TRACE_KEY_CAP = 4096
_SEEN_TRACE_KEYS: dict[tuple, None] = {}
_metrics.on_reset(_SEEN_TRACE_KEYS.clear)


def _trace_cache_note(key: tuple) -> None:
  """Record hit/miss for a dispatch trace key, evicting at the cap."""
  if key in _SEEN_TRACE_KEYS:
    _metrics.counter_inc("dispatch_trace_cache_hit")
    return
  while len(_SEEN_TRACE_KEYS) >= TRACE_KEY_CAP:
    _SEEN_TRACE_KEYS.pop(next(iter(_SEEN_TRACE_KEYS)))
    _metrics.counter_inc("dispatch_trace_cache_evict")
  _SEEN_TRACE_KEYS[key] = None
  _metrics.counter_inc("dispatch_trace_cache_miss")


def _promote_flat(args: tuple[Array, ...], n: int):
  """Flatten to (rows, n) and apply the uniform promote-compute contract:
  every inexact (floating/complex) argument below f32 is promoted to f32;
  integer/bool structure arrays pass through untouched.  Returns the flat
  list plus the original inexact dtype to demote results back to (None
  when no argument was inexact)."""
  inexact = [a.dtype for a in args if jnp.issubdtype(a.dtype, jnp.inexact)]
  orig = jnp.result_type(*inexact) if inexact else None
  flat = []
  for a in args:
    f = a.reshape(-1, n)
    if jnp.issubdtype(a.dtype, jnp.inexact):
      f = f.astype(jnp.promote_types(a.dtype, jnp.float32))
    flat.append(f)
  return flat, orig


def dispatch_projection(z: Array, w: Array, regularization: str,
                        impl: str | None, path: str | None = None,
                        plan: ExecutionPlan | None = None,
                        **kwargs) -> Array:
  """Route a permutahedron projection to its registered pipeline.

  Unlike ``dispatch``, implementations here own their batching (the fused
  path needs the unflattened unbatched-``w`` shape to share one weight
  sort across the batch), so ``z``/``w`` pass through unflattened;
  ``kwargs`` carry the static sortedness flags and optional precomputed
  permutations.  Runs under a ``repro_projection_<reg>_<path>`` named
  scope; fused calls are counted as ``projection_fused_calls``.
  """
  p = resolve_projection(path, regularization, shape=z.shape,
                         dtype=str(z.dtype), plan=plan)
  fn = _REGISTRY.get(("projection", regularization, p))
  if fn is None:
    raise ValueError(
        f"no projection path {p!r} registered for "
        f"regularization={regularization!r} (import repro.core.projection); "
        f"have {registered_backends('projection', regularization)}")
  if p == "fused":
    _metrics.counter_inc("projection_fused_calls",
                         regularization=regularization)
  _metrics.counter_inc("dispatch_calls", op="projection",
                       regularization=regularization, backend=p)
  with _tracing.backend_scope("projection", regularization, p):
    return fn(z, w, impl, plan=plan, **kwargs)


def dispatch(op: str, regularization: str, backend: str | None,
             *args: Array, plan: ExecutionPlan | None = None) -> Array:
  """Route a batched forward pass to the resolved backend.

  All ``args`` must share a common shape whose last axis is the problem
  dimension; leading batch axes are flattened to a single row axis before
  the backend call and restored afterwards, so backends only ever see
  (rows, n).  Half-precision inputs are promoted to f32 for the solve and
  the result demoted back — uniformly, for every backend.

  The backend call runs under ``jax.named_scope`` (see
  ``repro.obs.tracing.scope_name``) so its primitives are attributable in
  profiler traces, and — when metrics are enabled — records per-backend
  call counts, flattened shape buckets, and trace-cache hit/miss counters.
  """
  shape = args[0].shape
  in_dtype = str(jnp.result_type(args[0]))
  b = resolve_backend(op, regularization, backend, shape=shape,
                      dtype=in_dtype, plan=plan)
  fn = _REGISTRY[(op, regularization, b)]
  n = shape[-1]
  flat, orig_dtype = _promote_flat(args, n)
  if _metrics.enabled():
    rows = flat[0].shape[0] if n else 0
    _metrics.counter_inc("dispatch_calls", op=op,
                         regularization=regularization, backend=b)
    _metrics.counter_inc("dispatch_shape", op=op,
                         bucket=_metrics.shape_bucket(rows, n))
    _trace_cache_note((op, regularization, b, flat[0].shape, in_dtype))
  with _tracing.backend_scope(op, regularization, b):
    out = fn(*flat)
  if orig_dtype is not None:
    out = out.astype(orig_dtype)
  return out.reshape(shape)


def dispatch_backward(op: str, regularization: str, backend: str | None,
                      *args: Array, plan: ExecutionPlan | None = None):
  """Route a batched VJP to the resolved backward backend.

  Same flattening and promote-compute-demote contract as ``dispatch``
  (integer/bool segment-structure arrays pass through unpromoted); the
  impl may return a single gradient array or a tuple of gradient arrays
  (each is restored to the original batch shape).  Runs under a
  ``repro_<op>_bwd_<reg>_<backend>`` named scope and records
  ``dispatch_bwd_calls`` counters.
  """
  shape = args[0].shape
  b = resolve_backward(op, regularization, backend, shape=shape,
                       dtype=str(jnp.result_type(args[0])), plan=plan)
  fn = _BWD_REGISTRY[(op, regularization, b)]
  n = shape[-1]
  flat, orig_dtype = _promote_flat(args, n)
  _metrics.counter_inc("dispatch_bwd_calls", op=op,
                       regularization=regularization, backend=b)
  with _tracing.backend_scope(f"{op}_bwd", regularization, b):
    out = fn(*flat)
  if isinstance(out, tuple):
    if orig_dtype is not None:
      out = tuple(o.astype(orig_dtype) for o in out)
    return tuple(o.reshape(shape) for o in out)
  if orig_dtype is not None:
    out = out.astype(orig_dtype)
  return out.reshape(shape)


# ---------------------------------------------------------------------------
# Jit-stable entry points.
# ---------------------------------------------------------------------------

_STABLE_ENTRIES: dict[tuple, Callable] = {}
_STABLE_DISPATCHERS = {"forward": dispatch, "backward": dispatch_backward}


def stable_entry(op: str, regularization: str, backend: str | None = None,
                 *, kind: str = "forward",
                 plan: ExecutionPlan | None = None) -> Callable[..., Array]:
  """A process-stable callable for one pinned dispatch configuration.

  ``jax.jit`` keys its trace cache on function identity, and AOT callers
  (``jax.jit(fn).lower(...).compile()``, the serving engine's executable
  cache) need a deterministic function object per configuration — an
  ad-hoc ``lambda``/``partial`` built at the call site defeats both.
  This returns *the same* callable object for the same
  ``(kind, op, regularization, backend, plan)`` every time:

      f = stable_entry("isotonic", "l2", "scan")
      f is stable_entry("isotonic", "l2", "scan")   # True
      jax.jit(f)(y)        # hits the jit cache across call sites
      jax.jit(f).lower(spec).compile()              # AOT-friendly

  ``kind`` is ``"forward"`` (:func:`dispatch`) or ``"backward"``
  (:func:`dispatch_backward`); the pinned args follow those functions'
  signatures, so the returned callable takes the dispatch ``*args``.
  """
  if kind not in _STABLE_DISPATCHERS:
    raise ValueError(f"kind must be one of "
                     f"{tuple(_STABLE_DISPATCHERS)}, got {kind!r}")
  key = (kind, op, regularization, backend,
         None if plan is None else plan.plan_hash())
  fn = _STABLE_ENTRIES.get(key)
  if fn is None:
    fn = functools.partial(_STABLE_DISPATCHERS[kind], op, regularization,
                           backend, plan=plan)
    _STABLE_ENTRIES[key] = fn
  return fn


# ---------------------------------------------------------------------------
# Backend registration (isotonic optimization, paper §5).
# ---------------------------------------------------------------------------

from repro.kernels import pav as _pav  # noqa: E402
from repro.kernels import pav_dense as _pav_dense  # noqa: E402
from repro.kernels import pav_scan as _pav_scan  # noqa: E402
from repro.kernels import ref as _ref  # noqa: E402
from repro.kernels import segment_vjp as _svjp  # noqa: E402

register("isotonic", "l2", "lax")(_pav.pav_l2_lax)
register("isotonic", "kl", "lax")(_pav.pav_kl_lax)

register("isotonic", "l2", "scan")(_pav_scan.pav_l2_scan)
register("isotonic", "kl", "scan")(_pav_scan.pav_kl_scan)

register("isotonic", "l2", "dense")(_pav_dense.pav_l2_dense)
register("isotonic", "kl", "dense")(_pav_dense.pav_kl_dense)

register("isotonic", "l2", "pallas")(_pav.pav_l2)
register("isotonic", "kl", "pallas")(_pav.pav_kl)

# No per-backend casting wrappers: ``dispatch`` owns the uniform
# promote-compute-demote contract, so the O(n^2) closed forms register
# bare like every other backend.
register("isotonic", "l2", "minimax")(_ref.pav_l2_ref)
register("isotonic", "kl", "minimax")(_ref.pav_kl_ref)

register_backward("isotonic", "l2", "segscan")(_svjp.isotonic_l2_bwd_segscan)
register_backward("isotonic", "l2", "scatter")(_svjp.isotonic_l2_bwd_scatter)
register_backward("isotonic", "kl", "segscan")(_svjp.isotonic_kl_bwd_segscan)
register_backward("isotonic", "kl", "scatter")(_svjp.isotonic_kl_bwd_scatter)

# Fused-projection backward table: same Lemma 2 segment algebra, consuming
# the block structure precomputed by the fused forward (residuals) instead
# of re-deriving it from the solver output.  Forward projection paths
# ("fused" / "sortperm" / "composed") register themselves on
# ``repro.core.projection`` import — kernels must not import core.
register_backward("projection", "l2",
                  "segscan")(_svjp.projection_l2_bwd_segscan)
register_backward("projection", "l2",
                  "scatter")(_svjp.projection_l2_bwd_scatter)
register_backward("projection", "kl",
                  "segscan")(_svjp.projection_kl_bwd_segscan)
register_backward("projection", "kl",
                  "scatter")(_svjp.projection_kl_bwd_scatter)
