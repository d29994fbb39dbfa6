"""Isotonic optimization via Pool-Adjacent-Violators (paper §5).

Solves, for a vector of length n,

  v_Q(s, w) = argmin_{v_1 >= ... >= v_n} 1/2 ||v - (s - w)||^2            (Q)
  v_E(s, w) = argmin_{v_1 >= ... >= v_n} <e^{s-v}, 1> + <e^w, v>          (E)

exactly, in O(n) after inputs are sorted, with the analytic block solutions

  gamma_Q(B) = mean_{i in B} (s_i - w_i)          (Eq. 7)
  gamma_E(B) = LSE(s_B) - LSE(w_B)                (Eq. 8)

This module is batched-first: the public operators accept arbitrary leading
batch dimensions and make exactly one dispatch call per forward pass
(``repro.kernels.dispatch``), which routes the flattened (rows, n) batch to
a registered backend — ``"lax"`` (reference ``lax.fori_loop`` stack machine,
natively batched), ``"scan"`` (log-depth divide-and-conquer PAV),
``"dense"`` (the same PAV without element-wise gathers; the TPU route),
``"pallas"`` (tiled TPU kernel), or ``"minimax"`` (O(n^2) closed form for
small n / SPMD).  Backend choice follows the unified precedence chain
(explicit ``impl=`` > ``REPRO_BACKEND`` > execution plan — see
``repro.plan``); an :class:`~repro.plan.ExecutionPlan` can be pinned
per-call via ``plan=`` (it rides the custom_vjp as a static argument, so
it survives jit, unlike trace-time context managers).  The dtype contract
(bf16/f16 promoted to f32 for the solve, demoted on return) is enforced
centrally in dispatch — uniformly for every backend.

The backward pass is exact and O(n) for every forward backend (Lemma 2):
the Jacobian is block-diagonal with rank-1 blocks, recovered from runs of
equal values in the forward output, so the VJP is a couple of batched
segment reductions and never differentiates through solver iterates.  Those
reductions are themselves dispatched — ``dispatch_backward`` routes to a
registered backward backend (``"segscan"`` segmented prefix scans by
default, ``"scatter"`` segment_sum as the reference formulation; see
``repro.kernels.segment_vjp``) with its own named-scope attribution and
metrics.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

Array = jax.Array


# ---------------------------------------------------------------------------
# Public, batched, differentiable operators.
# ---------------------------------------------------------------------------


def _dispatch(regularization: str, impl: str | None, plan,
              *args: Array) -> Array:
  from repro.kernels import dispatch as _d  # lazy: keep core import light
  return _d.dispatch("isotonic", regularization, impl, *args, plan=plan)


def _dispatch_bwd(regularization: str, plan, *args: Array):
  from repro.kernels import dispatch as _d  # lazy: keep core import light
  return _d.dispatch_backward("isotonic", regularization, None, *args,
                              plan=plan)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def isotonic_l2(y: Array, impl: str | None = None, plan=None) -> Array:
  """Isotonic regression: argmin ||v - y||^2, v non-increasing (last axis).

  ``impl`` / ``plan`` must be passed EXPLICITLY by callers that need a
  specific backend under jit/grad: custom_vjp fwd rules are traced lazily
  (after any trace-time context manager has exited), so ``use_impl`` /
  ``use_plan`` only affect eager/top-level calls.
  """
  return _dispatch("l2", impl, plan, y)


def _isotonic_l2_fwd(y, impl, plan):
  v = _dispatch("l2", impl, plan, y)
  return v, v


def _isotonic_l2_bwd(impl, plan, v, g):
  # Lemma 2 (Q): dv/dy is block-diagonal with blocks 11^T/|B| (symmetric).
  return (_dispatch_bwd("l2", plan, v, g),)


isotonic_l2.defvjp(_isotonic_l2_fwd, _isotonic_l2_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def isotonic_kl(s: Array, w: Array, impl: str | None = None,
                plan=None) -> Array:
  """Entropic-regularization isotonic optimization (paper Eq. 8), last axis."""
  return _isotonic_kl_impl(s, w, impl, plan)


def _isotonic_kl_impl(s: Array, w: Array, impl: str | None, plan) -> Array:
  w = jnp.broadcast_to(w, s.shape)
  return _dispatch("kl", impl, plan, s, w)


def _isotonic_kl_fwd(s, w, impl, plan):
  v = _isotonic_kl_impl(s, w, impl, plan)
  return v, (s, w, v)


def _isotonic_kl_bwd(impl, plan, res, g):
  s, w, v = res
  w_b = jnp.broadcast_to(w, s.shape)

  # Lemma 2 (E): B_j = 1 (x) softmax(s_B); transpose-multiply:
  #   grad_s = softmax(s_B) * sum(g_B);  grad_w = -softmax(w_B) * sum(g_B).
  grad_s, grad_w = _dispatch_bwd("kl", plan, s, w_b, v, g)
  # Un-broadcast w gradient if w was unbatched.
  if w.shape != s.shape:
    grad_w = jnp.sum(
        grad_w.reshape((-1,) + w.shape), axis=0).reshape(w.shape)
  return grad_s, grad_w


isotonic_kl.defvjp(_isotonic_kl_fwd, _isotonic_kl_bwd)


# ---------------------------------------------------------------------------
# Backend selection: thin aliases over the dispatch registry (kept for
# backward compatibility; see repro.kernels.dispatch for the registry).
# ---------------------------------------------------------------------------


def set_default_impl(impl: str) -> None:
  """Set the process-default backend (one of repro.kernels.dispatch.BACKENDS)."""
  from repro.kernels import dispatch as _d
  _d.set_default_backend(impl)


@contextlib.contextmanager
def use_impl(impl: str):
  """Temporarily select the isotonic solver backend (trace-time)."""
  from repro.kernels import dispatch as _d
  with _d.use_backend(impl):
    yield
