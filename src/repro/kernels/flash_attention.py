"""Pallas TPU kernel: fused causal flash attention (forward).

The §Roofline analysis found every train cell memory-bound because the
XLA-level chunked attention round-trips (cq x ckv) score blocks through
HBM between fusions (EXPERIMENTS.md §Perf, gemma3/xlstm conclusions).
This kernel is the identified fix: one ``pallas_call`` per (batch, kv-head,
q-block) grid cell keeps Q/K/V blocks and the running (m, l, acc) state in
VMEM — HBM traffic collapses to reading Q, K, V once and writing O once.

Grid: (B, Hkv, Sq / BLOCK_Q); the kernel loops over kv blocks with
``lax.fori_loop`` entirely in registers/VMEM.  GQA handled by loading all
G query groups of a kv head per cell.  Validated in interpret mode against
``repro.models.layers.flash_attention`` (the pure-JAX reference).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

Array = jax.Array

_NEG_INF = -1e30

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *,
                  block_q: int, block_kv: int, skv: int, scale: float,
                  causal: bool):
  """One (batch, kv-head, q-block) cell.

  q_ref: (G, block_q, D); k_ref/v_ref: (Skv, D); o_ref: (G, block_q, Dv).
  """
  qi = pl.program_id(2)
  q = q_ref[...].astype(jnp.float32) * scale          # (G, bq, D)
  g, bq, d = q.shape
  dv = o_ref.shape[-1]
  q_pos = qi * block_q + jnp.arange(block_q)

  nkv = skv // block_kv
  if causal:
    # kv blocks beyond this q block never contribute: skip them.
    last = jnp.minimum(
        (qi * block_q + block_q + block_kv - 1) // block_kv, nkv)
  else:
    last = nkv

  def body(j, carry):
    m, l, acc = carry
    k_blk = k_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
    v_blk = v_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
    s = jnp.einsum("gqd,kd->gqk", q, k_blk)           # (G, bq, bkv)
    if causal:
      kv_pos = j * block_kv + jnp.arange(block_kv)
      mask = kv_pos[None, :] <= q_pos[:, None]
      s = jnp.where(mask[None], s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum("gqk,kv->gqv", p, v_blk)
    return m_new, l_new, acc_new

  m0 = jnp.full((g, bq), _NEG_INF, jnp.float32)
  l0 = jnp.zeros((g, bq), jnp.float32)
  acc0 = jnp.zeros((g, bq, dv), jnp.float32)
  m, l, acc = lax.fori_loop(0, last, body, (m0, l0, acc0))
  o_ref[...] = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_kv", "interpret"))
def flash_attention_tpu(
    q: Array, k: Array, v: Array, *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool | None = None,
) -> Array:
  """Fused attention. q: (B, Sq, H, D); k, v: (B, Skv, Hkv, D|Dv)."""
  if interpret is None:
    interpret = jax.default_backend() != "tpu"
  b, sq, h, d = q.shape
  _, skv, hkv, dv = v.shape
  g = h // hkv
  scale = 1.0 / math.sqrt(d)
  block_q = min(block_q, sq)
  block_kv = min(block_kv, skv)
  while sq % block_q:
    block_q -= 1
  while skv % block_kv:
    block_kv -= 1

  # (B, Hkv, G, S, D) layout: one grid cell sees all G groups of a kv head.
  qt = q.reshape(b, sq, hkv, g, d).transpose(0, 2, 3, 1, 4)
  kt = k.transpose(0, 2, 1, 3)                       # (B, Hkv, Skv, D)
  vt = v.transpose(0, 2, 1, 3)                       # (B, Hkv, Skv, Dv)

  grid = (b, hkv, sq // block_q)
  out = pl.pallas_call(
      functools.partial(
          _flash_kernel, block_q=block_q, block_kv=block_kv, skv=skv,
          scale=scale, causal=causal),
      out_shape=jax.ShapeDtypeStruct((b, hkv, g, sq, dv), q.dtype),
      grid=grid,
      in_specs=[
          pl.BlockSpec((None, None, g, block_q, d),
                       lambda bi, hi, qi: (bi, hi, 0, qi, 0)),
          pl.BlockSpec((None, None, skv, d),
                       lambda bi, hi, qi: (bi, hi, 0, 0)),
          pl.BlockSpec((None, None, skv, dv),
                       lambda bi, hi, qi: (bi, hi, 0, 0)),
      ],
      out_specs=pl.BlockSpec((None, None, g, block_q, dv),
                             lambda bi, hi, qi: (bi, hi, 0, qi, 0)),
      interpret=interpret,
  )(qt, kt, vt)
  return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv)
