"""Fused flash-attention Pallas kernel vs the pure-JAX reference, and the
reference's gradients vs dense softmax attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_tpu
from repro.models.layers import flash_attention

rng = np.random.default_rng(7)


@pytest.mark.parametrize("b,sq,h,hkv,d,dv", [
    (2, 128, 4, 2, 32, 32),    # GQA
    (1, 256, 8, 8, 16, 16),    # MHA
    (2, 128, 4, 1, 32, 32),    # MQA
    (1, 128, 4, 4, 48, 24),    # dv != d (MLA-style)
])
def test_matches_reference_causal(b, sq, h, hkv, d, dv):
  q = jnp.array(rng.normal(size=(b, sq, h, d)).astype(np.float32))
  k = jnp.array(rng.normal(size=(b, sq, hkv, d)).astype(np.float32))
  v = jnp.array(rng.normal(size=(b, sq, hkv, dv)).astype(np.float32))
  got = flash_attention_tpu(q, k, v, causal=True, block_q=64, block_kv=64)
  want = flash_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             atol=2e-4, rtol=2e-4)


def test_non_causal():
  q = jnp.array(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
  k = jnp.array(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
  v = jnp.array(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
  got = flash_attention_tpu(q, k, v, causal=False, block_q=32, block_kv=32)
  want = flash_attention(q, k, v, causal=False, q_chunk=32, kv_chunk=32)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_bf16_io():
  q = jnp.array(rng.normal(size=(1, 64, 4, 16)), jnp.bfloat16)
  k = jnp.array(rng.normal(size=(1, 64, 2, 16)), jnp.bfloat16)
  v = jnp.array(rng.normal(size=(1, 64, 2, 16)), jnp.bfloat16)
  got = flash_attention_tpu(q, k, v, block_q=32, block_kv=32)
  assert got.dtype == jnp.bfloat16
  want = flash_attention(q, k, v, q_chunk=32, kv_chunk=32)
  np.testing.assert_allclose(np.asarray(got, np.float32),
                             np.asarray(want, np.float32), atol=3e-2)


def _dense_attention(q, k, v, causal):
  b, sq, h, d = q.shape
  g = h // k.shape[2]
  k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
  s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
  if causal:
    s = jnp.where(jnp.tril(jnp.ones((sq, sq), bool)), s, -jnp.inf)
  return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("causal", [True, False])
def test_chunked_reference_gradients_match_dense_softmax(causal):
  """The chunked reference holds its running max out of the gradient; the
  gradients must still be those of plain softmax attention."""
  q = jnp.array(rng.normal(size=(1, 64, 4, 16)).astype(np.float32))
  k = jnp.array(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
  v = jnp.array(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
  u = jnp.array(rng.normal(size=(1, 64, 4, 16)).astype(np.float32))
  chunked = lambda *a: jnp.sum(u * flash_attention(
      *a, causal=causal, q_chunk=16, kv_chunk=16))
  dense = lambda *a: jnp.sum(u * _dense_attention(*a, causal))
  got = jax.grad(chunked, argnums=(0, 1, 2))(q, k, v)
  want = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
  for a, b in zip(got, want):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-5, rtol=2e-4)
