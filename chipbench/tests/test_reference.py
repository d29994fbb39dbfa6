"""The plain references agree with the program at small sizes on the
CPU, and the precision control departs from them by far more."""

import numpy as np
import pytest

from chipbench.reference import soft_ops


def test_pav_pools_violators():
  fit, counts = soft_ops.pav_l2(np.array([1.0, 3.0, 2.0, 0.0]))
  np.testing.assert_allclose(fit, [2.0, 2.0, 2.0, 0.0])
  assert counts.tolist() == [3, 1]


@pytest.mark.parametrize("n", [5, 37, 300])
def test_soft_rank_and_its_vjp_match_the_program(n):
  import jax
  import jax.numpy as jnp
  from repro import core
  rng = np.random.default_rng(n)
  x = rng.standard_normal(n).astype(np.float32)
  g = rng.standard_normal(n).astype(np.float32)
  eps = 0.1
  want, res = soft_ops.soft_rank_desc(x, eps)
  want_g = soft_ops.soft_rank_desc_vjp(g, eps, res)
  got, vjp = jax.vjp(lambda t: core.soft_rank(t, eps, "l2"), jnp.asarray(x))
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
  np.testing.assert_allclose(vjp(jnp.asarray(g))[0], want_g, rtol=1e-4,
                             atol=1e-4)


def test_trimmed_loss_matches_the_program():
  import jax
  import jax.numpy as jnp
  from repro.core.losses import soft_trimmed_token_loss
  x = np.random.default_rng(1).lognormal(1.0, 0.6, 2048).astype(np.float32)
  loss, grad = soft_ops.trimmed_loss(x, 0.1, 1e-2)
  got, got_g = jax.value_and_grad(
      lambda t: soft_trimmed_token_loss(t, 0.1, 1e-2))(jnp.asarray(x))
  # z = rho / eps reaches 2e5 here: float32 cancels in z - v
  assert abs(float(got) - loss) < 1e-4 * abs(loss)
  np.testing.assert_allclose(got_g, grad, atol=1e-7)


def test_bf16_control_departs_by_orders_of_magnitude():
  x = np.random.default_rng(2).standard_normal(200)
  exact, _ = soft_ops.soft_rank_desc(x, 0.1)
  low, _ = soft_ops.soft_rank_desc(x, 0.1, soft_ops.BF16)
  f32, _ = soft_ops.soft_rank_desc(x.astype(np.float32), 0.1)
  gap = lambda a: np.abs(a - exact).max() / np.abs(exact).max()
  assert gap(low) > 100 * max(gap(f32), 1e-9)
