"""Plain NumPy reference of the l2 soft operators, their losses and VJPs.

Written from the paper's definitions (Blondel et al., ICML 2020): sort,
pool adjacent violators, unpermute (Prop. 3), and the block-averaging
Jacobian of the isotonic fit (Lemma 2).  The PAV loop and the projection
are copies of the float64 references in ``chip_smoke.py``.  Nothing here
imports the program.

Every function takes ``rnd``, the rounding applied after each arithmetic
step: ``float`` (the default) keeps float64; ``BF16`` rounds every
intermediate to bfloat16 and is the precision control of the benchmark's
comparisons (see ``chipbench/control.py``).
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


def BF16(x):
  """Round a scalar or an array to bfloat16, returned as float64."""
  if np.ndim(x) == 0:
    return float(ml_dtypes.bfloat16(x))
  return np.asarray(x, ml_dtypes.bfloat16).astype(np.float64)


def _arr(x, rnd):
  x = np.asarray(x, np.float64)
  return x if rnd is float else rnd(x)


def pav_l2(y: np.ndarray, rnd=float) -> tuple[np.ndarray, np.ndarray]:
  """Non-increasing isotonic regression of y (least squares).

  Returns the fit and the length of each of its blocks, in order."""
  sums, counts = [], []
  for yi in y.tolist():
    s, c = yi, 1
    while sums and rnd(sums[-1] / counts[-1]) <= rnd(s / c):
      s = rnd(s + sums.pop())
      c += counts.pop()
    sums.append(s)
    counts.append(c)
  counts = np.asarray(counts)
  means = _arr([rnd(s / c) for s, c in zip(sums, counts)], rnd)
  return np.repeat(means, counts), counts


def block_mean(x: np.ndarray, counts: np.ndarray, rnd=float) -> np.ndarray:
  """The isotonic fit's Jacobian applied to x: each block's mean, repeated
  over the block (Lemma 2; symmetric, so it is its own transpose)."""
  ends = np.cumsum(counts)
  sums = np.add.reduceat(x, ends - counts) if len(x) else x
  return np.repeat(_arr(sums / counts, rnd), counts)


def soft_rank_desc(theta: np.ndarray, eps: float, rnd=float):
  """Soft rank, rank 1 for the largest score: P(-theta/eps, rho).

  Returns (ranks, (sigma, counts)) with what the VJP needs."""
  n = theta.shape[-1]
  z = _arr(-np.asarray(theta, np.float64) / eps, rnd)
  rho = np.arange(n, 0, -1, dtype=np.float64)
  sigma = np.argsort(-z, kind="stable")
  s = z[sigma]
  v, counts = pav_l2(_arr(s - rho, rnd), rnd)
  out = np.empty(n)
  out[sigma] = _arr(s - v, rnd)
  return out, (sigma, counts)


def soft_rank_desc_vjp(g: np.ndarray, eps: float, res, rnd=float):
  """d<g, soft_rank_desc(theta)>/d theta."""
  sigma, counts = res
  g = np.asarray(g, np.float64)
  g_z = g.copy()
  g_z[sigma] = _arr(g[sigma] - block_mean(g[sigma], counts, rnd), rnd)
  return _arr(-g_z / eps, rnd)


def soft_sort_desc(theta: np.ndarray, eps: float, rnd=float):
  """Soft sort, largest first: P(rho/eps, theta).

  Returns (sorted values, (tau, counts)) with what the VJP needs."""
  n = theta.shape[-1]
  z = _arr(np.arange(n, 0, -1, dtype=np.float64) / eps, rnd)
  theta = _arr(theta, rnd)
  tau = np.argsort(-theta, kind="stable")
  v, counts = pav_l2(_arr(z - theta[tau], rnd), rnd)
  return _arr(z - v, rnd), (tau, counts)


def soft_sort_desc_vjp(g: np.ndarray, res, rnd=float):
  """d<g, soft_sort_desc(theta)>/d theta."""
  tau, counts = res
  out = np.empty(len(tau))
  out[tau] = block_mean(np.asarray(g, np.float64), counts, rnd)
  return out


def spearman_row(theta: np.ndarray, target: np.ndarray, eps: float,
                 rnd=float) -> tuple[float, np.ndarray]:
  """0.5 * ||soft_rank_desc(theta) - target||^2 and its gradient."""
  r, res = soft_rank_desc(theta, eps, rnd)
  diff = _arr(r - np.asarray(target, np.float64), rnd)
  loss = rnd(0.5 * float(np.sum(_arr(diff * diff, rnd))))
  return loss, soft_rank_desc_vjp(diff, eps, res, rnd)


def trimmed_loss(x: np.ndarray, trim_fraction: float, eps: float,
                 rnd=float) -> tuple[float, np.ndarray]:
  """Soft least trimmed squares over one row of losses: the soft sort
  (largest first) with the round(trim_fraction * n) largest dropped,
  averaged; and its gradient."""
  n = x.shape[-1]
  k = int(round(trim_fraction * n))
  s, res = soft_sort_desc(x, eps, rnd)
  loss = rnd(float(np.sum(s[k:])) / (n - k))
  g = np.zeros(n)
  g[k:] = 1.0 / (n - k)
  return loss, soft_sort_desc_vjp(g, res, rnd)
