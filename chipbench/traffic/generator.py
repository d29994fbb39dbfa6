"""The one traffic generator: every mix under ``chipbench/traffic/*.json``
is a set of parameters for these functions.

Sizes and arrivals are stratified: a run draws the same multiset of list
lengths and gaps whatever its seed, and the seed only orders them.  Two seeds therefore give the device the same work, so the spread
between runs measures the system and not the draw.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
  """A generator for one stream of a run's draws (any seed >= 0)."""
  return np.random.default_rng([int(seed), *stream])


def key32(seed: int) -> int:
  """A 32-bit PRNG key for JAX from a seed of any size."""
  return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def _quantiles(m: int) -> np.ndarray:
  return (np.arange(m) + 0.5) / m


def lognormal_quantiles(m: int, mean: float, sigma: float) -> np.ndarray:
  """m stratified draws of a lognormal with the given mean and log-sd."""
  mu = math.log(mean) - sigma * sigma / 2
  z = np.array([_NORMAL.inv_cdf(q) for q in _quantiles(m)])
  return np.exp(mu + sigma * z)


def list_lengths(spec: dict, m: int, rng: np.random.Generator) -> np.ndarray:
  """m list lengths shaped by ``spec`` (a config's ``list_length``),
  shuffled by ``rng``."""
  if spec["distribution"] != "lognormal":
    raise ValueError(f"unknown length distribution {spec['distribution']!r}")
  x = lognormal_quantiles(m, spec["mean"], spec["sigma"])
  n = np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)
  return rng.permutation(n)


def graded_list(labels_spec: dict, score_spec: dict, n: int,
                rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
  """One query's candidates: relevance grades drawn with the config's
  shares, and model scores that follow the grade with Gaussian noise.
  Returns (scores, target ranks), rank 1 for the most relevant document
  (ties between equal grades broken by position)."""
  grades = rng.choice(np.asarray(labels_spec["grades"], np.float64), size=n,
                      p=np.asarray(labels_spec["shares"], np.float64))
  scores = (score_spec["per_grade"] * grades
            + score_spec["noise"] * rng.standard_normal(n))
  order = np.argsort(-grades, kind="stable")
  target = np.empty(n)
  target[order] = np.arange(1, n + 1)
  return scores.astype(np.float32), target.astype(np.float32)


def slate_batch(config: dict, rows: int, slate: int,
                rng: np.random.Generator):
  """``rows`` query lists padded (or cut) to ``slate``.

  Returns (scores (rows, slate) f32, true_n (rows,) i32, target (rows,
  slate) f32); entries past a row's length are 0."""
  lengths = np.minimum(list_lengths(config["list_length"], rows, rng), slate)
  scores = np.zeros((rows, slate), np.float32)
  target = np.zeros((rows, slate), np.float32)
  for i, n in enumerate(lengths):
    s, t = graded_list(config["labels"], config["scores"], int(n), rng)
    scores[i, :n], target[i, :n] = s, t
  return scores, lengths.astype(np.int32), target


def token_losses(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
  """n per-token losses: a lognormal body with a share of corrupted tokens
  lifted by a uniform amount.  Drawn afresh for every seed: the row's
  length, not its values, sets the work."""
  mu = math.log(spec["mean"]) - spec["sigma"] ** 2 / 2
  x = rng.lognormal(mu, spec["sigma"], n)
  bad = rng.permutation(n)[:int(round(spec["corrupt_share"] * n))]
  lo, hi = spec["corrupt_lift"]
  x[bad] += rng.uniform(lo, hi, len(bad))
  return x.astype(np.float32)


def open_loop_schedule(traffic: dict, config: dict, seconds: float,
                       rng: np.random.Generator):
  """Poisson arrivals at ``traffic['rate_per_s']`` for ``seconds``.

  Gaps are the stratified quantiles of the exponential distribution, so
  every seed offers the same count and the same gaps in another order.
  One client sends ``heavy_share`` of the requests, the others share the
  rest evenly.  Returns (due offsets in s, client ids, list lengths)."""
  m = int(round(traffic["rate_per_s"] * seconds))
  gaps = -np.log1p(-_quantiles(m)) / traffic["rate_per_s"]
  gaps *= seconds / gaps.sum()       # the m requests span the window
  gaps = rng.permutation(gaps)
  due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
  clients = traffic["clients"]
  heavy = int(round(traffic["heavy_share"] * m))
  client = np.concatenate([np.zeros(heavy, np.int64),
                           1 + np.arange(m - heavy) % (clients - 1)])
  n = list_lengths(config["list_length"], m, rng)
  return due, rng.permutation(client), n


def token_batch(seed: int, step: int, batch: int, seq: int, vocab: int,
                corrupt: float) -> dict[str, np.ndarray]:
  """Tokens and next-token targets for one training step, drawn afresh
  for every (seed, step); a ``corrupt`` share of the targets is replaced
  by uniform noise, the outliers the trimmed loss is there to drop."""
  rng = seed_rng(seed, 7, step)
  stream = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
  targets = stream[:, 1:].copy()
  bad = rng.random(targets.shape) < corrupt
  targets[bad] = rng.integers(0, vocab, int(bad.sum()), dtype=np.int32)
  return {"tokens": stream[:, :-1].copy(), "targets": targets}
