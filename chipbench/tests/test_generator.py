"""The traffic generator: reproducible from the seed, the same work for
every seed, and the serving window's lateness reported."""

import numpy as np

from chipbench.tests.small import serve_window
from chipbench.traffic import generator

CONFIG = {"list_length": {"distribution": "lognormal", "mean": 119.6,
                          "sigma": 0.75, "min": 1, "max": 1251}}
TRAFFIC = {"rate_per_s": 200.0, "clients": 4, "heavy_share": 0.5}
BIG = 2**31 + 12345


def test_schedule_is_reproducible_from_the_seed():
  a = generator.open_loop_schedule(TRAFFIC, CONFIG, 10.0,
                                   generator.seed_rng(BIG, 3))
  b = generator.open_loop_schedule(TRAFFIC, CONFIG, 10.0,
                                   generator.seed_rng(BIG, 3))
  for x, y in zip(a, b):
    np.testing.assert_array_equal(x, y)


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
  due1, client1, n1 = generator.open_loop_schedule(
      TRAFFIC, CONFIG, 10.0, generator.seed_rng(1, 3))
  due2, client2, n2 = generator.open_loop_schedule(
      TRAFFIC, CONFIG, 10.0, generator.seed_rng(BIG, 3))
  assert len(due1) == len(due2) == 2000
  assert not np.array_equal(n1, n2)
  np.testing.assert_array_equal(np.sort(n1), np.sort(n2))
  gaps = lambda due: np.sort(np.diff(np.append(due, 10.0)))
  np.testing.assert_allclose(gaps(due1), gaps(due2), rtol=1e-9, atol=1e-12)
  assert due1[0] == 0.0 and due1[-1] < 10.0
  assert (client1 == 0).sum() == 1000             # the heavy client
  assert set(np.unique(client1)) == {0, 1, 2, 3}


def test_lengths_follow_the_corpus():
  n = generator.list_lengths(CONFIG["list_length"], 31531,
                             generator.seed_rng(0))
  assert abs(n.mean() / 119.6 - 1) < 0.02
  assert n.min() >= 1 and n.max() <= 1251


def test_token_batches_differ_by_step_and_repeat_by_seed():
  a = generator.token_batch(BIG, 0, 1, 64, 1000, 0.05)
  b = generator.token_batch(BIG, 0, 1, 64, 1000, 0.05)
  c = generator.token_batch(BIG, 1, 1, 64, 1000, 0.05)
  np.testing.assert_array_equal(a["tokens"], b["tokens"])
  assert not np.array_equal(a["tokens"], c["tokens"])


def test_serving_window_reports_generator_lateness():
  w = serve_window()
  assert w.attempted == 20 and w.failed == 0
  assert len(w.lateness_ms) == len(w.latencies_ms) == w.attempted
  assert sorted(w.lateness_ms)[int(0.95 * w.attempted)] >= 0.0
  assert 0 < w.served <= w.attempted
