"""Open-loop traffic into the serving engine (``repro.serving``).

Set-up builds one ``ServingEngine`` for the mix's op and buckets, warms
every (bucket, rows) program it can route to (``warmup``, then one run
of each), draws the window's schedule and requests from the seed and
starts the engine's own step thread.  The window sends each request at
its due time from this thread, whatever the engine's backlog, and times
it from when it was due to when its result was back on the host.  After
the window closes every request is waited for (up to a minute), but
only those back by the close count as served in the window; a
sample of the answers, drawn from the seed with the longest request in
it, is compared with the float64 reference.
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from chipbench.harness import Compared, Window
from chipbench.reference import soft_ops
from chipbench.traffic import generator

HISTOGRAMS = ("serving_padding_waste", "serving_batch_occupancy")
LATE_WAIT_S = 60.0


def _timed_request():
  from repro.serving import Request

  @dataclasses.dataclass
  class TimedRequest(Request):
    """A request that notes when its result came back."""
    due: float = 0.0
    sent: float = 0.0
    done_at: float = 0.0

    def finish(self, result):
      self.done_at = time.perf_counter()
      super().finish(result)

  return TimedRequest


def _result(req):
  """The request's result, or None while it has none."""
  return req.result(0.0) if req.done() else None


def _hist_sums():
  from repro.obs import metrics
  out = {}
  for key, h in metrics.histograms().items():
    name = key.split("{")[0]
    if name in HISTOGRAMS:
      s, c = out.get(name, (0.0, 0))
      out[name] = (s + h["sum"], c + h["count"])
  return out


class ServeLoop:

  def __init__(self, ctx):
    self.ctx = ctx
    self.requests = []

  def engine_config(self):
    from repro.serving import EngineConfig
    t = self.ctx.traffic
    return EngineConfig(ops=(t["op"],), min_bucket=t["min_bucket"],
                        max_bucket=t["max_bucket"], max_batch=t["max_batch"],
                        max_wait_ms=t["max_wait_ms"],
                        queue_capacity=t["queue_capacity"])

  def make_requests(self, rate: float | None = None):
    """The window's requests, due offsets from the window's start."""
    c, t = self.ctx.config, dict(self.ctx.traffic)
    if rate is not None:
      t["rate_per_s"] = rate
    rng = generator.seed_rng(self.ctx.seed, 3)
    due, _, n = generator.open_loop_schedule(t, c, self.ctx.seconds,
                                                  rng)
    cls = _timed_request()
    out = []
    for d, k in zip(due, n):
      scores, _ = generator.graded_list(c["labels"], c["scores"], int(k), rng)
      out.append(cls(op=t["op"], values=scores,
                     eps=c["regularization_strength"], due=float(d)))
    return out

  def setup(self):
    from repro.serving import ServingEngine
    self.engine = ServingEngine(self.engine_config())
    self.engine.warmup()
    self._first_runs()
    self.requests = self.make_requests()
    self.engine.start()

  def _first_runs(self):
    """Run every warmed program once, so no first execution falls in the
    window."""
    from repro.serving import Request
    pol, op = self.engine.policy, self.ctx.traffic["op"]
    rng = np.random.default_rng(0)
    for size in pol.sizes:
      for rows in pol.row_sizes:
        for _ in range(rows):
          self.engine.submit(Request(op=op, values=rng.standard_normal(
              size).astype(np.float32)))
        self.engine.step(flush=True)

  def window(self, seconds: float) -> Window:
    before = _hist_sums()
    reqs = self.requests
    t0 = time.perf_counter() + 0.005
    for r in reqs:
      r.due += t0
      wait = r.due - time.perf_counter()
      if wait > 0:
        time.sleep(wait)
      r.sent = time.perf_counter()
      self.engine.submit(r)
    end = t0 + seconds
    deadline = end + LATE_WAIT_S
    for r in reqs:
      try:
        r.result(max(0.0, deadline - time.perf_counter()))
      except TimeoutError:
        pass
    after = _hist_sums()
    return self._record(seconds, end, before, after)

  def _record(self, seconds, end, before, after) -> Window:
    reqs = self.requests
    cap_ms = (seconds + LATE_WAIT_S) * 1e3
    lat, late, ok, by_close = [], [], 0, 0
    for r in reqs:
      late.append((r.sent - r.due) * 1e3)
      res = _result(r)
      if res is not None and res.ok:
        ok += 1
        by_close += r.done_at <= end
        lat.append((r.done_at - r.due) * 1e3)
      else:
        lat.append(cap_ms)
    hist = {}
    for name in HISTOGRAMS:
      s0, c0 = before.get(name, (0.0, 0))
      s1, c1 = after.get(name, (0.0, 0))
      if c1 > c0:
        hist[name] = (s1 - s0) / (c1 - c0)
    return Window(seconds=seconds, attempted=len(reqs),
                  failed=len(reqs) - ok, latencies_ms=lat, lateness_ms=late,
                  served=by_close, histograms=hist)

  def release(self):
    self.engine.stop(drain=True)
    self.engine = None

  # -- correctness ------------------------------------------------------------

  def sample(self) -> list[int]:
    t = self.ctx.traffic
    done = [i for i, r in enumerate(self.requests)
            if _result(r) is not None and _result(r).ok]
    if not done:
      return []
    rng = generator.seed_rng(self.ctx.seed, 5)
    longest = max(done, key=lambda i: self.requests[i].n)
    pick = rng.permutation(done)[:t["check_sample"]].tolist()
    return sorted(set(pick) | {longest})

  def answers(self, idx: list[int]) -> dict[int, np.ndarray]:
    return {i: _result(self.requests[i]).value for i in idx}

  def value_gap(self, answers: dict[int, np.ndarray]) -> float:
    """Largest gap of an answer from the reference, over max(1, max
    |reference|) of its request."""
    worst = 0.0
    for i, got in answers.items():
      r = self.requests[i]
      want, _ = soft_ops.soft_rank_desc(np.asarray(r.values, np.float64),
                                        r.eps)
      got = np.asarray(got, np.float64)
      if got.shape != want.shape:
        return math.inf
      worst = max(worst, float(np.max(np.abs(got - want)))
                  / max(1.0, float(np.max(np.abs(want)))))
    return worst

  def compare(self) -> list[Compared]:
    limits = self.ctx.traffic["limits"]
    unanswered = sum(1 for r in self.requests
                     if _result(r) is None or _result(r).status == "error")
    answers = self.answers(self.sample())
    return [Compared("unanswered", float(unanswered), 0.0),
            Compared("value_gap", self.value_gap(answers),
                     limits["value_gap"])]


def build(ctx):
  return ServeLoop(ctx)
