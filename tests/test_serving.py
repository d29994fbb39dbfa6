"""`repro.serving`: bucket policy, AOT executable cache, admission
control (typed shedding), micro-batch engine end-to-end (padded results
bitwise vs the unpadded operators per backend), plan-derived warmup
(zero post-warmup misses), and the jit-stable dispatch entries."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import plan as plan_mod
from repro.core import soft_rank
from repro.core.losses import soft_lts_loss
from repro.kernels import dispatch as D
from repro.obs import metrics
from repro.serving import (
    AOTExecutableCache,
    AdmissionQueue,
    BucketPolicy,
    EngineConfig,
    Request,
    ServingEngine,
    STATUS_ERROR,
    STATUS_OK,
    STATUS_SHED_DEADLINE,
    STATUS_SHED_QUEUE_FULL,
    SERVING_OPS,
    synthetic_stream,
)
from repro.serving.ops import bound_op

rng = np.random.default_rng(3)


@pytest.fixture(autouse=True)
def clean_metrics():
  metrics.set_enabled(True)
  metrics.reset()
  yield
  metrics.set_enabled(None)
  metrics.reset()


class FakeClock:
  def __init__(self, t=100.0):
    self.t = t

  def __call__(self):
    return self.t


def _req(n=5, op="soft_rank/l2/desc", **kw):
  return Request(op=op, values=rng.standard_normal(n).astype(np.float32),
                 eps=0.5, **kw)


# ---------------------------------------------------------------------------
# Bucket policy.
# ---------------------------------------------------------------------------


def test_bucket_policy_pow2_ladder_and_lookup():
  p = BucketPolicy.pow2(min_n=64, max_n=4096, max_batch=8)
  assert p.sizes == (64, 128, 256, 512, 1024, 2048, 4096)
  assert p.row_sizes == (1, 2, 4, 8)
  assert p.bucket_for(1) == 64
  assert p.bucket_for(64) == 64
  assert p.bucket_for(65) == 128
  assert p.bucket_for(4096) == 4096
  assert p.rows_for(3) == 4
  with pytest.raises(ValueError, match="exceeds the largest bucket"):
    p.bucket_for(4097)
  with pytest.raises(ValueError, match=">= 1"):
    p.bucket_for(0)


def test_bucket_policy_from_plan_splices_breakpoints():
  plan = plan_mod.ExecutionPlan(name="edges", rules=(
      plan_mod.PlanRule("forward", "minimax", max_n=100,
                        max_elems=10**6),
      plan_mod.PlanRule("forward", "scan", min_n=3000),
  ))
  p = BucketPolicy.from_plan(plan, platform="cpu", min_n=64, max_n=4096,
                             max_batch=4)
  # 100 (a max_n edge) and 2999 (min_n - 1) join the pow2 ladder, so no
  # bucket pads a request across a backend cutoff.
  assert 100 in p.sizes and 2999 in p.sizes
  assert p.bucket_for(70) == 100      # would have been 128 without the plan
  assert p.bucket_for(101) == 128
  # Builtin-plan edges (e.g. the minimax small-n cutoff at 64) are also
  # representable: the chain is consulted when plan=None.
  assert BucketPolicy.from_plan(None, platform="cpu", min_n=8, max_n=128,
                                max_batch=2).bucket_for(8) <= 64


@pytest.mark.parametrize("platform, cpu_edge", [
    ("cpu", True), ("tpu", False), ("gpu", False)])
def test_shape_breakpoints_skip_rules_of_other_platforms(platform, cpu_edge):
  plan = plan_mod.ExecutionPlan(name="edges", rules=(
      plan_mod.PlanRule("forward", "lax", platform="cpu", max_n=100),
      plan_mod.PlanRule("forward", "scan", min_n=3000),
  ))
  edges = plan_mod.shape_breakpoints(plan, platform=platform)
  assert 2999 in edges
  assert (100 in edges) == cpu_edge
  sizes = BucketPolicy.from_plan(plan, min_n=64, max_n=4096, max_batch=1,
                                 platform=platform).sizes
  assert (100 in sizes) == cpu_edge


def test_shape_breakpoints_and_resolve_grid():
  plan = plan_mod.ExecutionPlan(name="edges", rules=(
      plan_mod.PlanRule("forward", "minimax", max_n=100, max_elems=10**6),
      plan_mod.PlanRule("forward", "scan"),
  ))
  edges = plan_mod.shape_breakpoints(plan, platform="cpu")
  assert 100 in edges
  grid = plan_mod.resolve_grid(
      "forward", ["isotonic"], ["l2"], [(4, 32), (4, 4096)],
      platform="cpu", plan=plan)
  assert [g["backend"] for g in grid] == ["minimax", "scan"]
  assert all(g["plan"] == "edges" and g["source"] == "plan" for g in grid)
  # Enumeration must not pollute dispatch-decision counters.
  assert metrics.counters("plan_decide") == {}


# ---------------------------------------------------------------------------
# AOT executable cache.
# ---------------------------------------------------------------------------


def test_aot_cache_hit_miss_warm_evict_counters():
  cache = AOTExecutableCache(capacity=2)
  builds = []

  def builder(tag):
    def build():
      builds.append(tag)
      return ("exe", tag)
    return build

  assert cache.warm("a", builder("a")) is True
  assert cache.warm("a", builder("a")) is False     # already resident
  assert cache.get("a", builder("a")) == ("exe", "a")
  assert cache.get("b", builder("b")) == ("exe", "b")   # miss, compile
  assert cache.get("c", builder("c")) == ("exe", "c")   # miss, evicts "a"
  assert len(cache) == 2 and "a" not in cache
  assert builds == ["a", "b", "c"]
  c = metrics.counters()
  assert c["aot_cache_warm"] == 1
  assert c["aot_cache_hit"] == 1
  assert c["aot_cache_miss"] == 2
  assert c["aot_cache_evict"] == 1


def test_aot_cache_lru_order():
  cache = AOTExecutableCache(capacity=2)
  cache.warm("a", lambda: 1)
  cache.warm("b", lambda: 2)
  cache.get("a", lambda: 1)        # refresh "a"
  cache.get("c", lambda: 3)        # evicts "b", the least recently used
  assert "a" in cache and "b" not in cache and "c" in cache


# ---------------------------------------------------------------------------
# Admission queue.
# ---------------------------------------------------------------------------


def test_queue_reject_on_full_and_fifo_groups():
  fc = FakeClock()
  q = AdmissionQueue(capacity=3, clock=fc)
  a, b, c, d = _req(3), _req(4), _req(3, op="soft_sort/l2/desc"), _req(5)
  for r in (a, b, c):
    r.bucket_n = 64
    assert q.try_push(r)
  d.bucket_n = 64
  assert not q.try_push(d)                  # bounded: reject, don't grow
  assert q.head_group_size() == 2           # a and b share (op, bucket)
  got = q.pop_group(max_batch=8)
  assert [r.request_id for r in got] == [a.request_id, b.request_id]
  assert len(q) == 1                        # c kept its place


def test_queue_deadline_expiry():
  fc = FakeClock()
  q = AdmissionQueue(capacity=8, clock=fc)
  r1, r2 = _req(3), _req(3)
  r1.submitted_at = fc.t
  r1.deadline_at = fc.t + 0.005
  r2.submitted_at = fc.t                    # no deadline: never expires
  q.try_push(r1)
  q.try_push(r2)
  assert q.expire() == []
  fc.t += 0.006
  expired = q.expire()
  assert [r.request_id for r in expired] == [r1.request_id]
  assert len(q) == 1


# ---------------------------------------------------------------------------
# Engine: admission statuses are typed results, never exceptions.
# ---------------------------------------------------------------------------


def test_engine_shed_queue_full_is_typed():
  eng = ServingEngine(EngineConfig(ops=("soft_rank/l2/desc",), min_bucket=8,
                                   max_bucket=16, max_batch=2,
                                   queue_capacity=2), clock=FakeClock())
  handles = [eng.submit(_req(5)) for _ in range(3)]
  assert not handles[0].done() and not handles[1].done()
  res = handles[2].result(timeout=0)
  assert res.status == STATUS_SHED_QUEUE_FULL and not res.ok
  assert metrics.counter_value("serving_shed", reason="queue_full") == 1
  assert metrics.counter_value("serving_admit", op="soft_rank") == 2


def test_engine_shed_deadline_in_queue():
  fc = FakeClock()
  eng = ServingEngine(EngineConfig(ops=("soft_rank/l2/desc",), min_bucket=8,
                                   max_bucket=16, max_batch=4,
                                   max_wait_ms=1000.0), clock=fc)
  h = eng.submit(_req(5, deadline_ms=5.0))
  fc.t += 0.006
  stepped = eng.step()
  assert [r.status for r in stepped] == [STATUS_SHED_DEADLINE]
  res = h.result(timeout=0)
  assert res.status == STATUS_SHED_DEADLINE
  assert res.latency_us == pytest.approx(6000.0, rel=0.01)
  assert metrics.counter_value("serving_shed", reason="deadline") == 1
  assert len(eng.queue) == 0


def test_engine_invalid_requests_are_typed_errors():
  eng = ServingEngine(EngineConfig(ops=("soft_rank/l2/desc",), min_bucket=8,
                                   max_bucket=16, max_batch=2),
                      clock=FakeClock())
  bad_op = eng.submit(_req(5, op="nope/l2"))
  assert bad_op.result(0).status == STATUS_ERROR
  assert "unknown serving op" in bad_op.result(0).detail
  too_big = eng.submit(_req(999))
  assert too_big.result(0).status == STATUS_ERROR
  assert "exceeds the largest bucket" in too_big.result(0).detail


def test_engine_default_deadline_applies():
  fc = FakeClock()
  eng = ServingEngine(EngineConfig(ops=("soft_rank/l2/desc",), min_bucket=8,
                                   max_bucket=16, max_batch=4,
                                   default_deadline_ms=2.0), clock=fc)
  h = eng.submit(_req(5))
  assert h.deadline_at == pytest.approx(fc.t + 0.002)
  fc.t += 0.003
  eng.step()
  assert h.result(0).status == STATUS_SHED_DEADLINE


# ---------------------------------------------------------------------------
# Engine: batching policy (fake clock; first exec lazily compiles).
# ---------------------------------------------------------------------------


def test_engine_max_wait_and_max_batch_policy():
  fc = FakeClock()
  eng = ServingEngine(EngineConfig(ops=("soft_rank/l2/desc",), min_bucket=8,
                                   max_bucket=8, max_batch=2, impl="lax",
                                   max_wait_ms=10.0), clock=fc)
  h1 = eng.submit(_req(5))
  assert eng.step() == []                  # under-full and not yet due
  assert len(eng.queue) == 1
  fc.t += 0.02                             # past max-wait: due
  res = eng.step()
  assert [r.status for r in res] == [STATUS_OK]
  assert h1.result(0).ok
  assert metrics.counter_value("aot_cache_miss") == 1   # lazy compile
  # A full group launches immediately, no clock advance needed — but a
  # 2-row batch is a different (rows, bucket) cell: second lazy compile.
  h2, h3 = eng.submit(_req(6)), eng.submit(_req(7))
  res = eng.step()
  assert len(res) == 2 and h2.result(0).ok and h3.result(0).ok
  assert metrics.counter_value("aot_cache_miss") == 2
  # The same cell again is a cache hit.
  h4, h5 = eng.submit(_req(3)), eng.submit(_req(8))
  eng.step()
  assert h4.result(0).ok and h5.result(0).ok
  assert metrics.counter_value("aot_cache_hit") == 1
  occ = metrics.histograms("serving_batch_occupancy")
  assert sum(h["count"] for h in occ.values()) == 3     # three batches


# ---------------------------------------------------------------------------
# Engine end-to-end: warmup -> mixed-n stream -> exact results, no misses.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def warm_engine():
  cfg = EngineConfig(ops=("soft_rank/l2/desc", "lts/l2"), min_bucket=8,
                     max_bucket=16, max_batch=2, max_wait_ms=0.0,
                     impl="lax", use_plan_buckets=False)
  eng = ServingEngine(cfg)
  compiled = eng.warmup()
  assert compiled == 2 * 2 * 2             # ops x n-buckets x row-buckets
  return eng


def test_engine_end_to_end_bitwise_and_zero_miss(warm_engine):
  eng = warm_engine
  reqs = [_req(n) for n in (3, 8, 5, 11, 16, 2, 7)]
  results = eng.serve(reqs)
  assert all(r.ok for r in results)
  for req, res in zip(reqs, results):
    ref = np.asarray(soft_rank(jnp.asarray(req.values)[None], req.eps,
                               "l2", "DESCENDING", impl="lax"))[0]
    # The padding contract: sliced-back engine output is bitwise equal
    # to the unpadded operator on the same backend.
    np.testing.assert_array_equal(res.value, ref)
    assert res.n == req.n and res.bucket_n >= req.n
  assert metrics.counter_value("aot_cache_miss") == 0
  assert metrics.counters("aot_cache_hit")      # served from warm cache
  lat = metrics.histograms("serving_latency_us")
  assert sum(h["count"] for h in lat.values()) == len(reqs)


def test_engine_scalar_op_matches_unpadded_loss(warm_engine):
  vals = rng.standard_normal(11).astype(np.float32)
  h = warm_engine.submit(Request(op="lts/l2", values=vals, eps=0.7,
                                 extras={"trim": 3}))
  warm_engine.drain()
  res = h.result(timeout=0)
  assert res.ok
  pin_lax = plan_mod.ExecutionPlan(name="pin-lax", rules=(
      plan_mod.PlanRule("forward", "lax"),))
  ref = float(soft_lts_loss(jnp.asarray(vals), 3, 0.7, "l2", plan=pin_lax))
  assert res.value == pytest.approx(ref, rel=1e-5)


def test_engine_background_thread_smoke(warm_engine):
  warm_engine.start()
  try:
    handles = [warm_engine.submit(_req(n)) for n in (4, 9, 13)]
    results = [h.result(timeout=30.0) for h in handles]
  finally:
    warm_engine.stop()
  assert all(r.ok for r in results)


def test_synthetic_stream_is_deterministic_and_in_range():
  a = synthetic_stream(20, seed=5, n_min=8, n_max=64)
  b = synthetic_stream(20, seed=5, n_min=8, n_max=64)
  assert [r.n for r in a] == [r.n for r in b]
  assert all(8 <= r.n <= 64 for r in a)
  assert all(r.op in SERVING_OPS for r in a)
  np.testing.assert_array_equal(a[0].values, b[0].values)


# ---------------------------------------------------------------------------
# Jit-stable entries.
# ---------------------------------------------------------------------------


def test_stable_entry_identity_and_dispatch():
  f = D.stable_entry("isotonic", "l2", "lax")
  assert f is D.stable_entry("isotonic", "l2", "lax")
  assert f is not D.stable_entry("isotonic", "l2", "scan")
  assert D.stable_entry("isotonic", "l2", "segscan", kind="backward") is \
      D.stable_entry("isotonic", "l2", "segscan", kind="backward")
  with pytest.raises(ValueError, match="kind"):
    D.stable_entry("isotonic", "l2", "lax", kind="projection")
  y = jnp.asarray(rng.standard_normal((2, 9)).astype(np.float32))
  np.testing.assert_array_equal(
      np.asarray(jax.jit(f)(y)),
      np.asarray(D.dispatch("isotonic", "l2", "lax", y)))


def test_stable_entry_distinguishes_plans():
  plan = plan_mod.ExecutionPlan(name="p", rules=(
      plan_mod.PlanRule("forward", "lax"),))
  f_plain = D.stable_entry("isotonic", "l2")
  f_plan = D.stable_entry("isotonic", "l2", plan=plan)
  assert f_plain is not f_plan
  assert f_plan is D.stable_entry("isotonic", "l2", plan=plan)


def test_bound_op_identity():
  assert bound_op("soft_rank/l2/desc", "lax", None) is \
      bound_op("soft_rank/l2/desc", "lax", None)
  assert bound_op("soft_rank/l2/desc", "lax", None) is not \
      bound_op("soft_rank/l2/desc", "scan", None)


@pytest.mark.parametrize("impl", ["lax", "scan"])
def test_warmup_compiles_each_cell_once(impl):
  cfg = EngineConfig(ops=("soft_rank/l2/desc",), min_bucket=8,
                     max_bucket=16, max_batch=2, impl=impl,
                     use_plan_buckets=False)
  eng = ServingEngine(cfg)
  assert eng.warmup() == 2 * 2   # n-buckets x row-buckets
  assert eng.warmup() == 0       # every cell already warm
  assert len(eng.cache) == 4
  assert metrics.counter_value("aot_cache_warm") == 4
  res = eng.serve([_req(n) for n in (3, 12)])
  assert all(r.ok for r in res)
  assert metrics.counter_value("aot_cache_miss") == 0
