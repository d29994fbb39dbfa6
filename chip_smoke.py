"""Run the system's main path once on one TPU chip and check what comes out.

    python chip_smoke.py [--seed 0]

One process, three phases, in this order:

* operators -- ``soft_rank``, ``soft_sort`` and ``soft_topk_mask`` from
  ``repro.core``, each with l2 and kl regularization, forward and
  ``jax.grad`` under ``jit`` at (256, 4096) and (1, 131072), with no
  ``impl=`` or ``plan=``.  Forward values on a few rows must match a
  float64 NumPy reference written here (sort, pool adjacent violators,
  unpermute); gradients must match the ``scatter`` backward formulation.
* server -- the ``repro.serving`` engine with soft_rank, soft_sort and
  soft_topk ops, buckets 64..4096 and ``max_batch=32``: warmup, then a
  256-request synthetic stream.  Every result must be ``ok``, no
  executable may compile after warmup, and sliced results must match the
  unpadded operator.
* trainer -- ``repro.launch.train.Trainer`` on llama3.2-1b at full width
  (16 layers, d_model 2048, vocab 128256) with the soft-LTS token loss on,
  3 steps from random weights made from ``--seed``.  Every loss and
  gradient norm must be finite.

After each phase the ``plan_decide`` counters must show forward ``dense``,
backward ``segscan`` and projection ``sortperm``, and ``pallas`` nowhere.

The script exits non-zero before any phase when JAX finds no TPU, and on
any failed check.  Times are printed for information only.  The last line
of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

OPS = ("soft_rank", "soft_sort", "soft_topk_mask")
REGS = ("l2", "kl")
OP_SHAPES = ((256, 4096), (1, 131072))
CHECK_ROWS = {(256, 4096): (0, 128, 255), (1, 131072): (0,)}
SCATTER_PLAN_NAME = "smoke-scatter-backward"
# float32 against float64: forward error over max(1, |z|, |out|), gradient
# error over max |g|.  Block sums and log-sum-exps over up to 131072
# entries in float32 land near 3e-5 of scale on CPU.
FWD_RTOL = 1e-4
GRAD_RTOL = 1e-4

ENGINE_OPS = ("soft_rank/l2/desc", "soft_sort/l2/desc", "soft_topk/l2")
ENGINE_REQUESTS = 256
# Padding is exact, so a sliced result equals the unpadded operator up to
# float32 rounding in a differently shaped program.
SERVE_RTOL = 1e-5

TRAIN_ARCH = "llama3.2-1b"
TRAIN_BATCH = 4
TRAIN_SEQ = 1024
TRAIN_STEPS = 3
TRAIN_TRIM = 0.1


class SmokeFailure(RuntimeError):
  """A check of this script failed."""


def check(cond, what: str) -> None:
  if not cond:
    raise SmokeFailure(what)


def log(msg: str) -> None:
  print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# float64 NumPy reference: sort, pool adjacent violators, unpermute.
# ---------------------------------------------------------------------------


def _pav_l2_np(y: np.ndarray) -> tuple[np.ndarray, int]:
  """Non-increasing isotonic regression of y (least squares); returns the
  fit and its number of blocks."""
  sums, counts = [], []
  for yi in y.tolist():
    s, c = yi, 1
    while sums and sums[-1] / counts[-1] <= s / c:
      s += sums.pop()
      c += counts.pop()
    sums.append(s)
    counts.append(c)
  return np.repeat(np.array(sums) / np.array(counts), counts), len(counts)


def _logaddexp(a: float, b: float) -> float:
  m = max(a, b)
  return m + math.log(math.exp(a - m) + math.exp(b - m))


def _pav_kl_np(s: np.ndarray, w: np.ndarray) -> tuple[np.ndarray, int]:
  """Non-increasing entropic isotonic fit, block value LSE(s) - LSE(w);
  returns the fit and its number of blocks."""
  lse_s, lse_w, counts = [], [], []
  for si, wi in zip(s.tolist(), w.tolist()):
    a, b, c = si, wi, 1
    while lse_s and lse_s[-1] - lse_w[-1] <= a - b:
      a = _logaddexp(a, lse_s.pop())
      b = _logaddexp(b, lse_w.pop())
      c += counts.pop()
    lse_s.append(a)
    lse_w.append(b)
    counts.append(c)
  return np.repeat(np.array(lse_s) - np.array(lse_w), counts), len(counts)


def projection_np(z: np.ndarray, w: np.ndarray,
                  reg: str) -> tuple[np.ndarray, int]:
  """P_Psi(z, w) = z - v(z sorted, w sorted)[sigma^-1] in float64, and
  the number of blocks the isotonic fit pooled the n entries into."""
  sigma = np.argsort(-z, kind="stable")
  s = z[sigma]
  ws = np.sort(w)[::-1]
  v, blocks = _pav_l2_np(s - ws) if reg == "l2" else _pav_kl_np(s, ws)
  out = np.empty_like(z)
  out[sigma] = s - v
  return out, blocks


def op_eps(op: str, n: int) -> float:
  """A strength at which each operator pools some entries and not all."""
  return {"soft_rank": 4.0 / n, "soft_sort": n / 4.0,
          "soft_topk_mask": 0.1}[op]


def topk_k(n: int) -> int:
  return n // 8


def op_reference(op: str, reg: str,
                 x: np.ndarray) -> tuple[np.ndarray, float, int]:
  """One row of the operator from the paper's definitions, in float64.

  Returns (output, max |z| of the projected point, number of blocks).
  The output is z - v, so float32 rounding in it scales with max |z|."""
  x = x.astype(np.float64)
  n = x.shape[-1]
  eps = op_eps(op, n)
  rho = np.arange(n, 0, -1, dtype=np.float64)
  if op == "soft_rank":
    z, w = -x / eps, rho
  elif op == "soft_sort":
    z, w = rho / eps, x
  else:
    z, w = x / eps, np.where(np.arange(n) < topk_k(n), 1.0, 0.0)
  out, blocks = projection_np(z, w, reg)
  return out, float(np.abs(z).max()), blocks


# ---------------------------------------------------------------------------
# Programs.
# ---------------------------------------------------------------------------


def op_program(op: str, reg: str, n: int, plan=None):
  """(x, u) -> (op(x), d<op(x), u>/dx), for jit."""
  import jax
  import jax.numpy as jnp
  from repro import core

  eps = op_eps(op, n)

  def forward(t):
    if op == "soft_topk_mask":
      return core.soft_topk_mask(t, topk_k(n), eps, reg, plan=plan)
    return getattr(core, op)(t, eps, reg, plan=plan)

  def program(x, u):
    def objective(t):
      y = forward(t)
      return jnp.sum(y * u), y
    g, y = jax.grad(objective, has_aux=True)(x)
    return y, g

  return program


def scatter_backward_plan():
  from repro.plan import ExecutionPlan, PlanRule
  return ExecutionPlan(name=SCATTER_PLAN_NAME,
                       rules=(PlanRule("backward", "scatter"),))


def engine_config():
  from repro.serving import EngineConfig
  return EngineConfig(ops=ENGINE_OPS, min_bucket=64, max_bucket=4096,
                      max_batch=32)


def train_configs():
  """llama3.2-1b at full width, soft-LTS on, sized for one 16 GB chip.

  Returns (arch config, optimizer config, reductions from the published
  training setup)."""
  from repro.configs.base import get_config
  from repro.optim import adamw

  base = get_config(TRAIN_ARCH)
  cfg = dataclasses.replace(base, loss_trim_fraction=TRAIN_TRIM,
                            grad_accum=1)
  opt_cfg = adamw.AdamWConfig(moment_dtype="bfloat16")
  reductions = [
      f"grad_accum {base.grad_accum} -> 1",
      f"AdamW moments {adamw.AdamWConfig().moment_dtype} -> bfloat16",
      f"batch x seq = {TRAIN_BATCH} x {TRAIN_SEQ} "
      f"(soft-LTS row n = {TRAIN_BATCH * TRAIN_SEQ})",
      f"{TRAIN_STEPS} steps from random weights",
  ]
  return cfg, opt_cfg, reductions


# ---------------------------------------------------------------------------
# Counters.
# ---------------------------------------------------------------------------


def _labels(key: str) -> dict[str, str]:
  inner = key[key.index("{") + 1:key.rindex("}")]
  return dict(kv.split("=", 1) for kv in inner.split(","))


def check_routes(phase: str) -> None:
  """Every decision so far: forward dense, backward segscan, projection
  sortperm; the scatter reference plan is the only other backward route."""
  from repro.obs import metrics

  want = {"forward": "dense", "backward": "segscan",
          "projection": "sortperm"}
  decided = metrics.counters("plan_decide")
  check(decided, f"{phase}: no plan_decide counters were recorded")
  for key, count in sorted(decided.items()):
    lab = _labels(key)
    ok = lab["backend"] == want[lab["kind"]] or (
        lab["kind"] == "backward" and lab["backend"] == "scatter"
        and lab["plan"] == SCATTER_PLAN_NAME)
    check(ok, f"{phase}: unexpected route {key}")
  calls = metrics.counters("dispatch_calls")
  check(not any("pallas" in k for k in calls),
        f"{phase}: a pallas backend was dispatched: {calls}")
  log(f"{phase}: plan_decide " + json.dumps(decided, sort_keys=True))


def _counter_delta(before: dict, after: dict) -> dict:
  return {k: v - before.get(k, 0) for k, v in after.items()
          if v - before.get(k, 0)}


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------


def compile_all(lowered: list) -> tuple[list, float]:
  """Compile lowered programs on the serving engine's pool size (XLA
  releases the GIL while it compiles); returns the executables and the
  wall time."""
  from repro.serving.engine import COMPILE_WORKERS

  t0 = time.perf_counter()
  with concurrent.futures.ThreadPoolExecutor(COMPILE_WORKERS) as pool:
    exes = list(pool.map(lambda low: low.compile(), lowered))
  return exes, time.perf_counter() - t0


def lower_operator_programs(args_by_shape: dict) -> list:
  """[(shape, op, reg, program, scatter-backward program)], lowered.

  ``args_by_shape`` maps each of OP_SHAPES to the (x, u) pair to lower
  with: arrays, or shape structs naming a device."""
  import jax

  scatter_plan = scatter_backward_plan()
  out = []
  for shape in OP_SHAPES:
    x, u = args_by_shape[shape]
    for op in OPS:
      for reg in REGS:
        out.append((shape, op, reg,
                    jax.jit(op_program(op, reg, shape[-1])).lower(x, u),
                    jax.jit(op_program(op, reg, shape[-1],
                                       plan=scatter_plan)).lower(x, u)))
  return out


def phase_operators(seed: int) -> None:
  import jax
  import jax.numpy as jnp

  rng = np.random.default_rng(seed)
  host = {shape: (rng.standard_normal(shape).astype(np.float32),
                  rng.standard_normal(shape).astype(np.float32))
          for shape in OP_SHAPES}
  args = {shape: (jnp.asarray(x), jnp.asarray(u))
          for shape, (x, u) in host.items()}
  t0 = time.perf_counter()
  cases = lower_operator_programs(args)
  t_lower = time.perf_counter() - t0
  exes, t_compile = compile_all(
      [low for case in cases for low in case[3:]])
  log(f"operators: traced {len(exes)} programs in {t_lower:.1f}s, "
      f"compiled them in {t_compile:.1f}s")
  for i, (shape, op, reg, _, _) in enumerate(cases):
    exe, ref_exe = exes[2 * i], exes[2 * i + 1]
    name = f"{op}/{reg} {shape[0]}x{shape[-1]}"
    check("tpu_custom_call" not in exe.as_text(),
          f"{name}: the compiled program holds a Pallas kernel")
    x, u = args[shape]
    t0 = time.perf_counter()
    y, g = jax.block_until_ready(exe(x, u))
    t_run = time.perf_counter() - t0
    _, g_ref = ref_exe(x, u)
    y, g, g_ref = np.asarray(y), np.asarray(g), np.asarray(g_ref)
    check(y.shape == shape and g.shape == shape,
          f"{name}: shapes {y.shape}, {g.shape}")
    check(np.isfinite(y).all() and np.isfinite(g).all(),
          f"{name}: non-finite values")
    worst_y = worst_g = 0.0
    blocks = []
    for r in CHECK_ROWS[shape]:
      ref, zmax, nb = op_reference(op, reg, host[shape][0][r])
      scale = max(1.0, zmax, float(np.abs(ref).max()))
      err = float(np.abs(y[r] - ref).max()) / scale
      check(err <= FWD_RTOL, f"{name} row {r}: forward differs from "
            f"the float64 reference by {err:.3g} of its scale")
      gscale = max(1e-6, float(np.abs(g_ref[r]).max()))
      gerr = float(np.abs(g[r] - g_ref[r]).max()) / gscale
      check(gerr <= GRAD_RTOL, f"{name} row {r}: gradient differs from "
            f"the scatter backward by {gerr:.3g} of max|g|")
      worst_y, worst_g = max(worst_y, err), max(worst_g, gerr)
      blocks.append(nb)
    log(f"operators: {name} first run {t_run * 1e3:.1f}ms "
        f"fwd-vs-f64 {worst_y:.2e} grad-vs-scatter {worst_g:.2e} "
        f"blocks {blocks}")
  check_routes("operators")


def lower_unpadded(req):
  """The operator a served request asks for, lowered at its own size."""
  import jax
  import jax.numpy as jnp
  from repro import core

  op, reg = req.op.split("/")[:2]
  if op == "soft_topk":
    fn = lambda v: core.soft_topk_mask(v, req.extras["k"], req.eps, reg)
  else:
    fn = lambda v: getattr(core, op)(v, req.eps, reg, "DESCENDING")
  return jax.jit(fn).lower(jnp.asarray(req.values))


def phase_server(seed: int) -> None:
  from repro.obs import metrics
  from repro.serving import ServingEngine, synthetic_stream

  engine = ServingEngine(engine_config())
  t0 = time.perf_counter()
  compiled = engine.warmup()
  log(f"server: warmup compiled {compiled} executables "
      f"({len(engine.policy.sizes)} buckets {engine.policy.sizes} x "
      f"{len(engine.policy.row_sizes)} row sizes x {len(ENGINE_OPS)} ops) "
      f"in {time.perf_counter() - t0:.1f}s")
  requests = synthetic_stream(ENGINE_REQUESTS, seed=seed, ops=ENGINE_OPS,
                              n_min=64, n_max=4096)
  before = metrics.counters("aot_cache")
  t0 = time.perf_counter()
  results = engine.serve(requests)
  wall = time.perf_counter() - t0
  delta = _counter_delta(before, metrics.counters("aot_cache"))
  bad = [r for r in results if not r.ok]
  check(len(results) == ENGINE_REQUESTS,
        f"server: {len(results)} results for {ENGINE_REQUESTS} requests")
  check(not bad, f"server: {len(bad)} results not ok, first: "
        f"{bad[0].status if bad else ''} {bad[0].detail if bad else ''}")
  misses = delta.get("aot_cache_miss", 0)
  check(misses == 0, f"server: {misses} aot_cache_miss after warmup")
  log(f"server: {len(results)} requests all ok in {wall:.3f}s; "
      f"aot_cache after warmup {json.dumps(delta, sort_keys=True)}")

  # The smallest and the largest request of each op, against the operator
  # run unpadded at the request's own size.
  picks = []
  for key in ENGINE_OPS:
    mine = sorted((req.n, i) for i, req in enumerate(requests)
                  if req.op == key)
    check(mine, f"server: the stream holds no {key} request")
    picks += sorted({mine[0][1], mine[-1][1]})
  exes, t_compile = compile_all([lower_unpadded(requests[i]) for i in picks])
  bitwise = 0
  for i, exe in zip(picks, exes):
    req = requests[i]
    want = np.asarray(exe(req.values))
    got = np.asarray(results[i].value)
    check(got.shape == want.shape, f"server: request {req.request_id} "
          f"shape {got.shape} vs {want.shape}")
    scale = max(1.0, float(np.abs(want).max()))
    err = float(np.abs(got - want).max()) / scale
    check(err <= SERVE_RTOL, f"server: {req.op} n={req.n} sliced result "
          f"differs from the unpadded operator by {err:.3g} of max|want|")
    bitwise += int(np.array_equal(got, want))
  log(f"server: {len(picks)} sliced results (n = "
      f"{sorted(requests[i].n for i in picks)}) match the unpadded operator, "
      f"{bitwise} bitwise; their programs compiled in {t_compile:.1f}s")
  check_routes("server")


def phase_trainer(seed: int) -> None:
  import jax
  from repro.launch.train import Trainer
  from repro.obs import metrics

  cfg, opt_cfg, _ = train_configs()
  trainer = Trainer(cfg, opt_cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                    ckpt_dir=None, total_steps=TRAIN_STEPS, seed=seed)
  t0 = time.perf_counter()
  state = trainer.init_or_restore()
  jax.block_until_ready(state.params)
  log(f"trainer: init {time.perf_counter() - t0:.1f}s")
  before = metrics.counters("dispatch")
  for _ in range(TRAIN_STEPS):
    state, m = trainer.run(state, 1)
    loss, gnorm = float(m["loss"]), float(m["grad_norm"])
    check(math.isfinite(loss) and math.isfinite(gnorm),
          f"trainer: step {state.step} loss {loss} grad_norm {gnorm}")
  check(state.step == TRAIN_STEPS, f"trainer: stopped at step {state.step}")
  delta = _counter_delta(before, metrics.counters("dispatch"))
  lts = {k: v for k, v in delta.items() if "op=projection" in k
         or "op=isotonic" in k}
  check(any("dispatch_calls{" in k and "backend=dense" in k for k in lts)
        and any("dispatch_bwd_calls{" in k for k in lts),
        f"trainer: no soft-LTS dispatch in the counters: {delta}")
  times = ", ".join(f"{t:.3f}s" for t in trainer._step_times)
  log(f"trainer: step times {times} (the first includes compilation)")
  log(f"trainer: soft-LTS dispatch {json.dumps(lts, sort_keys=True)}")
  stats = jax.devices()[0].memory_stats() or {}
  if "peak_bytes_in_use" in stats:
    log(f"trainer: peak device memory "
        f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB")
  check_routes("trainer")


# ---------------------------------------------------------------------------
# Entry point.
# ---------------------------------------------------------------------------


def _tpu_or_exit():
  import jax
  try:
    devices = jax.devices()
  except RuntimeError as e:
    sys.exit(f"chip_smoke: no TPU found: {e}")
  if devices[0].platform != "tpu":
    sys.exit(f"chip_smoke: no TPU found (JAX reports platform "
             f"{devices[0].platform!r}); there is no CPU fallback")
  return devices


def main() -> None:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--seed", type=int, default=0)
  args = ap.parse_args()

  devices = _tpu_or_exit()
  if not os.path.isdir(os.path.join(SRC, "repro")):
    sys.exit(f"chip_smoke: no src/repro beside {__file__}")
  sys.path.insert(0, SRC)
  from repro.launch.compile_cache import enable_compile_cache

  dev = devices[0]
  log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
      f"compile cache {enable_compile_cache()}; seed {args.seed}")
  _, _, reductions = train_configs()
  log(f"trainer reductions for one chip: {'; '.join(reductions)}")

  for name, phase in (("operators", phase_operators),
                      ("server", phase_server),
                      ("trainer", phase_trainer)):
    t0 = time.perf_counter()
    phase(args.seed)
    log(f"{name}: phase ok in {time.perf_counter() - t0:.1f}s")

  print(json.dumps({"ok": True, "device": {
      "platform": dev.platform, "kind": dev.device_kind,
      "count": len(devices)}}))


if __name__ == "__main__":
  main()
