"""Observability layer: metrics counters per dispatch, disabled-mode
statelessness, BENCH artifact schema round-trip, named_scope attribution
in compiled HLO, and the REPRO_BACKEND validation fix."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import soft_rank
from repro.kernels import dispatch as D
from repro.obs import artifacts, metrics

rng = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def clean_registry():
  """Each test starts from an empty, enabled registry and ends reset."""
  metrics.set_enabled(True)
  metrics.reset()
  yield
  metrics.set_enabled(None)
  metrics.reset()


# ---------------------------------------------------------------------------
# Counters increment per dispatch.
# ---------------------------------------------------------------------------


def test_counters_increment_per_dispatch():
  x = jnp.array(rng.normal(size=(3, 8)).astype(np.float32))
  for _ in range(2):
    soft_rank(x, 0.5, "l2", impl="lax")
  c = metrics.counters()
  assert c["dispatch_calls{backend=lax,op=isotonic,regularization=l2}"] == 2
  assert c["dispatch_resolve{backend=lax,op=isotonic,"
           "regularization=l2,source=arg}"] == 2
  # the identical (shape, dtype, backend) key: 1 miss then 1 hit
  assert c["dispatch_trace_cache_miss"] == 1
  assert c["dispatch_trace_cache_hit"] == 1
  # shape buckets recorded (3 rows <= 2^2, n=8 <= 2^3)
  assert c["dispatch_shape{bucket=r2^2_n2^3,op=isotonic}"] == 2


def test_plan_decide_counter_labels_kind_source_and_plan():
  from repro import plan as plan_mod
  D.resolve_backend("isotonic", "l2", None, shape=(4, 9), platform="cpu")
  D.resolve_backend("isotonic", "l2", None, shape=(4, 9), platform="tpu")
  with plan_mod.use_plan(plan_mod.ExecutionPlan(
      name="pinned", rules=(plan_mod.PlanRule("forward", "lax"),))):
    D.resolve_backend("isotonic", "l2", None, shape=(4, 9), platform="cpu")
  c = metrics.counters("plan_decide")
  # cpu routes through the committed autotuned default plan (small-n,
  # few-row cells measure fastest on lax); tpu is not measured there,
  # so it falls through to the builtin tpu -> dense rule.
  assert c["plan_decide{backend=lax,kind=forward,"
           "plan=autotuned-cpu,source=default_plan}"] == 1
  assert c["plan_decide{backend=dense,kind=forward,"
           "plan=builtin,source=builtin}"] == 1
  assert c["plan_decide{backend=lax,kind=forward,"
           "plan=pinned,source=plan}"] == 1


# ---------------------------------------------------------------------------
# Disabled mode records no state.
# ---------------------------------------------------------------------------


def test_disabled_mode_records_no_state():
  metrics.set_enabled(False)
  x = jnp.array(rng.normal(size=(2, 6)).astype(np.float32))
  soft_rank(x, 0.5, "l2", impl="lax")
  jax.grad(lambda t: jnp.sum(soft_rank(t, 0.5, "kl", impl="minimax")))(x)
  assert metrics.counters() == {}
  assert metrics.histograms() == {}
  assert D._SEEN_TRACE_KEYS == {}
  snap = metrics.snapshot()
  assert snap == {"enabled": False, "counters": {}, "histograms": {}}


def test_disabling_drops_previously_recorded_state():
  metrics.counter_inc("x", y="z")
  assert metrics.counters()
  metrics.set_enabled(False)
  assert metrics.counters() == {}


def test_env_var_gates_metrics(monkeypatch):
  metrics.set_enabled(None)  # defer to environment
  monkeypatch.setenv(metrics.ENV_VAR, "0")
  assert not metrics.enabled()
  metrics.counter_inc("nope")
  assert metrics.counters() == {}
  monkeypatch.setenv(metrics.ENV_VAR, "1")
  assert metrics.enabled()


# ---------------------------------------------------------------------------
# Artifact schema round-trip.
# ---------------------------------------------------------------------------


def test_artifact_roundtrips_against_schema(tmp_path):
  x = jnp.array(rng.normal(size=(2, 16)).astype(np.float32))
  soft_rank(x, 0.5, "l2", impl="lax")   # populate dispatch counters
  results = [
      {"name": "t/a", "fwd_us": 12.5, "n": 16, "batch": 2,
       "backend": "lax"},
      {"name": "t/b", "skipped": "infeasible on cpu"},
      {"name": "t/c", "wall_us": 0.0},
  ]
  path = tmp_path / "BENCH_test.json"
  payload = artifacts.write_bench_artifact(
      str(path), results, artifacts.collect_meta(suite="test"))
  assert artifacts.validate_bench_payload(payload) == []
  loaded = json.loads(path.read_text())
  assert loaded == json.loads(json.dumps(payload))  # JSON-stable
  assert artifacts.validate_file(str(path)) == []
  assert loaded["schema"] == artifacts.SCHEMA_VERSION
  assert any(k.startswith("dispatch_resolve")
             for k in loaded["metrics"]["counters"])
  assert loaded["meta"]["platform"] == jax.default_backend()


@pytest.mark.parametrize("mutate,fragment", [
    (lambda p: p.pop("schema"), "schema"),
    (lambda p: p["meta"].pop("git_sha"), "git_sha"),
    (lambda p: p.pop("metrics"), "metrics"),
    (lambda p: p["results"].append({"name": "x"}), "_us"),
    (lambda p: p["results"].append({"name": "x", "fwd_us": float("nan")}),
     "finite"),
    (lambda p: p["results"].append({"fwd_us": 1.0}), "name"),
    (lambda p: p["results"].append({"name": "x", "skipped": ""}), "skipped"),
])
def test_validator_rejects_malformed_payloads(mutate, fragment):
  payload = artifacts.bench_payload(
      [{"name": "ok", "fwd_us": 1.0}], artifacts.collect_meta())
  assert artifacts.validate_bench_payload(payload) == []
  mutate(payload)
  errors = artifacts.validate_bench_payload(payload)
  assert errors and any(fragment in e for e in errors), errors


def test_writer_refuses_invalid_results(tmp_path):
  with pytest.raises(ValueError, match="refusing to write"):
    artifacts.write_bench_artifact(
        str(tmp_path / "BENCH_bad.json"), [{"name": "no-timing"}])
  assert not (tmp_path / "BENCH_bad.json").exists()


def test_validator_cli(tmp_path, capsys):
  good = tmp_path / "BENCH_good.json"
  artifacts.write_bench_artifact(str(good), [{"name": "a", "fwd_us": 1.0}])
  bad = tmp_path / "BENCH_bad.json"
  bad.write_text("{}")
  assert artifacts.main([str(good)]) == 0
  assert artifacts.main([str(good), str(bad)]) == 1
  assert artifacts.main([]) == 2


# ---------------------------------------------------------------------------
# named_scope attribution in compiled HLO for a jitted soft_rank.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["lax", "minimax"])
def test_named_scope_label_in_compiled_hlo(backend):
  from repro.obs.tracing import scope_name
  x = jnp.array(rng.normal(size=(2, 7)).astype(np.float32))
  f = jax.jit(lambda t: soft_rank(t, 0.5, "l2", impl=backend))
  hlo = f.lower(x).compile().as_text()
  assert scope_name("isotonic", "l2", backend) in hlo


def test_scope_name_is_sanitized():
  from repro.obs.tracing import scope_name
  assert scope_name("isotonic", "l2", "lax") == "repro_isotonic_l2_lax"
  assert scope_name("Iso/Tonic", "L-2", "") == "repro_iso_tonic_l_2_unknown"


# ---------------------------------------------------------------------------
# REPRO_BACKEND validation (read-time, clear error).
# ---------------------------------------------------------------------------


def test_unknown_env_backend_raises_clear_error(monkeypatch):
  monkeypatch.setenv(D.ENV_VAR, "cuda")
  with pytest.raises(ValueError, match="REPRO_BACKEND='cuda'"):
    D.resolve_backend("isotonic", "l2", None, shape=(4, 9))


def test_explicit_backend_bypasses_invalid_env(monkeypatch):
  monkeypatch.setenv(D.ENV_VAR, "bogus")
  assert D.resolve_backend("isotonic", "l2", "lax", shape=(4, 9)) == "lax"


def test_valid_env_backend_still_works(monkeypatch):
  monkeypatch.setenv(D.ENV_VAR, "minimax")
  assert D.resolve_backend("isotonic", "l2", None, shape=(4, 500)) == "minimax"


# ---------------------------------------------------------------------------
# REPRO_BACKWARD / REPRO_PROJECTION validation (same read-time contract).
# ---------------------------------------------------------------------------


def test_unknown_env_backward_raises_clear_error(monkeypatch):
  monkeypatch.setenv(D.BWD_ENV_VAR, "cuda")
  with pytest.raises(ValueError, match="REPRO_BACKWARD='cuda'"):
    D.resolve_backward("isotonic", "l2", None, shape=(4, 9))


def test_explicit_backward_bypasses_invalid_env(monkeypatch):
  monkeypatch.setenv(D.BWD_ENV_VAR, "bogus")
  assert D.resolve_backward("isotonic", "l2", "segscan",
                            shape=(4, 9)) == "segscan"


def test_valid_env_backward_still_works(monkeypatch):
  monkeypatch.setenv(D.BWD_ENV_VAR, "scatter")
  assert D.resolve_backward("isotonic", "l2", None, shape=(4, 9)) == "scatter"


def test_unknown_env_projection_raises_clear_error(monkeypatch):
  monkeypatch.setenv(D.PROJECTION_ENV_VAR, "vectorized")
  with pytest.raises(ValueError, match="REPRO_PROJECTION='vectorized'"):
    D.resolve_projection(None, "l2", shape=(4, 9))


def test_explicit_projection_bypasses_invalid_env(monkeypatch):
  monkeypatch.setenv(D.PROJECTION_ENV_VAR, "bogus")
  assert D.resolve_projection("composed", "l2", shape=(4, 9)) == "composed"


def test_valid_env_projection_still_works(monkeypatch):
  monkeypatch.setenv(D.PROJECTION_ENV_VAR, "fused")
  assert D.resolve_projection(None, "l2", shape=(4, 9)) == "fused"
