"""The trace reduction: scope attribution, self times, busy time and
idle gaps, on HLO text and on a trace recorded on a TPU v5e (three steps
of the rank-loss cell's program, ``chipbench/tests/data``)."""

import gzip
import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

HLO = """HloModule jit_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(f32[4]{0} %p), metadata={op_name="jit(step)/jvp(repro_projection_l2_fused)/repro_isotonic_l2_scan/neg"}
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %sort.3 = f32[4]{0} sort(f32[4]{0} %x), dimensions={0}, metadata={op_name="jit(step)/jvp(repro_projection_l2_fused)/sort"}
  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %sort.3), kind=kLoop, calls=%fused_computation.1
  ROOT %add.2 = f32[4]{0} add(%fusion.1, %fusion.1), metadata={op_name="jit(step)/transpose(jvp(repro_projection_l2_fused))/repro_projection_bwd_l2_segscan/add"}
}
"""


def test_op_names_from_hlo_text():
  names = trace.hlo_op_names(HLO)
  assert names["sort.3"].endswith("repro_projection_l2_fused)/sort")
  # A fusion without metadata takes its fused computation's root's.
  assert "repro_isotonic_l2_scan" in names["fusion.1"]
  assert trace.instruction("%fusion.1 = f32[4]{0} fusion(...)") == "fusion.1"


def test_layers_by_scope():
  names = trace.hlo_op_names(HLO)
  op = lambda inst: trace.Op(inst, 0, 1, names[inst], "d")
  assert trace.kind(op("sort.3")) == "projection"
  assert trace.kind(op("fusion.1")) == "isotonic"
  assert trace.kind(op("add.2")) == "isotonic_bwd"
  assert trace.kind(trace.Op("dot.1", 0, 1, "jit(step)/dot", "d")) == ""


def test_self_times_split_nested_and_overlapping_ops():
  # A loop (0-100) holding two ops, the second running past its end;
  # then a lone op.
  spans = [(0, 100), (10, 30), (90, 120), (130, 140)]
  assert trace.self_times(spans) == [70, 20, 30, 10]
  assert sum(trace.self_times(spans)) == trace._union_ns(spans)


def test_idle_gaps_are_named_by_the_host():
  devices = {"d": [(0, 10, "a"), (40, 50, "b")]}
  host = [(10, 40, "feed"), (0, 100, "chipbench_window")]
  gaps = trace._idle_gaps(devices, host, 0, 100)
  assert [g[0] for g in gaps] == ["chipbench_window", "feed"]
  assert [g[1] for g in gaps] == pytest.approx([50e-9, 30e-9])


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
  path = tmp_path_factory.mktemp("trace") / "rank.xplane.pb"
  with gzip.open(os.path.join(DATA, "rank_loss_3_steps.xplane.pb.gz")) as f:
    path.write_bytes(f.read())
  with gzip.open(os.path.join(DATA, "rank_loss_3_steps.hlo.txt.gz")) as f:
    hlo = f.read().decode()
  # The three steps of the trace: 47.0128 ms to 274.05 ms on its clock.
  return trace.reduce(str(path), [hlo], window_ns=(47012761.0, 274052734.0))


def test_recorded_trace_adds_up(recorded):
  layers = {k: recorded.seconds(k) / 3 * 1e3
            for k in ("projection", "isotonic", "isotonic_bwd", "")}
  # Self times split the busy time without loss or double counting.
  assert sum(layers.values()) == pytest.approx(recorded.busy_s / 3 * 1e3)
  assert recorded.busy_s / recorded.window_s > 0.99
  # The scan solve takes most of a 75.7 ms step.
  assert layers["isotonic"] == pytest.approx(62.0, abs=0.1)
  assert layers["projection"] == pytest.approx(10.76, abs=0.05)
  assert layers["isotonic_bwd"] == pytest.approx(2.69, abs=0.05)
  assert layers[""] < 0.5
  top = recorded.breakdown()["device_ops"][0]
  assert top[0] == "repro_isotonic_l2_scan:fusion"
