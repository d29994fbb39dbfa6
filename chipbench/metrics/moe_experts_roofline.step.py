"""The held experts' share of their roofline: the forward and backward
FLOPs of every assignment the held experts computed in the window (the
step's ``moe_assignments{where=held}`` counter times
``chipbench.moe_counts.expert_flops_per_assignment``), over their device
time (the ops ``moe_experts_ms.step`` reads) times the chip's bf16 peak.
The grouped products are bound by compute: at 768 rows an expert is past
the v5e's ridge.  None where the program keeps no such counter."""

from chipbench import moe_counts, scope_time

HELD = "moe_assignments{where=held}"


def read(record):
  t, w = record.trace, record.window
  if t is None or not w.histograms.get(HELD):
    return None
  device_s = scope_time.seconds(t, "repro_moe_experts",
                                scope_time.EXPERT_PRODUCTS)
  if device_s <= 0:
    return None
  flops = w.histograms[HELD] * moe_counts.expert_flops_per_assignment(
      record.config)
  return 100.0 * flops / (device_s * record.peaks["bf16_flops_per_s"])
