"""Model FLOPs utilization of the whole step: model FLOPs per step
(``chipbench.counts.train_flops``) times the steps of the traced window,
over the window's length times the chip's bf16 peak."""


def read(record):
  t, w = record.trace, record.window
  if t is None or not w.attempted or not w.flops_per_step:
    return None
  flops = w.flops_per_step * w.attempted
  return 100.0 * flops / (t.window_s * record.peaks["bf16_flops_per_s"])
