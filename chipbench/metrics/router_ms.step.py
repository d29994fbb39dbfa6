"""Device ms per step under ``repro_moe_router``: the MoE router (logits,
softmax, the soft top-k with its projection, isotonic solve and VJP, the
top-k choice), forward, backward and recomputation."""

from chipbench import scope_time


def read(record):
  return scope_time.ms_per_step(record, "repro_moe_router")
