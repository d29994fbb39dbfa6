"""Device ms per step under ``repro_moe_experts``: the held experts'
grouped matrix products (the compiler's ``ragged-dot-*`` custom calls)
and what runs between them, forward, backward and recomputation."""

from chipbench import scope_time


def read(record):
  return scope_time.ms_per_step(record, "repro_moe_experts",
                                scope_time.EXPERT_PRODUCTS)
