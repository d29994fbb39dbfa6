"""Device ms per step under ``repro_moe_dispatch``: the sort by held
expert, the row gathers to and from expert order and the combine, forward,
backward and recomputation."""

from chipbench import scope_time


def read(record):
  return scope_time.ms_per_step(record, "repro_moe_dispatch")
