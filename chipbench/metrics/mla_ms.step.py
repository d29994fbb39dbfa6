"""Device ms per step under ``repro_mla``: multi-head latent attention
(projections, the latent's norm, rope and attention), forward, backward
and recomputation."""

from chipbench import scope_time


def read(record):
  return scope_time.ms_per_step(record, "repro_mla")
