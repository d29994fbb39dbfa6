"""The soft operator's share of its roofline: the least HBM time for the
bytes its call must read and write (``chipbench.counts.soft_op_bytes``,
from shapes), over its device time under every projection, isotonic and
backward scope."""


def read(record):
  t, w = record.trace, record.window
  if t is None or not w.attempted or not w.op_bytes_per_step:
    return None
  device_s = sum(t.seconds(k) for k in ("projection", "isotonic",
                                        "isotonic_bwd"))
  if device_s <= 0:
    return None
  least_s = w.op_bytes_per_step * w.attempted / record.peaks["hbm_bytes_per_s"]
  return 100.0 * least_s / device_s
