"""Device idle share of the window: 100 x (1 - busy / window), busy the
union of the device's op intervals (profiler trace)."""


def read(record):
  t = record.trace
  if t is None or t.window_s <= 0:
    return None
  return 100.0 * (1.0 - t.busy_s / t.window_s)
