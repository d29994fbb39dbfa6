"""Device ms per step under the forward ``repro_isotonic_*`` scopes: the
isotonic solve."""


def read(record):
  t, w = record.trace, record.window
  if t is None or not w.attempted:
    return None
  s = t.seconds("isotonic")
  return 1e3 * s / w.attempted if s > 0 else None
