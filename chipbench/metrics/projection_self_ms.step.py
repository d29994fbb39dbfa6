"""Device ms per step under ``repro_projection_*`` scopes and under no
isotonic scope: the sort, the permutations and the gathers."""


def read(record):
  t, w = record.trace, record.window
  if t is None or not w.attempted:
    return None
  s = t.seconds("projection")
  return 1e3 * s / w.attempted if s > 0 else None
