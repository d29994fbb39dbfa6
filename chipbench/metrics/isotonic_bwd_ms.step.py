"""Device ms per step under the backward ``repro_*_bwd_*`` scopes: the
segment algebra of the VJP (Lemma 2)."""


def read(record):
  t, w = record.trace, record.window
  if t is None or not w.attempted:
    return None
  s = t.seconds("isotonic_bwd")
  return 1e3 * s / w.attempted if s > 0 else None
