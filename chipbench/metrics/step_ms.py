"""Window time over the steps completed in it (host clock; the window
closes when the last step dispatched in it is done)."""


def read(record):
  w = record.window
  return 1e3 * w.seconds / w.attempted if w.attempted else None
