"""The part of ``build_s.setup`` paid on every run, cache hit or miss:
the step program's trace and lower records alone."""

from chipbench import harness


def read(record):
  return harness.load_module("metrics", "build_s.setup").seconds(
      record, ("trace", "lower"))
