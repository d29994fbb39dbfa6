"""Persistent compilation cache placement for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it and no
other directory is configured.  Otherwise the cache lives at one fixed,
gitignored path inside the checkout, ``<repo>/.jax_cache``: the cache key
includes nothing that moves between runs, so a later process of the same
checkout finds what an earlier one compiled.

Call :func:`enable_compile_cache` from a program's ``main()``, never at
import time.  A measurement of compile cost runs under
:func:`compile_cache_off`, so it pays for every compile whatever an
earlier run left in the cache.
"""

from __future__ import annotations

import contextlib
import os

import jax
from jax.experimental.compilation_cache import compilation_cache

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
  """Turn on JAX's persistent compilation cache; returns its directory."""
  path = os.environ.get(ENV_VAR)
  if not path:
    path = DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
  return path


@contextlib.contextmanager
def compile_cache_off():
  """Neither read nor write the persistent cache inside the block.

  JAX decides once per process whether the cache is in use, so the
  decision is reset on the way in and on the way out.
  """
  was = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  try:
    yield
  finally:
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
