"""Where the entry points keep JAX's persistent compilation cache."""

from __future__ import annotations

import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache


@pytest.mark.parametrize("env", [None, "/somewhere/cache"])
def test_cache_dir_honours_env_else_fixed_path_in_checkout(env, monkeypatch):
  updates = []
  monkeypatch.setattr(jax.config, "update",
                      lambda name, value: updates.append((name, value)))
  if env is None:
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
  else:
    monkeypatch.setenv(compile_cache.ENV_VAR, env)

  path = compile_cache.enable_compile_cache()

  if env is None:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", path)]
  else:
    # JAX reads the variable itself; the code sets no other directory.
    assert path == env
    assert updates == []


@pytest.mark.parametrize("off", [True, False])
def test_compile_cache_off_neither_reads_nor_writes(off, tmp_path):
  names = ("jax_compilation_cache_dir",
           "jax_persistent_cache_min_compile_time_secs",
           "jax_persistent_cache_min_entry_size_bytes")
  saved = {name: getattr(jax.config, name) for name in names}
  try:
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    program = jax.jit(lambda x: x * 3.0 + float(off)).lower(
        jax.ShapeDtypeStruct((7,), "float32"))
    if off:
      with compile_cache.compile_cache_off():
        program.compile()
    else:
      program.compile()
    assert bool(os.listdir(tmp_path)) != off
  finally:
    for name, value in saved.items():
      jax.config.update(name, value)
    compilation_cache.reset_cache()
