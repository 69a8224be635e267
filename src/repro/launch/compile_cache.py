"""Where the persistent XLA compilation cache lives.

A chip run spends much of a cold start compiling; a second process that
finds the same programs in the persistent cache skips that.  The cache's
directory is part of its key, so it must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX already reads it, and no code
    here sets another directory;
  * otherwise — ``<checkout>/.jax_cache`` (gitignored), a fixed path
    built from nothing that changes between runs.

Every entry point that compiles for the chip (``chip_smoke.py``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before its
first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(checkout: os.PathLike) -> str:
    """Turn the persistent compilation cache on and return its directory:
    the environment's ``JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/.jax_cache``.  Every program is cached, however quick
    its compile, so a warm run compiles nothing."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(pathlib.Path(checkout).resolve() / CACHE_DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
