"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the directory, so a directory that moves between
runs never hits.  ``enable_compile_cache()`` is called by every entry point
(``chip_smoke.py``, ``repro.launch.*``, ``benchmarks/run.py``) before its
first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing is set
  here and no other directory is chosen in code.
* unset — ``<checkout>/.jax_cache`` (listed in ``.gitignore``), the same
  path on every call.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns the
    directory in use."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    path = str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
