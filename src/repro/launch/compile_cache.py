"""Persistent XLA compilation cache placement for the entry points.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``examples/serve_multitenant.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile. Importing
this module changes nothing.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, so
  the cache lands there and this helper sets no other directory.
- unset: the cache goes to ``<checkout>/.jax_cache/`` — a fixed path
  (the directory is part of the cache key, so a path that moved between
  runs would never hit), listed in ``.gitignore``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The checkout's own cache directory (repo root / .jax_cache).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
