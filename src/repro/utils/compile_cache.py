"""JAX's persistent compilation cache, placed for the program's entry points.

Every entry point (``chip_smoke.py``, ``repro.launch.train``,
``benchmarks.run``, ``benchmarks.perf_bench``) calls
:func:`enable_compile_cache` first thing in ``main``; nothing calls it while a
module is imported, and the tests never turn it on.

Where the cache lives:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module sets
  no other directory.
- otherwise: ``.jax_cache/`` at the root of the checkout (gitignored). The
  path is fixed, so a second run of the same program finds what the first
  compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """Number of compiled programs currently stored under ``path``."""
    p = Path(path)
    return len(list(p.glob("*-cache"))) if p.is_dir() else 0
