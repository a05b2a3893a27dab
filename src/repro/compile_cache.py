"""Persistent XLA compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call ``enable_compile_cache()`` once, before their first compile; the
library never does so on import.  The cache directory is part of every
entry's key, so it must not move between runs: ``JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself), else a fixed ``.jax_cache``
directory at the root of the checkout (git-ignored).
"""

from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    The minimum compile time is lowered to zero so the many sub-second
    programs of the streaming path (``fleet_step``, bucket solvers) are
    cached too, not only the long segment programs.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
