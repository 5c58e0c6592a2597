"""JAX's persistent compilation cache, placed from outside the program.

Every entry point (``chip_smoke.py``, ``examples/train_gnn_*.py``,
``python -m repro.launch.serve``, ``benchmarks/run.py``) calls
:func:`enable` before its first compile.  ``JAX_COMPILATION_CACHE_DIR``,
when set, names the directory and JAX reads it itself; otherwise the cache
sits at ``<checkout>/.jax_cache``.  The path is fixed because it is part of
what a later run must find again: a directory named after a temporary
file, a process id or the time never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir(environ=os.environ) -> str:
    """Where the persistent cache lives for the given environment."""
    return environ.get(ENV) or str(REPO_CACHE)


def enable() -> str:
    """Turn the persistent cache on for every compile, however short, and
    return its directory.  Sets the directory in code only when the
    environment names none."""
    import jax
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    # the Pallas kernels and small steps compile in well under JAX's
    # default one-second threshold; a warm run should find them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir()
