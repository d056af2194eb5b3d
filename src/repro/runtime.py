"""Process-level JAX set-up for entry points.

Nothing here runs at import: ``chip_smoke.py`` and ``benchmarks/run.py``
call :func:`enable_compile_cache` from their ``main``, and library code
never does.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else the fixed
    ``<repo>/.jax_cache``. The path is part of every cache key, so it
    never depends on a temporary name, a process id or the time."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(REPO_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
