"""Where JAX keeps its persistent compilation cache for this repo's programs.

Call :func:`configure` first thing in a program's ``main`` (``chip_smoke.py``,
``benchmarks/run.py``), never from an import.  With
``JAX_COMPILATION_CACHE_DIR`` set it sets nothing: JAX reads the variable
itself.  Otherwise the cache goes to ``.jax_cache/`` at the repo root.  The
path is fixed (no temp name, pid or time in it) because it is part of what
a later run must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["ENV_VAR", "REPO_CACHE_DIR", "configure"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> str:
    """Point JAX's persistent compilation cache at its place and return
    that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
