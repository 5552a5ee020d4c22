"""JAX persistent compilation cache location for this repo's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache goes to one fixed directory
inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the path is
part of what makes a later process find the entry, so it is never built
from a temporary name, a pid or the time.

Called once at start-up by ``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.prune`` and ``benchmarks/run.py``; library code and tests
never call it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
