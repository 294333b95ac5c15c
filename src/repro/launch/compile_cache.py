"""JAX's persistent compilation cache for the entry points.

Called by ``launch/train.py``, ``launch/serve.py`` and ``chip_smoke.py``
before their first compile; library modules never touch the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout root (src/repro/launch/ -> three levels up). A later run finds
# the cache only where an earlier one left it, so the path is fixed: no
# temporary name, pid or time in it. Listed in .gitignore.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across runs and return the directory in use.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here. Otherwise the cache lives at :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
