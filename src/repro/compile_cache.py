"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`setup_compile_cache` before their first compile; nothing calls it at
import.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself
and this module sets nothing.  Otherwise the cache goes to one fixed,
gitignored directory inside the checkout -- the path is part of the cache
key, so a temporary or per-run directory would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``).
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
