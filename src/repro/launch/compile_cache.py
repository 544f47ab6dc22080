"""JAX's persistent compilation cache for the entry points.

Called first by ``chip_smoke.py``, ``python -m repro.launch.train`` and
``python -m benchmarks.run``; never at import time and never by tests.
"""
from __future__ import annotations

import os
from pathlib import Path

# the checkout root: src/repro/launch/compile_cache.py -> three levels up
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory and
    return it.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it
    itself and nothing else is set.  Otherwise the cache lives in
    ``.jax_cache/`` at the checkout root, so a later run from the same
    checkout finds what an earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
