"""JAX's persistent compilation cache, placed from outside the program.

``enable_compile_cache()`` is called by the entry points (``chip_smoke.py``,
``repro.launch.train``, ``repro.launch.serve``) before their first compile,
never at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here. Otherwise the cache lives at a fixed
``.jax_cache/`` in the checkout: the path is part of the cache key, so it is
never built from a temp name, a pid or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it lives in."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
