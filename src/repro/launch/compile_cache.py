"""JAX's persistent compilation cache for the launchers.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
nothing here overrides it.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (listed in .gitignore), so every run from one
checkout finds what an earlier run compiled.  Tests do not call this.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
