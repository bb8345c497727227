"""JAX's persistent compilation cache for the repository's entry points.

Scripts (``chip_smoke.py``, ``examples/``, ``benchmarks/run.py``) call
``enable_compile_cache()`` before their first compile; importing the library
sets nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and this helper sets no other cache.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (gitignored), never at a temporary or per-process
path, so a later run from the same checkout finds what an earlier one wrote.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
