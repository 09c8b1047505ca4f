"""JAX's persistent compilation cache, placed for the entry points.

Each entry point (``launch/serve.py``, ``launch/train.py``,
``python -m repro.compiler``, ``benchmarks/chip/run.py``,
``chip_smoke.py``) calls :func:`enable_compile_cache` once at start,
under its ``__main__`` guard — never at import, and never from tests.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets no other directory.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the directory is part
of the cache key, so it is never derived from a temp name, a pid or the
time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
