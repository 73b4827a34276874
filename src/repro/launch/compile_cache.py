"""Persistent XLA compile cache for the entry points.

Each launcher, bench script and ``chip_smoke.py`` calls
``enable_compile_cache()`` once from its ``main`` — never at import, so
the tests, which import these modules, keep JAX's cache off. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this sets
no other directory. Otherwise the cache lives at ``<checkout>/.jax_cache``
(gitignored): a fixed path, so a later process on the same checkout finds
what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compile cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, the serve path's second-long kernels included
    # (JAX's default skips compiles under one second)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
