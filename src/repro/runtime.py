"""Process start-up shared by the entry points (smoke, benchmarks,
examples): where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache directory and is
honoured as is; otherwise the cache lives at ``<checkout>/.jax_cache`` — a
fixed path, because the path is part of the cache key. Nothing here
queries a backend.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def init_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
