"""JAX persistent compilation cache for the entry points.

Called by ``chip_smoke.py``, ``launch/serve.py`` and the benchmark
entry points — never on import — so every process of a run shares one
cache. The directory is part of each entry's key, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself and
nothing else is touched), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
