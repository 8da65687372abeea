"""Persistent XLA compilation cache for the command-line entry points.

Only entry points (``chip_smoke.py``, ``examples/serve_readout.py``,
``benchmarks/readout/run.py``) call ``enable_compile_cache``; importing a
library module never changes JAX's configuration, and tests never cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and no
    other is used. Otherwise the cache lives in ``<checkout>/.jax_cache``:
    a fixed path, so a later run of the same checkout finds its entries.
    Every compile is kept, however short, because a cold chip run pays
    for each one.
    """
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(_CHECKOUT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
