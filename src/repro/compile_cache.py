"""JAX's persistent compilation cache, in one place.

`enable()` is called by the entry points that compile real-size programs
(`chip_smoke.py`, `benchmarks/run.py`), never at import.  When
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
configured here; otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (gitignored), so repeated runs from one
checkout find each other's compiled programs.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
