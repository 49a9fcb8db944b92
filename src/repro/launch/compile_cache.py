"""Where JAX keeps its persistent compilation cache.

A cold process compiles every prefill bucket and decode variant of a
28-layer model; the persistent cache lets the next process on the same
machine load them instead. Its key includes the directory, so the
directory is fixed: either the one ``JAX_COMPILATION_CACHE_DIR`` names
(JAX reads that variable itself) or ``.jax_cache`` at the root of this
checkout, which ``.gitignore`` lists. Entry points call
``use_compile_cache`` once before their first compile; tests do not.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` when set (left to JAX),
    else ``CHECKOUT_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
