"""Where JAX's persistent compilation cache lives.

The cache is keyed by path, so it only pays off at a fixed one.  When
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing here
overrides it; otherwise the entry points (``chip_smoke.py``,
``repro.launch.serve``, ``repro.launch.train``, ``benchmarks.run``) point
the cache at ``.jax_cache/`` in the checkout, which ``.gitignore`` lists.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def use_persistent_cache() -> str:
    """Enable the persistent compilation cache; returns its directory.
    Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
