"""Placement of JAX's persistent compilation cache for the entry-point
scripts (``chip_smoke.py``, ``benchmarks/``).  Importing the package never
touches the cache; a script opts in by calling ``use_compile_cache``
before its first compile."""
from __future__ import annotations

import os

import jax

# the checkout this package runs from: <checkout>/src/repro/launch/
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Keep compiled programs where ``JAX_COMPILATION_CACHE_DIR`` says when
    it is set (JAX reads the variable itself), otherwise at
    ``<checkout>/.jax_cache``: a fixed path, so later runs from the same
    checkout find what earlier runs compiled.  Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
