"""JAX's persistent compilation cache for the repository's entry points.

Scripts (``chip_smoke.py``, ``benchmarks/``, ``examples/``) call
`enable_compile_cache` once before their first compile; importing
`repro` never touches the cache.  When ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and nothing else is configured here.  Otherwise
the cache lives at one fixed path in the checkout, ``.jax_cache/``
(git-ignored): the path is part of what a cached entry is found by, so
it never depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os

import jax

#: the checkout root: this file is <checkout>/src/repro/launch/compile_cache.py
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

#: fixed in-checkout cache directory used when the environment names none
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
