"""JAX persistent compilation cache for the entry points.

Entry points (``launch/train.py``, ``launch/serve.py``,
``launch/serve_split.py``, ``benchmarks/run.py``, ``chip_smoke.py``)
call ``enable_compile_cache()`` once at start-up; library code never
does. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here. Otherwise the cache lives at one fixed path
inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the path is
part of the cache key, so it must not move between runs.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
