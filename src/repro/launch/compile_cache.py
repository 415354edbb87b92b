"""JAX's persistent compilation cache: one place that decides where it lives.

Every entry point (``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls :func:`enable_compile_cache`
before its first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing is set here. Otherwise the cache lives at one
fixed path in the checkout, ``<repo>/.jax_cache`` (git-ignored): the path is
part of what the cache is keyed on, so it never depends on a temporary
directory, a pid or a time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
