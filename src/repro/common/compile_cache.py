"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (examples, the benchmark harness, ``chip_smoke.py``) call
``enable_compile_cache`` before their first compile; importing the library
never touches the cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled programs across runs. Where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself and this leaves it alone; otherwise the
    cache is ``<repo>/.jax_cache``, a fixed path, so every run of this
    checkout finds what the previous one compiled."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
