"""Persistent XLA compilation cache for the repo's entry points."""
from __future__ import annotations

import os
import pathlib

import jax

# fixed path under the checkout root: the path is part of the cache key,
# so a directory that moves between runs never hits
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled programs across processes.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing
    is set here; otherwise the cache lives in the checkout's
    ``.jax_cache/``.  Called by ``chip_smoke.py`` and the benchmarks,
    never at library import or from tests."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
