"""Where the persistent XLA compilation cache lives.

A sweep's one-trace-per-group compile and the trainer's step compile are
the dominant cold costs of a process, so repeat invocations load the
compiled programs from disk (EXPERIMENTS.md §Perf). JAX's own settings
come first: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and
this module sets no directory (``JAX_ENABLE_COMPILATION_CACHE=false``
turns the cache off). Otherwise the cache goes to ``.jax_cache/`` at the
root of the checkout, an absolute path derived from this file, so every
working directory shares one cache: the path is part of the cache key.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compilation_cache"]

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> None:
    """Point JAX's persistent compilation cache at its directory.

    Idempotent; call it before the first compile of a process.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
