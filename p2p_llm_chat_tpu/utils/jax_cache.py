"""Persistent XLA compilation cache for the serving/bench planes.

An 8B serve boot compiles dozens of programs (admit chunk-sizes x
buckets, decode windows x fused-K, prefix splices). The JAX persistent
cache keys compiled executables by HLO fingerprint on local disk, so
every boot after the first reuses them and warmup drops to cache reads.

Where the cache lives is decided OUTSIDE the program when
``JAX_COMPILATION_CACHE_DIR`` is set: JAX reads that variable itself and
no code here (or in tests/conftest.py, which calls this helper) sets a
directory. Unset, the cache is the fixed ``<checkout>/.jax_cache`` (in
.gitignore) — a fixed path, because the path is part of the cache key.
A cache directory that cannot be created is an error, not a silent
cold-compile on every boot.
"""

from __future__ import annotations

import os

from .log import get_logger

log = get_logger("jax_cache")

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache() -> str:
    """Idempotent; call before the first jit compilation. Returns the
    cache directory in effect."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or CHECKOUT_CACHE
    os.makedirs(path, exist_ok=True)
    if not placed:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    log.info("persistent compile cache at %s", path)
    return path
