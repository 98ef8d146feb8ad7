"""The one place that points JAX's persistent compilation cache.

The placement kernels cost seconds to compile per shape bucket; a disk
cache makes fresh processes (benches, the chip smoke, tests, sidecars)
start warm. Every entry point calls `enable_compile_cache()` once, before
its first compile. Where `JAX_COMPILATION_CACHE_DIR` is set, that
directory is the cache and no other is set in code; otherwise the cache
is the fixed, gitignored `<checkout>/.jax_cache`. The path is part of
the cache's key, so it is never made from a temporary name, a pid or a
time.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at $JAX_COMPILATION_CACHE_DIR,
    else at DEFAULT_DIR, and return that directory. Idempotent."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path
