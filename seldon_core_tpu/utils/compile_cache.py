"""Where a process keeps JAX's persistent compilation cache.

Every entry point that initialises jax (the microservice and deployer
CLIs, bench.py, the tools) calls :func:`configure_compile_cache` before
its first compile, so a cold server does not recompile what the last
one on this checkout already built.

The directory is part of the cache key, so it must not move between
runs: no tempdir, pid or time in it.  ``JAX_COMPILATION_CACHE_DIR``
places it from outside — jax reads that variable itself, so when it is
set this module writes nothing to ``jax.config``.  Otherwise the cache
lives in ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """Where the cache lives for this environment (imports no jax)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def configure_compile_cache() -> str:
    """Point jax at the persistent compile cache; returns its path."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
