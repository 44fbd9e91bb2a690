"""Where JAX keeps its persistent compilation cache for this repo's
entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when that variable
is set nothing here changes. Otherwise the cache lives at a fixed
directory inside the checkout, ``<repo>/.jax_cache``: the path is part
of the cache's key, so it must not move from one run to the next."""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``."""
    return os.environ.get(ENV) or REPO_CACHE


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``cache_dir()`` (a no-op when the
    environment already names it) and return the directory."""
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return cache_dir()
