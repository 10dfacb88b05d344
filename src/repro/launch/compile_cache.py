"""Where JAX's persistent compilation cache lives.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads the
variable itself and nothing here overrides it. Otherwise the cache goes to
one fixed directory inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``). It never moves, so a later run finds what an earlier one
wrote: no temporary, per-process or time-stamped directory.
"""
from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Call before
    the first compilation of the process."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
