"""Where JAX keeps its persistent compilation cache.

A cold run of the served ResNet34 path compiles dozens of stage and kernel
programs; the persistent cache lets the next process on the same machine
skip that.  The cache key includes the directory, so the directory must
not move between runs: either the one the caller placed with
``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself, so nothing
is set here), or the fixed ``<repo>/.jax_cache`` (git-ignored) — never a
path built from a temp name, a pid or a time.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, '.jax_cache')


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory.  Call once, early, from a program's main."""
    placed = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if placed:
        return placed
    import jax
    jax.config.update('jax_compilation_cache_dir', DEFAULT_DIR)
    return DEFAULT_DIR
