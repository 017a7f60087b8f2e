"""Where the persistent compilation cache lives, for every entry point.

A cold process on the chip compiles every kernel and chunk runner again;
the persistent cache lets the processes of one machine share that work.
The cache key includes the directory, so the directory must not move
between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout, git-ignored
CHECKOUT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
    nothing else is set here; otherwise the cache goes to ``CHECKOUT_DIR``.
    No libtpu flag is set either, so ``LIBTPU_INIT_ARGS`` stays as the
    machine set it."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
