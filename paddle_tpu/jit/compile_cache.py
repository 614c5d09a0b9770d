"""Where this process keeps jax's persistent compilation cache.

One rule for every entry script (``chip_smoke.py``, ``bench.py``,
``__graft_entry__.py``), because the directory is part of what has to stay
the same for a later process to find what an earlier one compiled.
"""
from __future__ import annotations

import os

import jax

__all__ = ["use_compile_cache"]


def use_compile_cache(checkout):
    """Place the persistent compilation cache; -> the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it by itself
    and nothing is set here: whoever runs the program decides. Otherwise
    the cache is ``<checkout>/.jax_cache`` as an absolute path (listed in
    ``.gitignore``) — never a temporary or per-process directory, which
    no second process would look in."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
