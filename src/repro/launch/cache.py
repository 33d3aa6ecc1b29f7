"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, the ``benchmarks/`` mains) call
:func:`enable_compile_cache` once, before their first compile.  Nothing
calls it at import time or from tests.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache
    there and no other directory is set.  Otherwise the cache lives at
    the fixed ``<repo root>/.jax_cache``: the directory takes part in
    finding an entry again, so it never depends on a temporary name, a
    process id or the time.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
