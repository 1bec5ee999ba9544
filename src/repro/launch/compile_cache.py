"""JAX's persistent compilation cache, at one fixed place per checkout.

Every entry point calls :func:`enable_compile_cache` before it compiles
anything. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing is set here; otherwise the cache lives at ``<checkout>/.jax_cache``
(listed in ``.gitignore``). The path must not move between runs — it is
part of what a later run looks up — so it is never a temp, pid or time
directory.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
