"""Placement of XLA's persistent compilation cache for the entry scripts.

The cache's location is part of what its entries are keyed by, so a
directory that moves never hits: the path is either the one the
environment names or one fixed place inside the checkout — never a home
directory, a temporary name, a pid or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax


def use_compile_cache(checkout) -> str:
    """Point jax's persistent compilation cache and return the directory
    in use. With ``JAX_COMPILATION_CACHE_DIR`` set, jax has already read
    it and nothing is set in code; otherwise ``<checkout>/.jax_cache``.
    Call before the first compilation."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
