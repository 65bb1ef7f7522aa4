"""Where JAX keeps its persistent compilation cache.

Call ``use_compile_cache()`` first thing in an entry point, before anything
compiles — never at import. A compiled program is found again only under the
same cache path, so the path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself, and nothing else is set
here), otherwise ``.jax_cache/`` at the checkout root, which git ignores.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
