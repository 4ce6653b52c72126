"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["DEFAULT_DIR", "enable_compile_cache"]

#: A fixed directory inside the checkout (listed in ``.gitignore``): a
#: cache that moves between runs is never found again.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is what JAX reads on its own
    and nothing else is set; otherwise the cache goes to ``DEFAULT_DIR``.
    Call before the first compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
