"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache and JAX reads
it itself: nothing else is set.  Otherwise the cache lives at the fixed
``.jax_cache/`` of this checkout (listed in ``.gitignore``).  The path
is part of every entry's key, so it never depends on a temp name, a pid
or the time: a later run from the same checkout finds what an earlier
one compiled.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
