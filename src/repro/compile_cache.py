"""JAX's persistent compilation cache for this checkout's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing here changes that.  Otherwise the cache goes to
``.jax_cache/`` at the root of the checkout: a fixed path, because the
path is part of what a later process looks the cache up by.
"""

from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
