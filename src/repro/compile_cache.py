"""JAX's persistent compilation cache, as the entry points turn it on."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed on purpose: a later run finds its compiled programs again only at
# the same path. ``.gitignore`` lists it.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    it stays as it is. Otherwise the cache lives at ``<checkout>/.jax_cache``.
    Entry points (the serving CLI, the benchmark scripts, ``chip_smoke.py``)
    call this; library code and tests do not."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
