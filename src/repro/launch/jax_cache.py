"""JAX's persistent compile cache for the entry points.

`enable_compile_cache()` is called first thing by `chip_smoke.py` and by
every `repro.launch` `main()`.  If `JAX_COMPILATION_CACHE_DIR` is set, JAX
already reads it and nothing else is set here; otherwise the cache lives at
`<checkout>/.jax_cache` (git-ignored).  The path is fixed on purpose: it is
part of the cache key, so a path built from a temporary name, a process id
or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
