"""Where JAX's persistent compilation cache lives, for every entry point.

One rule, which ``chip_smoke.py`` follows:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that directory itself and
  nothing here names another one.
- otherwise: ``<checkout>/.jax_cache``, a fixed path inside the checkout
  (gitignored). The path is part of the cache's key, so a directory that
  moved between runs (a temporary, pid- or time-based one) would never hit.

Enabling the cache only updates ``jax.config``; it initializes no backend,
so a parent may call it before spawning children that need the chip.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every program (no size or
    compile-time floor: the engine's many small dispatch shapes are the
    ones worth keeping) and return the directory in use."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = CHECKOUT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
