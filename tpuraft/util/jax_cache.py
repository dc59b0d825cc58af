"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles (the engine's jax path,
``chip_smoke.py``, the device benches, the test suite): when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no other
directory is set in code; when it is not, the cache is
``<checkout>/.jax_cache`` — a fixed path found from this package's own
location, because the path is part of what makes a cache reusable (a
temporary, pid- or time-derived name never hits).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compile_cache() -> str:
    """Enable the persistent compile cache; returns the directory in use.

    Must run before the process's first compile: JAX decides once, at
    that compile, whether a cache is in use."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # the tick is a small program (well under JAX's default 1 s floor)
    # and is exactly what a restarted store must not recompile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
