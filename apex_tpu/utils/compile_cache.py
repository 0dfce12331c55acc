"""Where compiled programs are kept between processes.

One rule, one place: when ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it
itself and this module configures NO directory in code — the cache lives
where the machine's owner put it. Otherwise the cache is
``<checkout>/.jax_cache`` (git-ignored), a path derived from this file's
location and nothing else: the directory is part of what a cached program
is found by, so a temporary name, a pid or a time would never hit.

Entry points (``chip_smoke.py``, the GPT and serving examples) call :func:`enable_compile_cache` before their first compile.
Libraries and tests do not: a test process that enabled it would write
every later compile to disk.
"""

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

__all__ = ["enable_compile_cache"]


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory."""
    import jax

    cache_dir = os.environ.get(_ENV)
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache the many sub-second programs too (state init, metric resets):
    # on a warm start they are what is left of set-up time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
