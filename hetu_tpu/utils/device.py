"""Process set-up the entry points share: where compiled programs persist.

A cold compile of a whole train step or of the serving programs takes
seconds to minutes; JAX's persistent compilation cache keeps the result
across processes.  The directory is part of the cache key, so it must not
move between runs: it is either the one the environment names or one
fixed path inside the checkout — never a temporary directory.
"""
from __future__ import annotations

import os

#: keep every program that took at least this long to compile — the
#: main-path Pallas kernels compile in 1-4 s each, JAX's default of 1 s
#: would drop the quick ones
_MIN_COMPILE_SECS = 0.5


def _checkout_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  With ``JAX_COMPILATION_CACHE_DIR`` set JAX already uses
    that directory and this sets no other; unset, the cache lives at
    ``<checkout>/.jax_cache``.  Call before the first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_checkout_root(), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _MIN_COMPILE_SECS)
    return path
