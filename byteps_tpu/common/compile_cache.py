"""Where XLA's persistent compilation cache lives.

One rule, applied by every entry point before its first compile
(``api.init``, ``serving.frontend.build_engine_from_env``,
``chip_smoke.py``): the cache directory is chosen from
OUTSIDE the program.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and this module sets nothing.  Otherwise the cache goes to
``<checkout>/.jax_cache`` — a fixed path derived from this package's own
location (the directory is part of the cache key, so a path built from a
temp dir, a pid or a clock would never hit), listed in ``.gitignore``.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Place the persistent compilation cache; returns the directory in
    effect.  Idempotent, initializes no backend."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    if jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        # JAX's default skips programs that compiled in under a second.
        # A process start here is dozens of those (model init, prefill
        # buckets, small kernels): measured on the v5e, a warm
        # chip_smoke.py still spent 56 s of the cold run's 159 s
        # recompiling them (PR 21).  Whoever sets the variable owns
        # this threshold too (JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS).
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return DEFAULT_CACHE_DIR
