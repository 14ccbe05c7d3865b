"""Persistent XLA compile cache, placed from outside the program.

Every cold process otherwise recompiles every plan shape (seconds to
tens of seconds per shape on a TPU). A cache directory that MOVES never
hits, so it is either the one the environment names
(`JAX_COMPILATION_CACHE_DIR`, which JAX reads itself) or one fixed path
under the checkout. Never a temporary name, a pid, or a time.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache somewhere stable; returns
    the directory in effect. Call before the first jit (Node.__init__
    and chip_smoke.py do). Idempotent.

    With JAX_COMPILATION_CACHE_DIR set, no directory is set in code —
    JAX reads the variable. Otherwise `<checkout>/.jax_cache`. Whether
    entries are read or written at all stays with
    `jax_enable_compilation_cache` (tests/conftest.py turns it off:
    several tests count compiles from a cold start)."""
    import jax

    # every plan shape is worth keeping: the smallest fused kernel still
    # costs a second of Mosaic compile per cold process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
