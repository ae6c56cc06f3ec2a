"""Where XLA's persistent compilation cache lives.

One rule for every entry point (`adapm_tpu.setup`, `chip_smoke.py`,
`tests/conftest.py`): a directory given from outside
through `JAX_COMPILATION_CACHE_DIR` (or set on `jax.config` by the
caller) is left alone; otherwise the cache goes to `.jax_cache/` at the
root of the checkout. The default is a FIXED path on purpose: a later
process finds the entries only if it looks in the same place, so a
directory built from a temp name, a pid or the time would never hit.
"""
from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Make sure jax's persistent compilation cache is on; returns the
    directory in use. Safe to call repeatedly and after jax has already
    compiled programs (the cache initializes lazily on the next
    compile). The store threshold drops to 0.1 s of compile time
    (jax's default of 1 s would skip the small data-plane programs)
    unless `JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS` says otherwise."""
    import jax
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return jax.config.jax_compilation_cache_dir
