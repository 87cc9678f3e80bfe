"""Persistent XLA compilation cache: the one place that names its
directory.

The ECDSA ladder costs ~100 s to compile per bucket shape and the
idemix pairing program minutes; a persistent on-disk cache makes the
compiles survive process restarts.  bccsp/tpu.py and
ops/fp256bn_dev.py call `enable_compile_cache()` at import ("service
start"), tests/conftest.py calls it for the suite.

Where the cache lives:

* `JAX_COMPILATION_CACHE_DIR` set in the environment: jax reads the
  variable itself and this helper sets NO directory — the operator
  (or the machine image) placed the cache, and that placement wins.
* unset: `<checkout>/.cache/jax`, derived from this package's own
  location.  The directory is part of the cache key's lookup, so it
  is a fixed path — never the home directory, a temporary name, a pid
  or a time.

A cache that cannot be set up raises: on a path where one ladder
bucket is ~100 s of compile, silently running uncached is a fault.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_cache_dir() -> str:
    """The fixed in-checkout path used when the environment does not
    place the cache (`.gitignore` covers `.cache/`)."""
    return os.path.join(_CHECKOUT, ".cache", "jax")


def enable_compile_cache() -> str:
    """Idempotent; returns the cache directory in force."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = default_cache_dir()
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return cache_dir
