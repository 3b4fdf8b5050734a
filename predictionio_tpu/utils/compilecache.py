"""Persistent XLA compilation cache.

The reference has no compile step at run time (Spark ships JVM
bytecode); here every `pio train` jit-compiles the training program,
and at ML-20M geometry a cold compile is most of the wall-clock a user
experiences. JAX's persistent compilation cache stores the compiled
executable keyed by program + compiler fingerprint + **the cache
directory's path**, so every `pio train` / `pio deploy` / `bench.py`
after the first skips XLA entirely — as long as the directory does not
move.

Enabled by :func:`enable` from the workflow entry points. Placement:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module
  uses that directory and writes no ``jax_compilation_cache_dir``.
- unset: one fixed directory, :data:`DEFAULT_DIR` (``.jax_cache/`` at
  the root of the checkout, git-ignored) — independent of ``PIO_HOME``,
  pid, time or temp names, so two runs with different homes share it.

Switching the cache off is JAX's own ``jax_enable_compilation_cache``
(``JAX_ENABLE_COMPILATION_CACHE=0``).
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_enabled = False


def enable() -> str:
    """Idempotently turn on JAX's persistent compilation cache; returns
    the cache dir. Safe to call before or after the first jax use — the
    config is read at compile time."""
    global _enabled
    import jax

    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = from_env or DEFAULT_DIR
    if _enabled:
        return cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every program that took ≥1s to compile (default is 60s,
    # which would skip everything but the ALS train program itself)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _enabled = True
    return cache_dir
