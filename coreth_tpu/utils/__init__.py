"""Shared helpers (role of /root/reference/utils/)."""

from __future__ import annotations

import os

# the persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset:
# one fixed directory inside the checkout (listed in .gitignore) — a
# cache's path is part of its key, so it must not move between runs
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")

_cache_enabled = False


def enable_compilation_cache() -> None:
    """Persist XLA compilations across processes.

    The keccak kernels compile one program per shape bucket; with the
    disk cache a fresh process (a node restart, the next run) reuses them
    instead of paying each compile again. Where JAX_COMPILATION_CACHE_DIR
    is set, JAX already reads it and nothing is set here; otherwise the
    cache is CHECKOUT_CACHE_DIR.
    """
    global _cache_enabled
    if _cache_enabled:
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    _cache_enabled = True
