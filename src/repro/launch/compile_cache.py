"""Persistent XLA compilation cache for the launchers.

A cold 32-layer serving run compiles every step closure; the persistent
cache lets a second process (or a second run on the same machine) load them
instead. The cache key includes the directory, so the directory is fixed:
never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (``src/repro/launch`` -> repo)
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is already what JAX uses, and
    nothing else is set. Otherwise the cache goes to ``<repo>/.jax_cache``
    (gitignored). Call from an entry point's ``main``, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(REPO_ROOT / ".jax_cache")
    # lint: allow[jax-config-global] — the launchers' one cache-directory site
    jax.config.update("jax_compilation_cache_dir", path)
    return path
