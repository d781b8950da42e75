"""Where this repo keeps JAX's persistent compilation cache.

``chip_smoke.py``'s rule: where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and no directory is set in code; where it is not,
the cache is ``.jax_cache/`` at the root of the checkout (listed in
``.gitignore``). The directory is part of the cache key, so it never
moves within a checkout.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on for this process, before
    its first compile. Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


class CacheCounter:
    """Counts this process's persistent-cache hits and misses from
    JAX's own monitoring events (a miss is a program compiled here)."""

    def __init__(self):
        from jax import monitoring

        self.hits = 0
        self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1
