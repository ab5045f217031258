"""Where compiled programs are kept: one rule for every process of the repo.

If ``JAX_COMPILATION_CACHE_DIR`` is set, whoever runs the program has placed
the cache: JAX reads the variable itself and nothing here (or anywhere)
sets a directory in code. If not, the cache is ``<checkout>/.jax_cache`` —
a fixed path, because the path is part of the cache key and a directory
that moves (a data dir made by ``mkdtemp``) never hits. The warm-boot
marker that lets a respawned engine skip its warm-up lives in the same
directory, so marker and cache are found, or lost, together.

Importing this module does not import JAX: the control plane uses
``compile_cache_dir`` to build engine environments and stays off the chip.
"""

from __future__ import annotations

import os
from pathlib import Path

_CHECKOUT = Path(__file__).resolve().parents[2]


def compile_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_CHECKOUT / ".jax_cache")


class CompileCacheStats:
    """Counts of what this process asked of the persistent cache, from
    JAX's own monitoring events: ``requests`` compiles that consulted it,
    ``hits`` served from it, ``writes`` compiled afresh and stored (only
    compiles over the persistence threshold are stored)."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "writes",
    }

    def __init__(self) -> None:
        self.requests = self.hits = self.writes = 0

    def on_event(self, event: str, **_kwargs) -> None:
        name = self._EVENTS.get(event)
        if name is not None:
            setattr(self, name, getattr(self, name) + 1)

    def as_dict(self) -> dict:
        return {
            "dir": compile_cache_dir(),
            "requests": self.requests,
            "hits": self.hits,
            "writes": self.writes,
        }


def enable_compile_cache() -> CompileCacheStats:
    """Turn the persistent cache on for this process, before its first
    compile, and return the counters that watch it."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    # the engine's step programs take seconds to minutes; sub-half-second
    # compiles are cheaper to redo than to store
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    stats = CompileCacheStats()
    jax.monitoring.register_event_listener(stats.on_event)
    return stats
