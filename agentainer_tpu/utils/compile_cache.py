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
    """What this process asked of the persistent cache and what compiling
    cost it, from JAX's own monitoring events. Counts: ``requests`` compiles
    that consulted the cache, ``hits`` served from it, ``misses`` compiled
    afresh AND stored (also exported as ``writes``, the name the benchmark
    prints: JAX 0.9 records a miss where it writes the entry, so a program
    compiled afresh in under the persistence threshold counts in neither:
    there were ``requests - hits - misses`` of those).
    Seconds, summed over the process: ``trace_s`` tracing Python to a jaxpr,
    ``lower_s`` lowering it to an MLIR module, ``compile_s`` getting the
    executable (the backend compiler, or the cache's copy: JAX times the
    two as one), ``retrieval_s`` the part of ``compile_s`` spent reading
    the cache on a hit. After warm-up all of them should stand still.

    ``programs`` splits the three durations by the function JAX names on
    each of them (``jit(f)`` and ``f`` are one row, ``f``): ``n`` executables
    got, compiled or read from the cache (JAX's hit and miss events carry no
    name, so only seconds are by program). A function only traced, inside
    another, is no program: its seconds are in its caller's ``trace_s``.
    The document keeps the ``PROGRAMS_KEPT`` largest by seconds and sums the
    rest under ``other``."""

    PROGRAMS_KEPT = 32

    _FIELDS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
        "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    }

    _BY_PROGRAM = ("trace_s", "lower_s", "compile_s")

    def __init__(self) -> None:
        self.requests = self.hits = self.misses = 0
        self.trace_s = self.lower_s = self.compile_s = self.retrieval_s = 0.0
        # function name -> [n, trace_s, lower_s, compile_s]
        self._programs: dict[str, list[float]] = {}

    def on_event(self, event: str, amount: float = 1, fun_name: str = "", **_kwargs) -> None:
        """Listener for JAX's events (one more) and for its durations
        (``amount`` seconds more, of the program ``fun_name``)."""
        name = self._FIELDS.get(event)
        if name is None:
            return
        setattr(self, name, getattr(self, name) + amount)
        if fun_name and name in self._BY_PROGRAM:
            if fun_name.startswith("jit(") and fun_name.endswith(")"):
                fun_name = fun_name[4:-1]
            row = self._programs.setdefault(fun_name, [0, 0.0, 0.0, 0.0])
            row[1 + self._BY_PROGRAM.index(name)] += amount
            row[0] += name == "compile_s"

    def totals(self) -> dict:
        return {name: getattr(self, name) for name in self._FIELDS.values()}

    def programs(self) -> dict:
        # a name never lowered is a callee, no program: its caller's row has its seconds
        rows = [(name, row) for name, row in self._programs.copy().items() if row[0] or row[2]]
        rows.sort(key=lambda kv: -sum(kv[1][1:]))
        kept, rest = rows[: self.PROGRAMS_KEPT], rows[self.PROGRAMS_KEPT :]
        if rest:
            kept.append(("other", [sum(col) for col in zip(*(row for _, row in rest))]))
        return {name: dict(zip(("n", *self._BY_PROGRAM), row)) for name, row in kept}

    def as_dict(self) -> dict:
        return {
            "dir": compile_cache_dir(),
            **self.totals(),
            "writes": self.misses,
            "programs": self.programs(),
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
    jax.monitoring.register_event_duration_secs_listener(stats.on_event)
    return stats
