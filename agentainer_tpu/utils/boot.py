"""An engine host's boot as a timeline: ``/metrics`` ``boot``.

One ``Spans`` recorder (``utils/spans.py``) per boot, made where the boot
starts (``runtime/engine_main.main`` for a spawned engine, the serve app for
an embedded one) and handed down to whoever does the work: ``boot.*`` spans on
the thread that does it, their self times tiling the time from ``main``'s
entry to ready. A span with no parent on its thread is a STAGE: beside it the
timeline keeps the compile listener's difference across it
(``utils/compile_cache.CompileCacheStats``), so a stage's seconds split into
compiling afresh (``jit_s`` with ``cache_misses``), reading the cache
(``retrieval_s``, a part of ``jit_s``) and running.

``ready()`` freezes the document; ``first_dispatch`` adds, once, how long
after it the engine took its first request and whether that was a replayed
one. A respawned engine boots through the same code, so this is the engine's
half of a recovery: spawn, ready, first replayed dispatch.

Always on: a boot runs once. Importing this module does not import JAX.
"""

from __future__ import annotations

import os
import time

from ..core.protocol import SPAWNED_NS_ENV
from .compile_cache import CompileCacheStats
from .spans import Spans, _Span


def process_age_s() -> float | None:
    """Seconds since the kernel started this process (Linux: its start in
    clock ticks after the machine's, against the machine's uptime), which a
    process cannot take from a clock it reads only once it runs."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            return float(f.read().split()[0]) - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


class _Stage:
    """A top-level span with the compile listener's difference across it."""

    __slots__ = ("_boot", "_name", "_span", "_before")

    def __init__(self, boot: "BootTimeline", name: str, span: _Span) -> None:
        self._boot = boot
        self._name = name
        self._span = span

    def __enter__(self) -> "_Stage":
        self._before = self._boot._compiled()
        self._span.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._span.__exit__(*exc)
        boot = self._boot
        if boot.compile_stats is not None:
            had = boot._stages.get(self._name, (0.0, 0.0, 0))
            boot._stages[self._name] = tuple(
                h + a - b for h, a, b in zip(had, boot._compiled(), self._before)
            )


class BootTimeline(Spans):
    def __init__(
        self,
        started_ns: int | None = None,
        started_unix_ns: int | None = None,
        spawned_unix_ns: int | None = None,
        warm_boot: bool = False,
    ) -> None:
        """``started_ns`` / ``started_unix_ns``: ``time.perf_counter_ns`` and
        ``time.time_ns`` read together where the boot began (now, if not
        given); ``spawned_unix_ns``: the spawner's ``time.time_ns`` stamp,
        ``None`` for a process nobody spawned."""
        super().__init__()
        self.started_ns = time.perf_counter_ns() if started_ns is None else started_ns
        self.started_unix_ns = time.time_ns() if started_unix_ns is None else started_unix_ns
        self.spawned_unix_ns = spawned_unix_ns
        self.warm_boot = warm_boot
        # set by whoever turns the compile cache on (it happens inside the
        # first stage, so a stage that began without it began at zero)
        self.compile_stats: CompileCacheStats | None = None
        self._stages: dict[str, tuple[float, float, int]] = {}
        self._importing: _Stage | None = None
        self._ready_ns: int | None = None
        self._frozen: dict | None = None
        self.first_dispatch_s: float | None = None
        self.first_dispatch_replayed: bool | None = None

    @classmethod
    def at_main(cls, started_ns: int, started_unix_ns: int, environ) -> "BootTimeline":
        """The timeline of a spawned engine host, with ``boot.import`` open
        since ``main``'s entry: the recorder's own first span imports JAX,
        inside it. ``imported()`` closes it."""
        spawned = environ.get(SPAWNED_NS_ENV, "")
        boot = cls(
            started_ns,
            started_unix_ns,
            spawned_unix_ns=int(spawned) if spawned.isdigit() else None,
            warm_boot=environ.get("AGENTAINER_WARM_BOOT") == "1",
        )
        boot._importing = boot._staged("boot.import", boot.span_since("boot.import", started_ns))
        boot._importing.__enter__()
        return boot

    def imported(self) -> None:
        if self._importing is not None:
            self._importing.__exit__(None, None, None)
            self._importing = None

    def span(self, name: str, **attrs):
        return self._staged(name, super().span(name, **attrs))

    def _staged(self, name: str, span: _Span):
        return span if self._thread().stack else _Stage(self, name, span)

    def _compiled(self) -> tuple[float, float, int]:
        """(``jit_s``, ``retrieval_s``, ``cache_misses``) so far."""
        stats = self.compile_stats
        if stats is None:
            return 0.0, 0.0, 0
        return stats.trace_s + stats.lower_s + stats.compile_s, stats.retrieval_s, stats.misses

    def ready(self) -> None:
        """The loader calls this where it sets ready, its last span closed."""
        self._ready_ns = time.perf_counter_ns()
        self._frozen = self._document()

    def first_dispatch(self, entered_ns: int, replayed: bool) -> None:
        """The first request taken after ready, entered at ``entered_ns``."""
        if self._ready_ns is not None and self.first_dispatch_s is None:
            self.first_dispatch_s = max(0, entered_ns - self._ready_ns) / 1e9
            self.first_dispatch_replayed = replayed

    def _document(self) -> dict:
        spawned, stats = self.spawned_unix_ns, self.compile_stats
        return {
            "spawned_unix_ns": spawned,
            "started_unix_ns": self.started_unix_ns,
            "spawn_to_main_s": None if spawned is None else (self.started_unix_ns - spawned) / 1e9,
            "ready_s": None if self._ready_ns is None else (self._ready_ns - self.started_ns) / 1e9,
            "warm_boot": self.warm_boot,
            "phases": self.snapshot()["phases"],
            "stages": {
                name: {"jit_s": jit_s, "retrieval_s": retrieval_s, "cache_misses": misses}
                for name, (jit_s, retrieval_s, misses) in sorted(self._stages.copy().items())
            },
            "compile_cache_at_ready": (
                None if stats is None or self._ready_ns is None else stats.totals()
            ),
        }

    def as_dict(self) -> dict:
        """Live while the engine loads (``ready_s`` null, the stages so far);
        the same document ever after, but for its last two keys."""
        return {
            **(self._frozen or self._document()),
            "first_dispatch_s": self.first_dispatch_s,
            "first_dispatch_replayed": self.first_dispatch_replayed,
        }
