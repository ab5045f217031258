"""Phase spans: where a thread's wall time goes, by name.

One facility, two outlets. ``Spans.span(name, **attrs)`` is a context
manager that

- enters a ``jax.profiler.TraceAnnotation`` of that name, so that while a
  profiler capture runs the span sits on the profiler's own clock beside the
  device's timeline (no capture: a TraceMe costs one flag check); the trace
  is the span store, written out when the capture ends;
- on exit adds to the calling thread's accumulator, under ``name``: ``n``,
  ``total_s`` (the span's duration) and ``self_s`` (its duration minus what
  its child spans on the same thread covered). The parent of a span is the
  span enclosing it on its thread's stack, so self times never overlap and
  their sum is the time the thread spent under any span.

``snapshot()`` sums the accumulators of every thread that recorded and is
safe to call while they record. ``Spans.loop()`` brackets a worker loop:
``loop_s`` is that thread's wall time inside it, counted up to the end of its
last finished top-level span, so ``loop_s`` and the phases are cut at the same
instant and ``loop_s - sum(self_s)`` is the time no span covered.

The numbers are cumulative since the recorder was made; read them as
differences. Importing this module does not import JAX.
"""

from __future__ import annotations

import threading
import time

_annotation = None  # jax.profiler.TraceAnnotation, looked up at the first span


def _trace_annotation():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class _Thread:
    """One thread's stack of open spans and its finished totals. Only its
    thread writes; ``phases`` maps a name to an immutable ``(n, total_ns,
    self_ns)`` replaced whole, so a reader's copy is never torn."""

    __slots__ = ("stack", "phases", "loop_t0", "loop_base_ns", "loop_ns")

    def __init__(self) -> None:
        self.stack: list[_Span] = []
        self.phases: dict[str, tuple[int, int, int]] = {}
        self.loop_t0: int | None = None
        self.loop_base_ns = 0  # loops that ended
        self.loop_ns = 0


class _Span:
    __slots__ = ("_thread", "_name", "_trace", "_t0", "_children_ns")

    def __init__(self, thread: _Thread, name: str, attrs: dict) -> None:
        self._thread = thread
        self._name = name
        self._trace = _trace_annotation()(name, **attrs)

    def __enter__(self) -> "_Span":
        self._children_ns = 0
        self._thread.stack.append(self)
        self._trace.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._trace.__exit__(*exc)
        th = self._thread
        th.stack.pop()
        dur = t1 - self._t0
        n, total, own = th.phases.get(self._name, (0, 0, 0))
        th.phases[self._name] = (n + 1, total + dur, own + dur - self._children_ns)
        if th.stack:
            th.stack[-1]._children_ns += dur
        elif th.loop_t0 is not None:
            th.loop_ns = th.loop_base_ns + t1 - th.loop_t0


class _SpanSince(_Span):
    """A span that began before its recorder could be made (a boot's first:
    importing what the recorder needs is part of what it measures). The
    trace event starts where the span is entered; the accumulator counts
    from ``since_ns`` on ``time.perf_counter_ns``'s clock."""

    __slots__ = ("_since",)

    def __init__(self, thread: _Thread, name: str, attrs: dict, since_ns: int) -> None:
        super().__init__(thread, name, attrs)
        self._since = since_ns

    def __enter__(self) -> "_SpanSince":
        super().__enter__()
        self._t0 = self._since
        return self


class _Loop:
    __slots__ = ("_thread",)

    def __init__(self, thread: _Thread) -> None:
        self._thread = thread

    def __enter__(self) -> None:
        self._thread.loop_t0 = time.perf_counter_ns()

    def __exit__(self, *exc) -> None:
        th = self._thread
        th.loop_base_ns = th.loop_ns = th.loop_base_ns + time.perf_counter_ns() - th.loop_t0
        th.loop_t0 = None


class Spans:
    """A recorder, owned by whatever it measures (an engine has one, so two
    engines in a process keep their phases apart)."""

    def __init__(self) -> None:
        self._local = threading.local()
        self._threads: list[_Thread] = []
        self._lock = threading.Lock()  # guards the list, never a span

    def _thread(self) -> _Thread:
        th = getattr(self._local, "thread", None)
        if th is None:
            th = self._local.thread = _Thread()
            with self._lock:
                self._threads.append(th)
        return th

    def span(self, name: str, **attrs) -> _Span:
        """``attrs`` go to the trace event only: ``request_id=`` on a
        request-scoped span, ``lanes=``/``tokens=`` on a batch-scoped one."""
        return _Span(self._thread(), name, attrs)

    def note(self, **attrs) -> None:
        """Attributes for the trace event of the innermost span open on the
        calling thread, known only once it is under way (a launch's facts,
        at its dispatch)."""
        stack = self._thread().stack
        if stack:
            stack[-1]._trace.set_metadata(**attrs)

    def span_since(self, name: str, since_ns: int, **attrs) -> _Span:
        """A span counted from ``since_ns``, an earlier reading of
        ``time.perf_counter_ns`` on the calling thread."""
        return _SpanSince(self._thread(), name, attrs, since_ns)

    def loop(self) -> _Loop:
        return _Loop(self._thread())

    def snapshot(self) -> dict:
        with self._lock:
            threads = list(self._threads)
        phases: dict[str, list[int]] = {}
        loop_ns = 0
        for th in threads:
            loop_ns += th.loop_ns
            for name, (n, total, own) in th.phases.copy().items():
                acc = phases.setdefault(name, [0, 0, 0])
                acc[0] += n
                acc[1] += total
                acc[2] += own
        return {
            "loop_s": loop_ns / 1e9,
            "phases": {
                name: {"n": n, "self_s": own / 1e9, "total_s": total / 1e9}
                for name, (n, total, own) in sorted(phases.items())
            },
        }
