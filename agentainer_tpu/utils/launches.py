"""The launch ledger: every device launch counted once where it is dispatched
and timed where its output is read back.

Two calls. ``opened = ledger.dispatched(program, key, steps=, rows=, lanes=)``
when the jitted call has returned, i.e. the host has handed the launch over:
``program`` is the XLA module's name as a device trace shows it, ``key`` what
tells the programs of one name apart (a decode rung, a chunk's bucket, a
verify's K). ``ledger.ready(opened)`` where the read of that launch's output
returns, for a launch whose output somebody reads back.

The timing rule. Launches run in dispatch order on one stream, and those read
back are read in that order. For a launch L that is read back, with P the
launch read back before it, the interval is ``ready(L) - max(ready(P),
dispatched(the first launch after P))``: the device could not start before P
ended nor before the host had handed it work, so idle time the host made
before a dispatch is left out. If L is the only launch dispatched since P, the
interval is L's own: ``device_s``, ``timed_n`` and ``timed_steps`` of
``(program, key)`` grow. Otherwise (a launch nobody reads lay in between) it
belongs to several launches: ``shared.n`` counts it and its seconds go to no
program. Intervals never overlap.

What ``device_s`` is. The seconds a launch was in service: on a device that is
kept busy, the period of the tick (the previous readback's return to its own),
which is the module's device time over the device's busy share; after an idle
device, the module's time plus the readback's latency. It is what a launch
costs an operator, not the module's device time.

What it cannot see. A launch whose output nobody reads has no time of its own.
Small device programs the ledger is not told of (a lane's injection, a
state's admission, key splits, snapshot and restore, prefix forks) fall into
the next interval: they are part of what a tick costs in service. A ``ready``
stamp is the host's, so it is late by whatever the reader did between the
output landing and its read returning; idle time of the device while the
worker was busy on the host and had not yet come to read is inside the
interval, so the ledger gives no busy share of the device: a profiler's
capture does.

``cut()`` breaks the chain (a worker fault, a reallocated arena): the next
launch has no P and its interval starts at its own dispatch. ``reset()`` also
zeroes the rows (warm-up is not serving telemetry).

``snapshot()`` is cumulative since the last reset and safe to call from
another thread; read it as differences. Importing this module does not import
JAX.
"""

from __future__ import annotations

import threading
import time
from collections import deque

FIELDS = ("n", "steps", "rows", "lanes", "timed_n", "timed_steps", "device_s")
_N, _STEPS, _ROWS, _LANES, _TIMED_N, _TIMED_STEPS, _DEVICE_S = range(len(FIELDS))


class Launch:
    """One dispatched launch: its facts, and when the host handed it over."""

    __slots__ = ("program", "key", "steps", "rows", "lanes", "at", "epoch")

    def __init__(self, program, key, steps, rows, lanes, at, epoch) -> None:
        self.program = program
        self.key = key
        self.steps = steps
        self.rows = rows
        self.lanes = lanes
        self.at = at
        self.epoch = epoch

    def attrs(self) -> dict:
        """What the launch's dispatch span carries on its trace event."""
        return {
            "program": self.program, "key": self.key, "steps": self.steps, "rows": self.rows, "lanes": self.lanes,
        }


class Launches:
    """A ledger, owned by what launches (an engine has one beside its
    ``Spans``). ``clock`` returns seconds; tests pass their own."""

    def __init__(self, clock=time.perf_counter) -> None:
        self._clock = clock
        self._lock = threading.Lock()
        self._epoch = 0
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._rows: dict[tuple[str, str], list] = {}
            self._shared = 0  # readbacks whose interval belongs to several launches
            self._epoch += 1
            self._open: deque[Launch] = deque()  # dispatched since the last ready, in order
            self._prev_ready: float | None = None  # None: the chain is broken

    def cut(self) -> None:
        with self._lock:
            self._epoch += 1
            self._open.clear()
            self._prev_ready = None

    def dispatched(self, program: str, key, *, steps: int = 1, rows: int = 0, lanes: int = 0) -> Launch:
        with self._lock:
            launch = Launch(program, str(key), steps, rows, lanes, self._clock(), self._epoch)
            row = self._rows.get((program, launch.key))
            if row is None:
                row = self._rows[(program, launch.key)] = [0, 0, 0, 0, 0, 0, 0.0]
            row[_N] += 1
            row[_STEPS] += steps
            row[_ROWS] += rows
            row[_LANES] += lanes
            self._open.append(launch)
        return launch

    def ready(self, launch: Launch) -> None:
        now = self._clock()
        with self._lock:
            if launch.epoch != self._epoch or not self._open:
                return  # dispatched before a cut: its chain is gone
            first = popped = self._open.popleft()
            while popped is not launch and self._open:
                popped = self._open.popleft()  # dispatched before it, and nobody read them
            if first is launch:
                start = first.at if self._prev_ready is None else max(self._prev_ready, first.at)
                row = self._rows[(launch.program, launch.key)]
                row[_TIMED_N] += 1
                row[_TIMED_STEPS] += launch.steps
                row[_DEVICE_S] += max(0.0, now - start)
            else:
                self._shared += 1
            self._prev_ready = now

    def total(self, field: str, *programs: str) -> float:
        """The sum of one field over the rows of ``programs`` (all of them
        where none is named)."""
        i = FIELDS.index(field)
        with self._lock:
            return sum(row[i] for (program, _), row in self._rows.items() if not programs or program in programs)

    def reads(self) -> int:
        """Readbacks that returned: every ``ready`` the chain took."""
        with self._lock:
            return self._shared + sum(row[_TIMED_N] for row in self._rows.values())

    def by_key(self, *programs: str) -> dict[str, int]:
        """Launches by key, summed over ``programs``."""
        out: dict[str, int] = {}
        with self._lock:
            for (program, key), row in self._rows.items():
                if program in programs:
                    out[key] = out.get(key, 0) + row[_N]
        return out

    def snapshot(self) -> dict:
        with self._lock:
            doc: dict = {}
            for (program, key), row in sorted(self._rows.items()):
                doc.setdefault(program, {})[key] = dict(zip(FIELDS, row))
            doc["shared"] = {"n": self._shared}
        return doc
