"""cache manager: sessions evicted from their slot in the window."""

from harness import counters


def read(before, after, responses, trace, cell):
    return counters.delta(before, after, "session_evictions_total")
