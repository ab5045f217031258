"""cache manager: per-lane recurrent states zeroed for a fresh context
(``cache.state_resets`` of the engines' ``/metrics``) over the requests that
got their reply in the window. Every request of a single-turn mix starts a
new context, so the reading is 1.0 there (up to the requests the window's
edges cut): less is a session that began on another session's state, more a
state thrown away and rebuilt. ``None`` for a program whose cache has no
per-lane state."""

from harness import counters


def read(before, after, responses, trace, cell):
    def resets(docs):
        found = [(m.get("cache") or {}).get("state_resets") for m in docs]
        return None if any(x is None for x in found) or not found else float(sum(found))

    finished = counters.delta(before, after, "requests_finished")
    a, b = resets(after), resets(before)
    if a is None or b is None or finished <= 0:
        return None
    return (a - b) / finished
