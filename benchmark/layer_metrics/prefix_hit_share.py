"""cache manager: share of fresh-context admissions in the window that forked
a cached prefix from the prefix arena."""

from harness import counters


def read(before, after, responses, trace, cell):
    hits = counters.delta(before, after, "prefix_hits")
    lookups = hits + counters.delta(before, after, "prefix_misses")
    return hits / lookups if lookups > 0 else None
