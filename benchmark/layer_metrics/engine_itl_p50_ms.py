"""model runner: median gap between tokens as the engine measured it (its
newest ``itl`` samples at the window's end)."""

from harness import counters


def read(before, after, responses, trace, cell):
    return counters.recent_median(after, "itl_samples")
