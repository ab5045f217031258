"""scheduler: median wait from submit to the first prefill chunk's dispatch
(the engine's ``admission_ms`` samples at the window's end)."""

from harness import counters


def read(before, after, responses, trace, cell):
    return counters.recent_median(after, "admission_samples")
