"""scheduler: share of drafted tokens the verify step accepted in the window.
Random weights make greedy output loop, which n-gram drafting predicts well:
this says how flattering the decode numbers are."""

from harness import counters


def read(before, after, responses, trace, cell):
    drafted = counters.delta(before, after, "spec_drafted")
    return counters.delta(before, after, "spec_accepted") / drafted if drafted > 0 else None
