"""scheduler: median latency of the window's requests, from the instant a
request was due to the last byte of its reply. Not judged end to end: both
cells are closed loops, so by Little's law it mirrors ``req_per_s`` (over
nine ``mixtral.longprompt`` runs the two moved as one), and a median over a
hundred requests of very different sizes repeats worse than the rate."""

import math

from harness import stats


def read(before, after, responses, trace, cell):
    p = stats.percentile([r["latency_ms"] if r.get("ok") else math.inf for r in responses], 50.0)
    return None if p is None or math.isinf(p) else p
