"""scheduler: 95th percentile of the request latency, reported among the
per-layer metrics where it does not repeat within an end-to-end bound (a
returning turn can pay a store restore, compiled per restored length)."""

import math

from harness import stats


def read(before, after, responses, trace, cell):
    p = stats.percentile([r["latency_ms"] if r.get("ok") else math.inf for r in responses], 95.0)
    return None if p is None or math.isinf(p) else p
