"""cache manager: requests whose context lapped a window layer's ring
(``attention.window_wraps``: counted once when a request finishes with its
position past ``attention.window_rows``), over the requests that finished in
the window. Above 0 proves the traffic passes the window and the ring is
overwritten in service; ``None`` for a program whose cache has no ring."""

from harness import counters

from layer_metrics.window_kv_fetch_share import window_counters


def read(before, after, responses, trace, cell):
    a, b = window_counters(after), window_counters(before)
    finished = counters.delta(before, after, "requests_finished")
    if not a or not b or finished <= 0:
        return None
    return counters.delta(b, a, "window_wraps") / finished
