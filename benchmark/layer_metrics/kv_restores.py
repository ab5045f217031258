"""cache manager: returning turns in the window whose session had lost its
slot (``_find_slot`` evicts the idle LRU session) and came back through the
store's KV snapshot."""

from harness import counters


def read(before, after, responses, trace, cell):
    return counters.delta(before, after, "kv_restores")
