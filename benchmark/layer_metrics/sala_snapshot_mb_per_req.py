"""journal + replay: megabytes of session snapshot the engine hosts packed and
stored for crash-resume over the window, a request served (``kv_snapshot_bytes``
of the engines' ``/metrics``: the packed blobs' sizes, summed where a snapshot
is stored). The journaled path snapshots a session's leaves after a turn, at
most once a session in ``kv_snapshot_interval_s`` and deferred while the
engine is loaded; here a session's leaves are 0.15-0.45 GB (K/V rows of 8
layers, pooled keys, a 50 MB state). ``None`` for a program that does not count
the bytes (the parent), or a window that served nothing."""

from harness import counters


def read(before, after, responses, trace, cell):
    if not after or not all("kv_snapshot_bytes" in m for m in after):
        return None
    served = sum(1 for r in responses if r.get("ok"))
    if not served:
        return None
    return counters.delta(before, after, "kv_snapshot_bytes") / 1e6 / served
