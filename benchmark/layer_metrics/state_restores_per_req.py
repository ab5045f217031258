"""cache manager: slots restored from a snapshot (``cache.state_restores`` of
the engines' ``/metrics``: K/V rows, recurrent state and conv state written
back into a lane) over the requests that got their reply in the window. With
more sessions alive than lanes a returning turn whose session was evicted
either restores or prefills its whole history again; this is the share that
restored (``prefill_over_new_tokens`` shows the rest). ``None`` for a program
whose cache has no per-lane state."""

from harness import counters


def read(before, after, responses, trace, cell):
    def restores(docs):
        found = [(m.get("cache") or {}).get("state_restores") for m in docs]
        return None if any(x is None for x in found) or not found else float(sum(found))

    finished = counters.delta(before, after, "requests_finished")
    a, b = restores(after), restores(before)
    if a is None or b is None or finished <= 0:
        return None
    return (a - b) / finished
