"""scheduler: first turns (a session's document, 12k-20k tokens of prefill)
that were due inside the window, from the responses. The cell prefills its
documents during the warm-up and measures further turns: this has to read 0,
and the day it does not the cell wants a ``benchmark`` PR (a longer warm-up, or
longer sessions). ``None`` where the window holds no request at all."""


def read(before, after, responses, trace, cell):
    if not responses:
        return None
    return float(sum(1 for r in responses if r.get("turn") == 0))
