"""host: milliseconds of the engine worker's own time a finished request cost
over the window: the self time of every phase span (``phases`` of the
engines' ``/metrics``, ``utils/spans.py``) except the two in which the worker
has nothing to do (``engine.wait_request``) or is held by the device
(``engine.wait_device``), over the requests that got their reply
(``requests_finished``). Self times do not overlap, so this is the wall time
the one worker thread spent tracing, dispatching, drafting, admitting,
evicting and delivering, per request; the device waits for it."""

from harness import counters, phases

WAITING = ("engine.wait_request", "engine.wait_device")


def read(before, after, responses, trace, cell):
    finished = counters.delta(before, after, "requests_finished")
    working = [n for n in phases.names(after) if n not in WAITING]
    if finished <= 0 or not working:
        return None
    return 1000.0 * sum(phases.delta(before, after, n, "self_s") for n in working) / finished
