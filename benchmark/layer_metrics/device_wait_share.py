"""scheduler: share of the worker loop's wall time (``loop_s``) in which the
worker was held by the device: the self time of ``engine.wait_device`` (the
forced waits on a readback, and every other host read the worker blocks in).
Self time, so a prefill chunk dispatched from inside a wait is not waiting.
Higher is better: the device should be the one that is waited for. It cannot
pass the device's busy share of the same span."""

from harness import counters, phases

SPAN = "engine.wait_device"


def read(before, after, responses, trace, cell):
    loop_s = counters.delta(before, after, "loop_s")
    if loop_s <= 0 or SPAN not in phases.names(after):
        return None
    return phases.delta(before, after, SPAN, "self_s") / loop_s
