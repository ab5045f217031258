"""model runner: the milliseconds one decode step of ``jit_decode_n`` was in
service at the configured ``decode_chunk`` rung, over the window: the
difference of ``device_s`` over that of ``timed_steps`` in the engine's launch
ledger (``/metrics`` ``launches``; the rule is in ``mixed_launch_ms``). The
steady rung is dispatched back to back with nothing waiting on the worker, so
this is the decode control the host hardly moves, beside ``engine_itl_p50_ms``
which it can."""

from layer_metrics.mixed_ride_share import DECODE, delta


def read(before, after, responses, trace, cell):
    rungs = {str(m.get("decode_chunk")) for m in after}
    if len(rungs) != 1:
        return None
    (key,) = rungs
    steps = delta(before, after, "timed_steps", DECODE, key)
    if not steps or steps <= 0:
        return None
    return 1000.0 * delta(before, after, "device_s", DECODE, key) / steps
