"""model runner: the milliseconds one launch of ``jit_prefill_with_decode`` (a
prefill chunk that carries the decode lanes' step) was in service, at the
engine's largest mixed bucket, over the window: the difference of ``device_s``
over that of ``timed_n`` in the engine's launch ledger (``/metrics``
``launches``), which times a launch from the previous readback's return (or
from its own hand-over to the device, if later) to its own readback's, and
only where no other launch lay between. On a device kept busy that is the
tick's period, the module's device time over the device's busy share; it is
taken over thousands of launches, where a capture holds a few dozen."""

from layer_metrics.mixed_ride_share import MIXED, delta, ledgers


def read(before, after, responses, trace, cell):
    found = ledgers(after)
    buckets = [int(k) for ledger in found or () for k in ledger.get(MIXED, {})]
    if not buckets:
        return None
    key = str(max(buckets))
    launches = delta(before, after, "timed_n", MIXED, key)
    if not launches or launches <= 0:
        return None
    return 1000.0 * delta(before, after, "device_s", MIXED, key) / launches
