"""model runner: real prompt tokens per launch of the prefill step over the
window, as the engine counted them where the launch happens
(``prefill_tokens`` over ``prefill_launches``; bucket padding and tokens the
prefix arena served are not in it). A launch costs the same whatever it
carries up to its bucket, so fuller is better."""

from harness import counters


def read(before, after, responses, trace, cell):
    launches = counters.delta(before, after, "prefill_launches")
    return counters.delta(before, after, "prefill_tokens") / launches if launches > 0 else None
