"""model runner: seconds of ``boot.weights``, the ``params = ...`` block of
``LLMEngine.create``: a checkpoint read, or synthetic or random weights
generated, quantised and placed (the host's part: what the device still owes
when the last leaf is dispatched flows into the next stage). The largest over
the engines."""

from harness import boot


def read(before, after, responses, trace, cell):
    return boot.largest(after, lambda b: boot.total_s(b, "boot.weights"))
