"""kernels: the decode step's share of its memory roofline in a cell of the
``olmoe`` family, from the device trace. The method is
``decode_step_roofline``'s, imported and not copied: launches and device time
of ``jit_decode_n`` (and ``jit_verify``) from the trace, steps a launch and
lanes in use from the engine's counters around the trace, and the bytes a
step must read from the configuration's family (``families/olmoe.py``: every
layer's int8 weights with all 64 experts, the output head, and the keys and
values of the live context: 131 KB a token, 1.5 to 2.5 GB live at 16 lanes
beside 6.9 GB of weights). Memory bounds the step."""

from layer_metrics.decode_step_roofline import read  # noqa: F401
