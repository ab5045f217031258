"""kernels: ``kda_decode_roofline`` in the cell of the ``solar-open2-250b-ep8-1chip``
configuration: the same ``read`` (the device time of the trace's ops named
``kda_decode`` against calls x bytes a call over the chip's rate), which takes the
bytes of a call and the calls of a step from the cell's own family
(``families/solar_open2.kda_decode_bytes``: 64 heads x 128 x 128 x float32 a lane, read
and written; ``kernel_calls_per_step``: 6 KDA layers). Imported under a name of its
own, not copied. ``None`` where the op is not among the ten the trace keeps."""

from layer_metrics.kda_decode_roofline import read  # noqa: F401
