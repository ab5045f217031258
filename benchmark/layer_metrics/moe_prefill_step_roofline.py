"""kernels: prefill's achieved share of the chip's bf16 peak in a cell of the
``olmoe`` family, from the device trace. The method is
``prefill_step_roofline``'s, imported and not copied: launches and device
time of ``jit_prefill`` from the trace, the tokens of a launch from the
prompts sent, and the FLOPs from the configuration's family
(``families/olmoe.py``: the eight routed experts of 64, so the all-experts
einsum's eightfold work shows as a low share)."""

from layer_metrics.prefill_step_roofline import read  # noqa: F401
