"""kernels: the windowed ``flash_decode``'s share of its memory roofline, from
the device trace. One call of ``flash_decode`` (``agentainer_tpu/ops/
pallas_attention.py``, found among the trace's ``device_ops`` by that pinned
name) attends ONE layer for every lane: the K and V rows its queries see.
The trace does not tell a window layer's calls from a global layer's (one
kernel, one name), so this is the kernel over BOTH kinds: least bytes = the
rows the engine counted around the trace (``smallthinker_decode_step_roofline.
kv_bytes_per_step``: ``min(position + 1, window)`` rows a lane in a window
layer, the context in a global one) x the decode steps in the traced span,
those of ``jit_decode_n`` and the one step each ``jit_prefill_with_decode``
launch carries (the kernel runs in both programs and the trace sums its ops
by name), over the device time of the ops of that name.

No ``BENCHMARK.json`` entry lists this reader: ``harness/trace_reduce.py``
keeps the ten ops with most device time under their exact names. In this
cell the kernel IS among them (my chip run, PR 37: ``flash_decode.12`` 0.214 s,
the window layers' call, and ``flash_decode.11`` 0.129 s, the global layers',
of a 1.375 s capture; the same two names in both programs), but as one op a
KIND of layer under a suffix nobody pins, and a capture that keeps one kind's
op and drops the other's would read over its roofline; PERF.md section 7
lists it beside PR 30's and
PR 32's kernel readers for the ``benchmark`` PR that keeps kernel names
(ROADMAP A0(a)). It reads a trace reduced with the kernels' names kept
(``benchmark/tests/test_smallthinker.py``)."""

from harness import peaks

from layer_metrics.kda_decode_roofline import decode_steps, kernel_time_s
from layer_metrics.smallthinker_decode_step_roofline import kv_bytes_per_step

KERNEL = "flash_decode"
MIXED = ("jit_prefill_with_decode",)


def read(before, after, responses, trace, cell):
    if not trace or not trace.get("modules") or not trace.get("counters_after"):
        return None
    mixed = sum(v["count"] for k, v in trace["modules"].items() if k.startswith(MIXED))
    time_s, steps = kernel_time_s(trace, KERNEL), decode_steps(trace) + mixed
    kv = kv_bytes_per_step(trace, cell)
    if time_s <= 0 or steps <= 0 or kv is None:
        return None
    return 100.0 * steps * kv / peaks.peaks_of(cell["device"]["kind"])["hbm_bytes_per_s"] / time_s
