"""kernels: the MLA decode kernel's share of its memory roofline, from the
device trace. One call of ``mla_decode`` (``agentainer_tpu/ops/pallas_mla.py``,
found among the trace's ``device_ops`` by that pinned name) attends one MLA
layer: the latent rows of every lane's live context read once for all 32
heads, 1,152 B a token (``families/kimi_linear.mla_decode_bytes``).

The method is ``kda_decode_roofline``'s: calls = decode steps in the traced
span x the MLA layers; the live context = lanes in use x the mean context of
the window's replies. ``None`` where the op is not among the ten the trace
keeps (at 1.9k of context the kernel is about a twentieth of the step).
No ``BENCHMARK.json`` entry lists this reader yet: ``harness/trace_reduce.py``
keeps the ten ops with most device time under their exact names, and in this
program those are the loops (a ``while`` holds its body's time; the kernel's
calls are split over the decode ladder's programs, ``.8`` and ``.9``), so a
served run reads ``None`` (PR 30). It reads a trace reduced with the kernels'
names kept (``benchmark/tests/test_kimi_linear.py``)."""

from harness import peaks
from harness.family import family_of

from layer_metrics.kda_decode_roofline import decode_steps, kernel_time_s, live_lanes

KERNEL = "mla_decode"


def read(before, after, responses, trace, cell):
    if not trace or not trace.get("modules") or not trace.get("counters_after"):
        return None
    family = family_of(cell["config"])
    time_s, steps = kernel_time_s(trace, KERNEL), decode_steps(trace)
    ok = [r for r in responses if r.get("ok")]
    if time_s <= 0 or steps <= 0 or not ok or not hasattr(family, "mla_decode_bytes"):
        return None
    mean_context = sum(r["context_tokens"] for r in ok) / len(ok)
    layers = family.kernel_calls_per_step(cell["config"])[KERNEL]
    need = steps * layers * family.mla_decode_bytes(cell["config"], live_lanes(trace, responses, cell) * mean_context)
    return 100.0 * need / peaks.peaks_of(cell["device"]["kind"])["hbm_bytes_per_s"] / time_s
