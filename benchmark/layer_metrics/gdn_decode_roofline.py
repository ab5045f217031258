"""kernels: the GDN decode kernel's share of its memory roofline, from the
device trace. One call of ``gdn_decode`` (``agentainer_tpu/ops/pallas_kda.py``,
found among the trace's ``device_ops`` by that pinned name) steps one
delta-rule layer: the state tile of EVERY lane of the call read and written
once, a masked lane's written back as read (``families/olmo_hybrid.
gdn_decode_bytes`` at ``max_batch`` lanes: 35 MB a call at 8 lanes of
30 x 96 x 192 float32).

``kda_decode_roofline``'s method and its fate: least time = calls x bytes a
call / the chip's peak bytes/s, calls = decode steps in the traced span x the
delta-rule layers; measured time = the device time of the ops of that name.
No ``BENCHMARK.json`` entry lists this reader: ``harness/trace_reduce.py``
keeps the ten ops with most device time under their exact names, and a kernel
inside the step's loops is not among them, so a served run reads ``None``
(PERF.md section 7). It reads a trace reduced with the kernels' names kept
(``benchmark/tests/test_olmo_hybrid.py``)."""

from harness import peaks
from harness.family import family_of

from layer_metrics.kda_decode_roofline import decode_steps, kernel_time_s

KERNEL = "gdn_decode"


def read(before, after, responses, trace, cell):
    if not trace or not trace.get("modules") or not trace.get("counters_after"):
        return None
    family = family_of(cell["config"])
    time_s, steps = kernel_time_s(trace, KERNEL), decode_steps(trace)
    if time_s <= 0 or steps <= 0 or not hasattr(family, "gdn_decode_bytes"):
        return None
    lanes = float(trace["counters_after"][0].get("max_batch") or 1)
    layers = family.kernel_calls_per_step(cell["config"])[KERNEL]
    need = steps * layers * family.gdn_decode_bytes(cell["config"], lanes)
    return 100.0 * need / peaks.peaks_of(cell["device"]["kind"])["hbm_bytes_per_s"] / time_s
