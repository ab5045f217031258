"""kernels: the KDA decode kernel's share of its memory roofline, from the
device trace. One call of ``kda_decode`` (``agentainer_tpu/ops/pallas_kda.py``,
found among the trace's ``device_ops`` by that pinned name) steps one KDA
layer: every stepping lane's recurrent state read and written once, 128 KB a
lane and head (``families/kimi_linear.kda_decode_bytes``).

Least time = calls x bytes a call / the chip's peak bytes/s; calls = launches
of the decode module in the trace x the mean steps a launch (the engine's
``decode_chunk_hist`` around the trace) x the KDA layers; lanes from
``batch_occupancy`` around the trace; measured time = the device time of the
ops of that name. ``None`` where the op is not among the ten the trace
keeps, or the program has no such kernel.
No ``BENCHMARK.json`` entry lists this reader yet: ``harness/trace_reduce.py``
keeps the ten ops with most device time under their exact names, and in this
program those are the loops (a ``while`` holds its body's time; the kernel's
calls are split over the decode ladder's programs, ``.8`` and ``.9``), so a
served run reads ``None`` (PR 30). It reads a trace reduced with the kernels'
names kept (``benchmark/tests/test_kimi_linear.py``)."""

from harness import counters, peaks
from harness.family import family_of

from layer_metrics import batch_occupancy

KERNEL = "kda_decode"
DECODE = ("jit_decode_n",)


def kernel_time_s(trace: dict, name: str) -> float:
    return sum(t for op, t in trace.get("device_ops") or [] if op.startswith(name))


def decode_steps(trace: dict) -> float:
    """Decode steps (one token for every lane) launched in the traced span."""
    dec = [v for k, v in trace["modules"].items() if k.startswith(DECODE)]
    hist = counters.hist_delta(trace["counters_before"], trace["counters_after"], "decode_chunk_hist")
    launches = sum(hist.values())
    if launches <= 0:
        return 0.0
    return sum(v["count"] for v in dec) * sum(k * v for k, v in hist.items()) / launches


def live_lanes(trace: dict, responses, cell) -> float:
    around = trace["counters_before"], trace["counters_after"]
    occupancy = batch_occupancy.read(*around, responses, trace, cell) or 0.0
    return (around[1][0].get("max_batch") or 1) * occupancy


def read(before, after, responses, trace, cell):
    if not trace or not trace.get("modules") or not trace.get("counters_after"):
        return None
    family = family_of(cell["config"])
    time_s, steps = kernel_time_s(trace, KERNEL), decode_steps(trace)
    if time_s <= 0 or steps <= 0 or not hasattr(family, "kda_decode_bytes"):
        return None
    layers = family.kernel_calls_per_step(cell["config"])[KERNEL]
    need = steps * layers * family.kda_decode_bytes(cell["config"], live_lanes(trace, responses, cell))
    return 100.0 * need / peaks.peaks_of(cell["device"]["kind"])["hbm_bytes_per_s"] / time_s
