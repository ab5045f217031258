"""kernels: the decode step's share of its memory roofline, from the device
trace. A model-step number, named as such: per-kernel shares wait for stable
kernel names inside the program.

Least time = (weight-streaming passes in the traced span) x (bytes a pass must
read: every layer's weights as served and the output head, plus the keys and
values of the live context; the arithmetic is the configuration's family's,
``harness/family.py``) / the chip's peak bytes/s. A pass is one decode step or
one speculative verify forward. Passes = launches of the decode modules in the
trace x the mean steps per launch + launches of the verify modules; measured
time = device time of those modules.
Launches and time come from the trace's device plane. Steps per launch and
the lanes in use come from the engine's counters (``decode_chunk_hist``,
``batch_occupancy``) read right before and right after the trace, so they
describe the same span in the same regime; the mean context is the mix's
(the window's replies) and weighs a fiftieth of the weights. Memory bounds
the step: at 8 lanes it does 16 FLOPs per weight byte against the chip's 240.
"""

from harness import counters, peaks
from harness.family import family_of

from layer_metrics import batch_occupancy

DECODE = ("jit_decode_n",)
VERIFY = ("jit_verify",)


def read(before, after, responses, trace, cell):
    if not trace or not trace.get("modules") or not trace.get("counters_after"):
        return None
    around = trace["counters_before"], trace["counters_after"]
    mods = trace["modules"]
    dec = [v for k, v in mods.items() if k.startswith(DECODE)]
    ver = [v for k, v in mods.items() if k.startswith(VERIFY)]
    time_s = sum(v["time_s"] for v in dec + ver)
    hist = counters.hist_delta(*around, "decode_chunk_hist")
    launches = sum(hist.values())
    if time_s <= 0 or launches <= 0:
        return None
    steps_per_launch = sum(k * v for k, v in hist.items()) / launches
    passes = sum(v["count"] for v in dec) * steps_per_launch + sum(v["count"] for v in ver)
    ok = [r for r in responses if r.get("ok")]
    mean_context = sum(r["context_tokens"] for r in ok) / len(ok) if ok else 0.0
    occupancy = batch_occupancy.read(*around, responses, trace, cell) or 0.0
    lanes = (around[1][0].get("max_batch") or 8) * occupancy
    need = family_of(cell["config"]).decode_step_bytes(cell["config"], live_kv_tokens=lanes * mean_context)
    least_s = passes * need / peaks.peaks_of(cell["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / time_s
