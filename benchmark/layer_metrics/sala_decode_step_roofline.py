"""kernels: a floor of the whole decode step's share of its memory roofline in
the cell of the ``minicpm_sala`` family, from the device trace: the launches
and the device time of ``jit_decode_n`` in the trace against this family's
bytes a step (``families/minicpm_sala.decode_step_bytes``: the weights as
served once, 9.2 GB; the lightning state of the lanes in use read AND written,
50 MB a lane each way; the K and V rows the 8 sparse layers read of each
lane, 4,096 a K/V head past ``dense_len``; the pooled keys their selection
scores) over the chip's rate.

``olmo_hybrid_decode_step_roofline``'s method: the trace does not say how many
steps a launch ran, so every traced launch is counted at the SHORTEST rung the
ladder dispatched in the span: a floor, never above the share. The lanes'
contexts are taken as the mean context of the window's requests. ``None`` for
a program without the family's mixers (it cannot serve the cell), or where the
trace holds no decode launch."""

from harness import counters, peaks
from harness.family import family_of

from layer_metrics.kda_decode_roofline import DECODE, live_lanes


def read(before, after, responses, trace, cell):
    if not trace or not trace.get("modules") or not trace.get("counters_after"):
        return None
    family = family_of(cell["config"])
    dec = [v for k, v in trace["modules"].items() if k.startswith(DECODE)]
    time_s = sum(v["time_s"] for v in dec)
    hist = counters.hist_delta(trace["counters_before"], trace["counters_after"], "decode_chunk_hist")
    rungs = [k for k, v in hist.items() if v > 0]
    ok = [r for r in responses if r.get("ok")]
    if time_s <= 0 or not rungs or not ok or not hasattr(family, "decode_step_floor_s"):
        return None
    steps = sum(v["count"] for v in dec) * min(rungs)
    lanes = live_lanes(trace, responses, cell)
    mean_context = sum(r["context_tokens"] for r in ok) / len(ok)
    rate = peaks.peaks_of(cell["device"]["kind"])["hbm_bytes_per_s"]
    floor_s = family.decode_step_floor_s(cell["config"], [mean_context] * max(int(round(lanes)), 1), rate, live_lanes=lanes)
    return 100.0 * steps * floor_s / time_s
