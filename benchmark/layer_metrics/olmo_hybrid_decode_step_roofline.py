"""kernels: a floor of the whole decode step's share of its memory roofline
in a cell of the ``olmo_hybrid`` family, from the device trace: the launches
and the device time of ``jit_decode_n`` in the trace against this family's
bytes a step — the weights as served (7.0 GB: no expert is skipped, the FFN
is dense), the recurrent and conv state of the lanes in use read AND written
(55 MB a lane each way) and the K/V rows of the live context at the head count
they are stored with (``families/olmo_hybrid.decode_step_bytes``).

``kimi_decode_step_roofline``'s method with one difference, because that
method read 170 % here (my chip run, PR 32). It multiplies the launches IN
the trace by the mean steps a launch of the counters AROUND the whole
``/profile`` call; this program runs thousands of small operations a launch,
so the trace holds 0.6 s of the capture, and in 0.6 s of this traffic (bursts
of prefill chunks with one-step decode launches between them, then eight-step
launches once the bursts are admitted) the launches' own mix is not the
span's: 6 launches of 8 steps in all counted as 25. The trace does not say
how many steps a launch ran, and the harness cannot be edited here (PERF.md
section 7 names the edit), so this reader counts what it can be sure of: the
SHORTEST rung the ladder dispatched in the span, for every launch. The reading
is a floor, never above the share: where every traced launch is of that rung
(one step, whenever a chunk waits) it is the share itself. ``None`` for a
program without the family's mixers (it cannot serve the cell), or where the
trace holds no decode launch."""

from harness import counters, peaks
from harness.family import family_of

from layer_metrics.kda_decode_roofline import DECODE, live_lanes


def read(before, after, responses, trace, cell):
    if not trace or not trace.get("modules") or not trace.get("counters_after"):
        return None
    dec = [v for k, v in trace["modules"].items() if k.startswith(DECODE)]
    time_s = sum(v["time_s"] for v in dec)
    hist = counters.hist_delta(trace["counters_before"], trace["counters_after"], "decode_chunk_hist")
    rungs = [k for k, v in hist.items() if v > 0]
    ok = [r for r in responses if r.get("ok")]
    if time_s <= 0 or not rungs or not ok:
        return None
    steps = sum(v["count"] for v in dec) * min(rungs)
    lanes = live_lanes(trace, responses, cell)
    mean_context = sum(r["context_tokens"] for r in ok) / len(ok)
    need = family_of(cell["config"]).decode_step_bytes(
        cell["config"], live_kv_tokens=lanes * mean_context, live_lanes=lanes
    )
    return 100.0 * steps * need / peaks.peaks_of(cell["device"]["kind"])["hbm_bytes_per_s"] / time_s
