"""kernels: the prefill launches' share of their floor in a cell of the ``laguna``
family, from the device trace: in this cell nearly every tick is ONE launch that
carries a lane's 256-row chunk and the other lanes' decode step
(``jit_prefill_with_decode``; a chunk with no rider is ``jit_prefill``). The
launches and their device time are the trace's (modules whose name starts
``jit_prefill``). What a launch carried is the engine's own count around the trace
(``counters_before`` / ``counters_after``): ``prefill_tokens`` / ``prefill_launches``
real rows a launch, ``mixed_decode_lanes`` / ``prefill_launches`` riders, and the
K/V rows the riders' queries see by kind of layer
(``attention.global_decode_rows`` / ``window_decode_rows``, scaled by the riders'
share of the decode steps counted: ``rows_positioned`` less ``prefill_tokens``).
The K/V rows a CHUNK reads of its own lane are not counted by the engine and come
from the prompts the window's requests sent
(``families/laguna.chunk_rows_read``, ``attended_rows``: the mean over their
launches). The floor is ``families/laguna.mixed_step_floor_s``: the larger of the
bytes any implementation must move (weights as served once, those K/V rows once)
over the memory's rate and the model's FLOPs over the bf16 peak. A floor, so the
share cannot pass 100 %. ``None`` where the trace holds no such launch, or for a
program without the counters (a parent from before them, another family)."""

from harness import counters, peaks
from harness.family import family_of

from layer_metrics.prefill_step_roofline import PREFILL, PREFILL_CHUNK
from layer_metrics.window_kv_fetch_share import window_counters


def read(before, after, responses, trace, cell):
    if not trace or not trace.get("modules") or not trace.get("counters_after") or not trace.get("counters_before"):
        return None
    family = family_of(cell["config"])
    if not hasattr(family, "mixed_step_floor_s"):
        return None
    mods = [v for k, v in trace["modules"].items() if k.startswith(PREFILL)]
    time_s, launches = sum(v["time_s"] for v in mods), sum(v["count"] for v in mods)
    cb, ca = trace["counters_before"], trace["counters_after"]
    if any(k not in m for m in (*cb, *ca) for k in ("prefill_launches", "prefill_tokens", "mixed_decode_lanes")):
        return None
    wb, wa = window_counters(cb), window_counters(ca)
    counted = counters.delta(cb, ca, "prefill_launches")
    prompts = [r["want_prompt_tokens"] for r in responses if r.get("ok")]
    if time_s <= 0 or launches <= 0 or counted <= 0 or not prompts or not wa or not wb or "rows_positioned" not in wa[0]:
        return None
    doc = cell["config"]
    chunk = int((doc.get("engine_options") or {}).get("prefill_chunk", PREFILL_CHUNK))
    rows = counters.delta(cb, ca, "prefill_tokens") / counted
    lanes = counters.delta(cb, ca, "mixed_decode_lanes") / counted
    # the riders' share of the decode steps the engine counted around the trace
    steps = counters.delta(wb, wa, "rows_positioned") - counters.delta(cb, ca, "prefill_tokens")
    riding = min(1.0, counters.delta(cb, ca, "mixed_decode_lanes") / steps) if steps > 0 else 0.0
    lane_rows = {k: riding * counters.delta(wb, wa, f"{k}_decode_rows") / counted for k in ("global", "window")}
    # a chunk's own lane, from the prompts sent: rows read a launch, and (query, key) pairs
    reads = [family.chunk_rows_read(doc, p, chunk) for p in prompts]
    pairs = [family.attended_rows(doc, p) for p in prompts]
    n = sum(r["launches"] for r in reads)
    attended = {
        "global_rows": sum(r["global"] for r in reads) / n + lane_rows["global"],
        "window_rows": sum(r["window"] for r in reads) / n + lane_rows["window"],
        "global_pairs": sum(x["global"] for x in pairs) / n + lane_rows["global"],
        "window_pairs": sum(x["window"] for x in pairs) / n + lane_rows["window"],
    }
    floor_s = family.mixed_step_floor_s(doc, rows, lanes, attended, peaks.peaks_of(cell["device"]["kind"]))
    return 100.0 * launches * floor_s / time_s
