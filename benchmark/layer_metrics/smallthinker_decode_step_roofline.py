"""kernels: the whole decode step's share of its memory roofline in a cell of
the ``smallthinker`` family, from the device trace. ``decode_step_roofline``'s
method (launches and device time of ``jit_decode_n`` from the trace, steps a
launch from the engine's ``decode_chunk_hist`` around it), with the K and V
bytes of a step taken from the engine's own counters around the trace and not
from the replies' final lengths (PERF.md section 7: those overstate the live
context by a fifth; here K/V is over a third of the step):
``attention.global_decode_rows`` and ``attention.window_decode_rows`` are the
rows the stepping lanes' queries see in a layer of each kind (a window layer
``min(position + 1, window)``), counted at every decode step, riders of a
mixed launch included; ``attention.global_decode_blocks_stored`` counts the
same steps, so rows / steps is a step's mean. Bytes a step =
``families/smallthinker.decode_step_bytes``: the weights as served, once, and
those rows in every layer of their kind. ``None`` for a program without the
counters.

No ``BENCHMARK.json`` entry lists this reader (PR 37 wrote it for
``smallthinker.mixed`` and took it off again): a listed metric has to be on
the line of EVERY traced run of its cell, this cell's capture is 1.4-2.7 s
of the 5 s asked for (52 layers fill the profiler's buffer), and a capture
that holds no launch of ``jit_decode_n`` reads ``None`` (one traced run of two held 58
mixed launches and not one ``jit_decode_n``). It reads a capture that has
one (PERF.md sections 5 and 7; ``benchmark/tests/test_smallthinker.py``)."""

from harness import counters, peaks
from harness.family import family_of

from layer_metrics.kda_decode_roofline import DECODE, decode_steps
from layer_metrics.window_kv_fetch_share import window_counters


def kv_bytes_per_step(trace: dict, cell: dict) -> float | None:
    """Mean K/V bytes the queries of one decode step see, over the steps the
    engine counted around the trace."""
    b, a = window_counters(trace["counters_before"]), window_counters(trace["counters_after"])
    if not a or not b or "global_decode_rows" not in a[0]:
        return None
    doc = a[0]
    blocks_a_step = (trace["counters_after"][0].get("max_batch") or 1) * -(-doc["global_rows"] // doc["decode_block_positions"])
    steps = counters.delta(b, a, "global_decode_blocks_stored") / blocks_a_step
    if steps <= 0:
        return None
    row = family_of(cell["config"]).row_bytes(cell["config"])
    rows = doc["global_layers"] * counters.delta(b, a, "global_decode_rows") + doc["window_layers"] * counters.delta(b, a, "window_decode_rows")
    return row * rows / steps


def read(before, after, responses, trace, cell):
    if not trace or not trace.get("modules") or not trace.get("counters_after"):
        return None
    time_s = sum(v["time_s"] for k, v in trace["modules"].items() if k.startswith(DECODE))
    steps = decode_steps(trace)
    kv = kv_bytes_per_step(trace, cell)
    if time_s <= 0 or steps <= 0 or kv is None:
        return None
    need = family_of(cell["config"]).decode_step_bytes(cell["config"], kv_bytes=kv)
    return 100.0 * steps * need / peaks.peaks_of(cell["device"]["kind"])["hbm_bytes_per_s"] / time_s
