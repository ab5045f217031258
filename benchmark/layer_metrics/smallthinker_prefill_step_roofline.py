"""kernels: prefill's achieved share of the chip's bf16 peak in a cell of the
``smallthinker`` family, from the device trace. ``prefill_step_roofline``'s
method (launches and device time of the prefill modules from the trace, the
tokens of a launch from the prompts sent), with the family's FLOPs
(``families/smallthinker.prefill_flops``: the experts a token is routed to
that are held here, and the attention by kind of layer). The keys a token
attends to are summed prompt by prompt (``attended_rows``): a global layer
sees the whole prompt before it, a window layer the last 4,096, so a long
prompt's window layers are not counted at its length. A launch that carried
the decode lanes' step (``jit_prefill_with_decode``) is a prefill module here
as in the accepted reader; its riders' rows (8 of 264) are not counted as
work. ``None`` for a family without ``attended_rows``.

No ``BENCHMARK.json`` entry lists this reader (PR 37 wrote it for
``smallthinker.mixed`` and took it off again): a listed metric has to be on
the line of EVERY traced run of its cell, this cell's capture is 1.4-2.7 s
of the 5 s asked for (52 layers fill the profiler's buffer), and a capture
that holds no launch of a prefill module reads ``None`` (one traced run of two held 58
mixed launches and not one ``jit_decode_n``). It reads a capture that has
one (PERF.md sections 5 and 7; ``benchmark/tests/test_smallthinker.py``)."""

import math

from harness import peaks
from harness.family import family_of

from layer_metrics.prefill_step_roofline import PREFILL, PREFILL_CHUNK


def read(before, after, responses, trace, cell):
    if not trace or not trace.get("modules"):
        return None
    family = family_of(cell["config"])
    mods = [v for k, v in trace["modules"].items() if k.startswith(PREFILL)]
    time_s = sum(v["time_s"] for v in mods)
    prompts = [r["want_prompt_tokens"] for r in responses if r.get("ok")]
    if time_s <= 0 or not prompts or not hasattr(family, "attended_rows"):
        return None
    chunk = int((cell["config"].get("engine_options") or {}).get("prefill_chunk", PREFILL_CHUNK))
    tokens = sum(v["count"] for v in mods) * sum(prompts) / sum(math.ceil(p / chunk) for p in prompts)
    seen = [family.attended_rows(cell["config"], p) for p in prompts]
    flops = family.prefill_flops(
        cell["config"], tokens,
        mean_context=sum(s["global"] for s in seen) / sum(prompts),
        mean_window_context=sum(s["window"] for s in seen) / sum(prompts),
    )
    return 100.0 * flops / time_s / peaks.peaks_of(cell["device"]["kind"])["bf16_flops"]
