"""kernels: prefill's achieved share of the chip's bf16 peak, from the device
trace: FLOPs the algorithm needs for the prompt tokens prefilled while the
trace ran (routed experts only; the arithmetic is the configuration's
family's, ``harness/family.py``) over the device time of the prefill modules,
over the peak. Compute bounds a 256-token chunk.

Both the launches and their device time come from the trace's device plane.
The engine counts no prefill launches or tokens, so the tokens of a launch
come from what the generator sent: a prompt of P tokens is fed in
ceil(P / chunk) launches, so a launch carries sum(P) / sum(ceil(P / chunk))
tokens on average over the window's requests (235 of 256 in ``doc-closed``).
That holds where no part of a prompt comes from the prefix arena: the reader
is for cells of unshared prompts, and overstates elsewhere.
"""

import math

from harness import peaks
from harness.family import family_of

PREFILL = ("jit_prefill",)
PREFILL_CHUNK = 256  # the engine's shipped default of its ``prefill_chunk`` option


def read(before, after, responses, trace, cell):
    if not trace or not trace.get("modules"):
        return None
    mods = [v for k, v in trace["modules"].items() if k.startswith(PREFILL)]
    time_s = sum(v["time_s"] for v in mods)
    prompts = [r["want_prompt_tokens"] for r in responses if r.get("ok")]
    if time_s <= 0 or not prompts:
        return None
    chunk = int((cell["config"].get("engine_options") or {}).get("prefill_chunk", PREFILL_CHUNK))
    tokens = sum(v["count"] for v in mods) * sum(prompts) / sum(math.ceil(p / chunk) for p in prompts)
    # a token at position j attends to j others: the mean over a prompt's
    # tokens is half its length, weighted here by the prompts' lengths
    mean_context = sum(p * p for p in prompts) / (2.0 * sum(prompts))
    flops = family_of(cell["config"]).prefill_flops(cell["config"], tokens, mean_context)
    return 100.0 * flops / time_s / peaks.peaks_of(cell["device"]["kind"])["bf16_flops"]
