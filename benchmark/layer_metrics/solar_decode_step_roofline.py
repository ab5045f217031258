"""kernels: the whole decode step's share of its memory roofline in the cell of the
``solar_open2`` family, over the WHOLE window and from the engine's launch ledger, not
from a capture: the least time a step can take (``families/solar_open2.
decode_step_floor_s``: the weights as served with the routed experts the lanes' tokens
CHOOSE, the KDA and conv state of the lanes in use read and written, the K/V rows of
their contexts, over the chip's memory rate) over the time a step of ``jit_decode_n``
was in service (``decode_step_ms``: the ledger's ``device_s`` over ``timed_steps`` at
the configured rung, thousands of launches, none cut by an edge).

The lanes in use are ``batch_occupancy`` x ``max_batch`` over the window; a lane's
context is taken as the mean over its reply of the window's requests (prompt + half the
reply). A capture's edges cut launches, which is how ``kimi_decode_step_roofline`` and
``olmo_hybrid_decode_step_roofline`` came to read over 100 % (ledger notes, PRs 54 and
55; PERF.md section 7): the floor here counts fewer bytes than today's step reads (its
einsum reads every held expert), so the share cannot pass 100. ``None`` for a program
without the launch ledger, where no step of the rung was timed, or for a family
without this one's arithmetic (``experts_chosen``: the other families' ``decode_step_*``
take other arguments), or off the chip (a CPU rehearsal's step time is no device's)."""

from harness import peaks
from harness.family import family_of

from layer_metrics import batch_occupancy, decode_step_ms


def window_lanes_and_context(before, after, responses, trace, cell):
    """(lanes in use, mean context of a stepping lane) over the window, or ``None``."""
    occupancy = batch_occupancy.read(before, after, responses, trace, cell)
    ok = [r for r in responses if r.get("ok")]
    if not occupancy or not ok:
        return None
    lanes = occupancy * float(after[0].get("max_batch") or 1)
    context = sum(r["want_prompt_tokens"] + 0.5 * r["want_completion_tokens"] for r in ok) / len(ok)
    return lanes, context


def read(before, after, responses, trace, cell):
    family = family_of(cell["config"])
    step_ms = decode_step_ms.read(before, after, responses, trace, cell)
    found = window_lanes_and_context(before, after, responses, trace, cell)
    if not step_ms or found is None or not hasattr(family, "experts_chosen"):
        return None
    if cell["device"].get("platform") != "tpu":  # a rehearsal on the CPU: its times are no chip's
        return None
    lanes, context = found
    rate = peaks.peaks_of(cell["device"]["kind"])["hbm_bytes_per_s"]
    floor_s = family.decode_step_floor_s(cell["config"], lanes * context, rate, live_lanes=lanes)
    return 100.0 * floor_s / (step_ms / 1000.0)
