"""kernels: the whole decode step's share of its memory roofline in a cell of
the ``kimi_linear`` family, from the device trace. ``decode_step_roofline``'s
method (launches and device time of ``jit_decode_n`` from the trace, steps a
launch and lanes in use from the engine's counters around it), with the
family's bytes a step: the weights as served with the held experts, the
recurrent and conv state of the lanes in use read AND written (84 MB a lane
both ways), and the latent rows of the live context
(``families/kimi_linear.decode_step_bytes``). A reader of its own because the
state's bytes follow the lanes and not the context."""

from harness import peaks
from harness.family import family_of

from layer_metrics.kda_decode_roofline import DECODE, decode_steps, live_lanes


def read(before, after, responses, trace, cell):
    if not trace or not trace.get("modules") or not trace.get("counters_after"):
        return None
    time_s = sum(v["time_s"] for k, v in trace["modules"].items() if k.startswith(DECODE))
    steps = decode_steps(trace)
    ok = [r for r in responses if r.get("ok")]
    if time_s <= 0 or steps <= 0 or not ok:
        return None
    lanes = live_lanes(trace, responses, cell)
    mean_context = sum(r["context_tokens"] for r in ok) / len(ok)
    need = family_of(cell["config"]).decode_step_bytes(
        cell["config"], live_kv_tokens=lanes * mean_context, live_lanes=lanes
    )
    return 100.0 * steps * need / peaks.peaks_of(cell["device"]["kind"])["hbm_bytes_per_s"] / time_s
