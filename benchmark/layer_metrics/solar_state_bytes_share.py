"""cache manager: the share of a decode step's bytes that is the linear mixer's per-lane
state: the KDA state and conv bytes the lanes in use read and write a step (``linear.
state_bytes_lane`` and ``cache.conv_bytes`` of the engines' ``/metrics``, a lane's
share, x ``batch_occupancy`` x ``max_batch`` x 2) over the bytes the step must move
(``families/solar_open2.decode_step_bytes`` at those lanes and the window's mean
context). Says how much of the step the mechanism is: a third at 64 lanes and 1.3k of
context, falling as contexts grow. ``None`` for a program whose cache has no per-lane
state."""

from harness.family import family_of

from layer_metrics.solar_decode_step_roofline import window_lanes_and_context


def read(before, after, responses, trace, cell):
    family = family_of(cell["config"])
    m = after[0] if after else {}
    linear, cache = m.get("linear") or {}, m.get("cache") or {}
    found = window_lanes_and_context(before, after, responses, trace, cell)
    if not linear.get("state_bytes_lane") or found is None or not hasattr(family, "experts_chosen"):
        return None
    lanes, context = found
    lane_bytes = linear["state_bytes_lane"] + (cache.get("conv_bytes") or 0) / float(m.get("max_batch") or 1)
    return 2.0 * lanes * lane_bytes / family.decode_step_bytes(cell["config"], lanes * context, live_lanes=lanes)
