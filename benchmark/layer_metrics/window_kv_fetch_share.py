"""kernels: of the K/V blocks an unbounded read of the window layers would
fetch for the decode steps of the window, the share the ring's two bounds let
through (``attention.window_decode_blocks_live`` over
``attention.window_decode_blocks_unbounded`` of the engines' ``/metrics``,
both counted at each decode launch from the stepping lanes' positions: what
``flash_decode``'s index map fetches of a window layer's ring, against what
the same lanes would fetch of a window layer that kept its whole context).
1 while no context has passed the window; under 1 from there on. ``None`` for
a program whose cache has no ring."""

from harness import counters


def window_counters(docs: list[dict]) -> list[dict]:
    """The ``attention`` blocks that count by kind of layer."""
    blocks = [m.get("attention") or {} for m in docs]
    return blocks if blocks and all("window_decode_blocks_live" in a for a in blocks) else []


def read(before, after, responses, trace, cell):
    a, b = window_counters(after), window_counters(before)
    if not a or not b:
        return None
    unbounded = counters.delta(b, a, "window_decode_blocks_unbounded")
    if unbounded <= 0:
        return None
    return counters.delta(b, a, "window_decode_blocks_live") / unbounded
