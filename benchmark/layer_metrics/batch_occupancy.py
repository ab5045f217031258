"""scheduler: mean share of the ``max_batch`` lanes that held a request, per
decode dispatch in the window. The engine exports the lifetime mean and the
dispatch count; their product is the sum, whose difference is the window's."""

from harness import counters


def read(before, after, responses, trace, cell):
    def occupancy_sum(docs):
        return sum((m.get("batch_occupancy") or 0.0) * (m.get("decode_steps") or 0) for m in docs)

    steps = counters.delta(before, after, "decode_steps")
    return (occupancy_sum(after) - occupancy_sum(before)) / steps if steps > 0 else None
