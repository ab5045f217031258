"""model runner: host milliseconds per first-token sample over the window:
``total_s`` over ``n`` of the span ``engine.first_token_sample`` (the
``sample_step`` call after a prompt's last prefill chunk, on the worker
thread while the device waits)."""

from harness import phases

SPAN = "engine.first_token_sample"


def read(before, after, responses, trace, cell):
    n = phases.delta(before, after, SPAN, "n")
    return 1000.0 * phases.delta(before, after, SPAN, "total_s") / n if n > 0 else None
