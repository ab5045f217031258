"""host: seconds the engine process spent tracing, lowering and getting
executables up to ready (``boot.compile_cache_at_ready``: ``trace_s +
lower_s + compile_s``, ``jit_s_in_window``'s sum before the window;
``retrieval_s`` is not added: JAX times a cache read inside ``compile_s``).
The largest over the engines."""

from harness import boot

KEYS = ("trace_s", "lower_s", "compile_s")


def read(before, after, responses, trace, cell):
    return boot.largest(after, lambda b: boot.at_ready(b, *KEYS))
