"""host: programs the engine process compiled afresh and stored up to ready
(``boot.compile_cache_at_ready.misses``). JAX records a miss where it writes
the entry, so compiles under ``jax_persistent_cache_min_compile_time_secs``
(0.5), which are never stored, do not count (``requests - hits - misses`` of
the same block): a warm run reads 0, and anything above counts what an edit
threw out of the cache. The largest over the engines."""

from harness import boot


def read(before, after, responses, trace, cell):
    return boot.largest(after, lambda b: boot.at_ready(b, "misses"))
