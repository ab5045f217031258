"""host: seconds the engine processes spent tracing, lowering and getting
executables inside the window (``compile_cache.trace_s + lower_s +
compile_s`` from JAX's own duration events) — should be 0: the seconds beside
``compiles_in_window``'s count. ``retrieval_s`` is not added: JAX times a
cache read inside ``compile_s``."""

KEYS = ("trace_s", "lower_s", "compile_s")


def read(before, after, responses, trace, cell):
    def seconds(docs):
        caches = [m.get("compile_cache") or {} for m in docs]
        if not any(k in c for c in caches for k in KEYS):
            return None
        return sum(c.get(k) or 0.0 for c in caches for k in KEYS)

    a, b = seconds(after), seconds(before)
    return None if a is None or b is None else float(a - b)
