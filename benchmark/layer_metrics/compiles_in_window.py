"""host: programs the engines compiled or loaded from the compile cache inside
the window (``compile_cache.requests``; JAX counts only compiles that consult
the persistent cache) — should be 0."""


def read(before, after, responses, trace, cell):
    def requests(docs):
        return sum(((m.get("compile_cache") or {}).get("requests") or 0) for m in docs)

    return float(requests(after) - requests(before))
