"""model runner: median of the replies' own ``ttft_ms`` (submit to first
token on the host, as the engine measured it) over the window's requests."""

from harness import stats


def read(before, after, responses, trace, cell):
    return stats.median([float(r["ttft_ms"]) for r in responses if r.get("ok") and r.get("ttft_ms") is not None])
