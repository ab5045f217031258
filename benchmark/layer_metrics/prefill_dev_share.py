"""model runner: share of the device's busy time spent in prefill modules, from
the device trace."""

PREFILL = ("jit_prefill",)


def read(before, after, responses, trace, cell):
    if not trace or not trace.get("busy_s"):
        return None
    n = max(1, len(trace.get("device_planes") or [1]))
    t = sum(v["time_s"] for k, v in trace["modules"].items() if k.startswith(PREFILL)) / n
    return t / trace["busy_s"]
