"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark reads.

Run as a child of the harness under ``JAX_PLATFORMS=cpu`` (reading a trace
needs JAX's ``ProfileData`` but no device, and the engine still holds the
chip):

    python -m benchmark.harness.trace_reduce <file.xplane.pb>  ->  one JSON line

What it computes, per the ``on-chip-measurement`` guide:

- ``window_s``: the traced span on the device's own planes, first device
  event's start to last device event's end. The host planes run on for about
  a second while the profiler stops and collects (6.1 s against 5.0 s in a
  trace of 5 s, PR 22), and in that second no device event is recorded: time
  the device was not watched is not time it was idle. ``host_span_s`` is the
  span over every plane, for comparison.
- ``busy_s``: per device plane, the union of the intervals in which an
  operation ran (the ``XLA Ops`` line; the ``XLA Modules`` line where a plane
  has no op line), averaged over the device planes.
- ``modules``: device time and launches per XLA module (the jitted step's
  own name, ``jit_<fn>``; the ``(<fingerprint>)`` suffix is cut), summed over
  the device planes.
- ``device_ops``: the ten operations with most device time, by XLA's names
  (a ``while`` holds the layer scan, so its time contains its body's ops).
- ``idle_gaps``: the device's idle gaps attributed to what the host was doing:
  for each of the longest gaps the shortest host-thread event that covers at
  least half of it (the most specific explanation; events much longer than
  the gap enclose it and explain nothing, so they are passed over), else the
  event that overlaps it most; seconds summed by event name, ten largest;
  gaps no host event overlaps are ``unattributed``.
"""

from __future__ import annotations

import json
import re
import sys

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
GAPS_ATTRIBUTED = 400  # longest gaps looked at
ENCLOSING = 4.0  # a host event longer than this many gaps encloses, not explains
TOP = 10


def module_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def op_name(event_name: str) -> str:
    """XLA's own name of an operation: the trace carries the whole HLO line
    (``%fusion.12 = bf16[...] fusion(...)``), of which the name is enough."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()[:120]


def union_s(starts: np.ndarray, ends: np.ndarray) -> tuple[float, np.ndarray, np.ndarray]:
    """Length of the union of the intervals, and the merged intervals."""
    if len(starts) == 0:
        return 0.0, starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    ms, me = s[first], e[np.append(first[1:] - 1, len(s) - 1)]
    return float(np.sum(me - ms)), ms, me


def _events(line) -> tuple[list[str], np.ndarray, np.ndarray]:
    names, starts, durs = [], [], []
    for ev in line.events:
        names.append(ev.name)
        starts.append(ev.start_ns)
        durs.append(ev.duration_ns)
    s = np.asarray(starts, np.float64)
    return names, s, s + np.asarray(durs, np.float64)


def _span(planes: list[dict]) -> tuple[float, float]:
    lo, hi = np.inf, -np.inf
    for p in planes:
        for ln in p["lines"]:
            if len(ln["starts"]):
                lo, hi = min(lo, float(ln["starts"].min())), max(hi, float(ln["ends"].max()))
    return lo, hi


def reduce_planes(planes: list[dict]) -> dict:
    """``planes``: ``[{"name", "lines": [{"name", "names", "starts", "ends"}]}]``
    with times in nanoseconds on one clock (what ``load`` makes of a file;
    tests build it by hand)."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    lo, hi = _span(devices)
    host_lo, host_hi = _span(planes)
    out: dict = {
        "planes": [p["name"] for p in planes],
        "device_planes": [p["name"] for p in devices],
        "window_s": max(0.0, (hi - lo) / 1e9) if hi > lo else 0.0,
        "host_span_s": max(0.0, (host_hi - host_lo) / 1e9) if host_hi > host_lo else 0.0,
        "busy_s": 0.0,
        "modules": {},
        "device_ops": [],
        "idle_gaps": [],
    }
    if not devices:
        return out
    busy, ops_total, gap_lists = [], {}, []
    for p in devices:
        op_lines = [ln for ln in p["lines"] if ln["name"] in OP_LINES]
        mod_lines = [ln for ln in p["lines"] if ln["name"] in MODULE_LINES]
        for ln in mod_lines:
            for name, s, e in zip(ln["names"], ln["starts"], ln["ends"]):
                m = out["modules"].setdefault(module_name(name), {"time_s": 0.0, "count": 0})
                m["time_s"] += float(e - s) / 1e9
                m["count"] += 1
        for ln in op_lines:
            for name, s, e in zip(ln["names"], ln["starts"], ln["ends"]):
                ops_total[op_name(name)] = ops_total.get(op_name(name), 0.0) + float(e - s) / 1e9
        src = op_lines or mod_lines
        if not src:
            continue
        starts = np.concatenate([ln["starts"] for ln in src])
        ends = np.concatenate([ln["ends"] for ln in src])
        total, ms, me = union_s(starts, ends)
        busy.append(total / 1e9)
        # idle gaps of this device, up to the edges of the device span
        gs = np.concatenate([[lo], me])
        ge = np.concatenate([ms, [hi]])
        keep = ge > gs
        gap_lists.append((gs[keep], ge[keep]))
    out["busy_s"] = float(np.mean(busy)) if busy else 0.0
    out["device_ops"] = [[k, v] for k, v in sorted(ops_total.items(), key=lambda kv: -kv[1])[:TOP]]
    if gap_lists:
        out["idle_gaps"] = _attribute(gap_lists[0], [p for p in planes if p["name"].startswith("/host:")])
    return out


def _attribute(gaps: tuple[np.ndarray, np.ndarray], hosts: list[dict]) -> list:
    gs, ge = gaps
    order = np.argsort(-(ge - gs))[:GAPS_ATTRIBUTED]
    names: list[str] = []
    starts, ends = [], []
    for p in hosts:
        for ln in p["lines"]:
            names.extend(ln["names"])
            starts.append(ln["starts"])
            ends.append(ln["ends"])
    by_name: dict[str, float] = {}
    if names:
        hs, he = np.concatenate(starts), np.concatenate(ends)
        hdur = he - hs
    for i in order:
        a, b = gs[i], ge[i]
        gap = b - a
        label = "unattributed"
        if names:
            overlap = np.minimum(he, b) - np.maximum(hs, a)
            ok = (overlap > 0) & (hdur <= ENCLOSING * gap)
            covers = ok & (overlap >= 0.5 * gap)
            if covers.any():
                idx = np.flatnonzero(covers)
                label = names[idx[np.argmin(hdur[idx])]]
            elif ok.any():
                idx = np.flatnonzero(ok)
                label = names[idx[np.argmax(overlap[idx])]]
        by_name[label] = by_name.get(label, 0.0) + float(gap) / 1e9
    return [[k, v] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]]


def load(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    planes = []
    for p in ProfileData.from_file(path).planes:
        lines = []
        for ln in p.lines:
            names, s, e = _events(ln)
            lines.append({"name": ln.name, "names": names, "starts": s, "ends": e})
        planes.append({"name": p.name, "lines": lines})
    return planes


def main() -> int:
    print(json.dumps(reduce_planes(load(sys.argv[1]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
