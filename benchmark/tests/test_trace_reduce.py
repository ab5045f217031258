"""trace_reduce on hand-built planes (exact arithmetic) and on a small trace
recorded on the chip (``data/numerics.xplane.pb``: the numerics child's
prefill + 8 decode steps of a 2-layer model on a TPU v5e, PR 22)."""

import os

import numpy as np
import pytest

from harness import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def line(name, events):
    return {
        "name": name,
        "names": [e[0] for e in events],
        "starts": np.asarray([e[1] for e in events], np.float64),
        "ends": np.asarray([e[2] for e in events], np.float64),
    }


def test_union_merges_overlaps():
    total, s, e = trace_reduce.union_s(np.asarray([0.0, 5.0, 20.0]), np.asarray([10.0, 8.0, 30.0]))
    assert total == 20.0 and list(s) == [0.0, 20.0] and list(e) == [10.0, 30.0]


def test_busy_modules_and_gaps_on_built_planes():
    ms = 1e6  # ns
    planes = [
        {"name": "/device:TPU:0", "lines": [
            line("XLA Modules", [("jit_decode_n(123)", 10 * ms, 30 * ms), ("jit_prefill(9)", 60 * ms, 80 * ms)]),
            line("XLA Ops", [("fusion.1", 10 * ms, 20 * ms), ("fusion.2", 15 * ms, 30 * ms), ("copy.3", 60 * ms, 80 * ms)]),
        ]},
        {"name": "/host:CPU", "lines": [
            line("worker", [("whole_run", 0.0, 100 * ms), ("readback", 32 * ms, 58 * ms), ("tiny", 40 * ms, 41 * ms)]),
        ]},
    ]
    out = trace_reduce.reduce_planes(planes)
    # the window is the device planes' span: the host runs on while the
    # profiler stops, and what the device did then was not recorded
    assert out["window_s"] == pytest.approx(0.07)
    assert out["host_span_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx(0.04)
    assert out["modules"]["jit_decode_n"] == {"time_s": pytest.approx(0.02), "count": 1}
    assert out["modules"]["jit_prefill"]["time_s"] == pytest.approx(0.02)
    assert out["device_ops"][0] == ["copy.3", pytest.approx(0.02)]
    gaps = dict(out["idle_gaps"])
    # the one gap inside the device span is the readback's (the shortest
    # event covering half of it; the whole-run span is not over four times
    # as long, so only its length decides)
    assert gaps == {"readback": pytest.approx(0.03)}


def test_no_device_plane_means_no_busy_time():
    out = trace_reduce.reduce_planes([{"name": "/host:CPU", "lines": [line("t", [("x", 0.0, 5.0)])]}])
    assert out["busy_s"] == 0.0 and out["device_planes"] == []


def test_recorded_chip_trace():
    path = os.path.join(DATA, "numerics.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace")
    out = trace_reduce.reduce_planes(trace_reduce.load(path))
    assert out["device_planes"] and 0 < out["busy_s"] < out["window_s"]
    mods = out["modules"]
    # the child traces one prefill and 8 decode steps of its two jitted fns
    assert mods["jit_prefill"]["count"] == 1 and mods["jit_decode"]["count"] == 8
    assert out["device_ops"] and out["idle_gaps"]
