"""The readers of the engine's phase spans and counters on hand-built
``before``/``after`` documents, and ``trace_reduce`` on a host plane that
holds nested ``engine.*`` spans and no Python frames (what a capture with the
Python tracer off records)."""

import numpy as np
import pytest

from harness import trace_reduce
from layer_metrics import (
    device_wait_share,
    first_token_host_ms,
    host_ms_per_req,
    jit_s_in_window,
    journal_dispatch_p50_ms,
    prefill_tokens_per_launch,
)

READERS = [
    device_wait_share,
    first_token_host_ms,
    host_ms_per_req,
    jit_s_in_window,
    journal_dispatch_p50_ms,
    prefill_tokens_per_launch,
]


def phase(n, self_s, total_s):
    return {"n": n, "self_s": self_s, "total_s": total_s}


BEFORE = [{
    "loop_s": 100.0,
    "requests_finished": 10,
    "prefill_launches": 20,
    "prefill_tokens": 4000,
    "phases": {
        "engine.wait_request": phase(5, 50.0, 50.0),
        "engine.wait_device": phase(40, 10.0, 12.0),
        "engine.prefill_tick": phase(20, 1.0, 30.0),
        "engine.first_token_sample": phase(10, 25.0, 25.0),
        "engine.decode_dispatch": phase(90, 9.0, 9.0),
    },
    "compile_cache": {"requests": 30, "trace_s": 5.0, "lower_s": 2.0, "compile_s": 40.0, "retrieval_s": 1.0},
    "journal_dispatch_ms_samples": [9.0, 9.0],
}]
AFTER = [{
    "loop_s": 150.0,
    "requests_finished": 110,
    "prefill_launches": 220,
    "prefill_tokens": 34000,
    "phases": {
        "engine.wait_request": phase(6, 51.0, 51.0),
        "engine.wait_device": phase(400, 20.0, 25.0),
        "engine.prefill_tick": phase(220, 3.0, 60.0),
        "engine.first_token_sample": phase(110, 45.0, 45.0),
        "engine.decode_dispatch": phase(900, 13.0, 13.0),
        # a phase the window saw first: its whole total is the window's
        "engine.evict": phase(4, 1.0, 1.0),
    },
    "compile_cache": {"requests": 130, "trace_s": 15.0, "lower_s": 4.0, "compile_s": 43.0, "retrieval_s": 3.0},
    "journal_dispatch_ms_samples": [0.5, 0.7, 0.9],
}]


@pytest.mark.parametrize(
    "reader, want",
    [
        # self time outside the two waits: (3-1) + (45-25) + (13-9) + 1 = 27 s over 100 requests
        (host_ms_per_req, 270.0),
        # (20 - 10) s of waiting on the device in (150 - 100) s of loop
        (device_wait_share, 0.2),
        # (45 - 25) s over (110 - 10) samples
        (first_token_host_ms, 200.0),
        # (15-5) + (4-2) + (43-40): retrieval is inside compile_s already
        (jit_s_in_window, 15.0),
        # (34000 - 4000) tokens over (220 - 20) launches
        (prefill_tokens_per_launch, 150.0),
        # the newest samples, whatever the deque held before
        (journal_dispatch_p50_ms, 0.7),
    ],
)
def test_reader_takes_the_windows_difference(reader, want):
    assert reader.read(BEFORE, AFTER, [], None, {}) == pytest.approx(want)


@pytest.mark.parametrize("reader", READERS)
def test_a_program_without_the_keys_reads_none(reader):
    # the parent commit's documents: no phases, no new counters, no seconds
    old = [{"prefills": 3, "decode_steps": 9, "compile_cache": {"requests": 4, "hits": 4, "writes": 0}}]
    assert reader.read(old, old, [], None, {}) is None
    assert reader.read([{}], [{}], [], None, {}) is None


@pytest.mark.parametrize(
    "reader", [host_ms_per_req, device_wait_share, first_token_host_ms, prefill_tokens_per_launch]
)
def test_nothing_happened_in_the_window_reads_none(reader):
    # zero denominators: no request finished, no launch, no loop time
    assert reader.read(AFTER, AFTER, [], None, {}) is None


def test_two_engines_are_summed():
    got = prefill_tokens_per_launch.read(BEFORE * 2, AFTER + BEFORE, [], None, {})
    assert got == pytest.approx(150.0)  # the second engine stood still


def line(name, events):
    return {
        "name": name,
        "names": [e[0] for e in events],
        "starts": np.asarray([e[1] for e in events], np.float64),
        "ends": np.asarray([e[2] for e in events], np.float64),
    }


def test_idle_gaps_name_the_innermost_engine_span():
    """With the Python tracer off the host plane holds the program's spans:
    a gap goes to the shortest span covering half of it, which is the
    innermost one; a span enclosing many gaps explains none of them."""
    ms = 1e6
    planes = [
        {"name": "/device:TPU:0", "lines": [
            line("XLA Modules", [("jit_prefill(1)", 0 * ms, 10 * ms), ("jit_decode_n(2)", 110 * ms, 120 * ms),
                                 ("jit_decode_n(2)", 150 * ms, 160 * ms)]),
            line("XLA Ops", [("fusion.1", 0 * ms, 10 * ms), ("fusion.2", 110 * ms, 120 * ms),
                             ("fusion.3", 150 * ms, 160 * ms)]),
        ]},
        {"name": "/host:CPU", "lines": [
            line("llm-engine", [
                ("engine.prefill_tick", 0 * ms, 108 * ms),
                ("engine.prefill_dispatch", 1 * ms, 8 * ms),
                ("engine.first_token_sample", 12 * ms, 104 * ms),
                ("engine.inject_lane", 104 * ms, 107 * ms),
                ("engine.decode_dispatch", 108 * ms, 111 * ms),
                ("engine.wait_device", 111 * ms, 121 * ms),
                ("engine.admit", 122 * ms, 149 * ms),
                ("engine.prefix_fork", 125 * ms, 130 * ms),
            ]),
            line("asyncio", [("PjitFunction(step)", 300 * ms, 301 * ms)]),
        ]},
    ]
    gaps = dict(trace_reduce.reduce_planes(planes)["idle_gaps"])
    assert all(name.startswith("engine.") for name in gaps), gaps
    # 10 -> 110 ms: the sampler (92 ms) covers it and is shorter than the tick around it
    assert gaps["engine.first_token_sample"] == pytest.approx(0.1)
    # 120 -> 150 ms: admission, not the 5 ms fork inside it (covers a sixth of the gap)
    assert gaps["engine.admit"] == pytest.approx(0.03)
