"""Generators are deterministic from the seed; percentile and due-time
arithmetic; every file BENCHMARK.json names is found by name."""

import asyncio
import importlib
import itertools
import json
import math
import os

import pytest

from harness import loadgen, stats

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


def traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        t = json.load(f)
    if "base" in t:
        t = {**traffic(t["base"]), **t}
    return t


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted({w["traffic"] for w in bench()["workloads"]}))
def test_generator_is_deterministic_from_the_seed(name):
    t = traffic(name)
    gen = importlib.import_module("generators." + t["generator"])
    a = list(itertools.islice(gen.sessions(t, 5, 5, "m"), 50))
    b = list(itertools.islice(gen.sessions(t, 5, 5, "m"), 50))
    c = list(itertools.islice(gen.sessions(t, 6, 5, "m"), 50))
    assert a == b
    assert [s["turns"][0]["message"] for s in a] != [s["turns"][0]["message"] for s in c]
    n = int(t.get("shared_prefix_tokens", 0))
    for s in a + c:
        first = s["turns"][0]
        # N tokens = BOS + N - 1 ASCII bytes; the shared prefix fills its bucket
        assert first["prompt_tokens"] == 1 + len(first["message"].encode())
        assert all(ord(ch) < 128 for ch in first["message"])
        assert s["turns"][-1]["context_tokens"] <= t["context_limit_tokens"]
        if n:
            assert first["message"][: n - 1] == a[0]["turns"][0]["message"][: n - 1]


def test_percentiles_and_spread():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile([], 50) is None
    assert stats.percentile([1.0, math.inf], 95) == math.inf  # a failed request misses
    assert stats.spread([90.0, 100.0, 110.0, 120.0, 130.0]) == pytest.approx(20.0 / 110.0)


def test_requests_served_in_the_window_count_by_their_share_inside_it():
    reqs = [
        (1.0, 3.0),  # inside: 1
        (-2.0, 2.0),  # half over the start: 1/2
        (9.0, 13.0),  # a quarter before the end: 1/4
        (-1.0, 11.0),  # spans the window: 10/12
        (-5.0, -1.0),  # the warm-up's: 0
        (10.0, 12.0),  # after the window: 0
    ]
    assert stats.served_in_window(reqs, 10.0) == pytest.approx(1 + 0.5 + 0.25 + 10 / 12)
    # a steady stream of back-to-back requests is counted exactly, wherever the edges fall
    stream = [(0.7 * k - 3.3, 0.7 * k - 2.6) for k in range(40)]
    assert stats.served_in_window(stream, 10.0) == pytest.approx(10.0 / 0.7)


def test_window_membership_is_by_due_time():
    rec = loadgen.Recorder(seconds=10.0)
    rec.records = [{"due_s": -0.1}, {"due_s": 0.0}, {"due_s": 9.99}, {"due_s": 10.0}]
    assert [r["due_s"] for r in rec.window()] == [0.0, 9.99]


def test_every_named_file_is_found_by_name():
    b = bench()
    for c in b["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            doc = json.load(f)
        assert doc["name"] == c["name"] and doc["source"] == c["source"]
        assert set(c["reduced"]) == set(doc["reduced"])
    for w in b["workloads"]:
        importlib.import_module("generators." + traffic(w["traffic"])["generator"])
    for m in b["per_layer"]:
        assert callable(importlib.import_module("layer_metrics." + m["name"]).read)
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}


def test_a_request_cut_by_the_drain_is_a_failure_of_the_window():
    """A request due in the window that never answers stays in ``attempted``
    and ``failed`` and is infinite in the percentiles."""

    class Hangs:
        def post(self, *a, **kw):
            return self

        async def __aenter__(self):
            await asyncio.sleep(3600)

        async def __aexit__(self, *a):
            return False

    async def go():
        rec = loadgen.Recorder(seconds=10.0)
        sess = {"id": "s", "turns": [{"message": "x", "max_tokens": 4, "prompt_tokens": 2, "context_tokens": 6}]}
        task = asyncio.ensure_future(loadgen._turn(Hangs(), "http://x", sess, 0, 0.0, loadgen.time.monotonic(), rec))
        await asyncio.sleep(0.05)
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        return rec

    rec = asyncio.run(go())
    (r,) = rec.window()
    assert r["ok"] is False and math.isinf(r["latency_ms"]) and "drain" in r["error"]
    assert stats.percentile([r["latency_ms"] if r["ok"] else math.inf for r in rec.window()], 50.0) == math.inf


def test_roofline_readers_count_the_work_of_the_traced_span():
    """Launches and device time from the trace, work per launch from the
    counters around the trace and the prompts sent: not from window rates."""
    from harness import peaks
    from harness.family import family_of

    cell = {"config": bench_config(), "device": {"kind": "TPU v5 lite"}, "seconds": 51.0}
    family = family_of(cell["config"])
    peak = peaks.peaks_of("TPU v5 lite")
    prefill = importlib.import_module("layer_metrics.prefill_step_roofline")
    # prompts of 300 and 512 tokens are 2 + 2 launches: 203 tokens a launch
    responses = [{"ok": True, "want_prompt_tokens": 300, "context_tokens": 332},
                 {"ok": True, "want_prompt_tokens": 512, "context_tokens": 544}]
    trace = {"modules": {"jit_prefill": {"time_s": 0.5, "count": 10}}, "window_s": 5.0}
    flops = family.prefill_flops(cell["config"], 10 * 812 / 4, (300**2 + 512**2) / (2 * 812))
    assert prefill.read([], [], responses, trace, cell) == pytest.approx(100 * flops / 0.5 / peak["bf16_flops"])
    # twice the window's requests at the same lengths: the same share
    assert prefill.read([], [], responses * 2, trace, cell) == pytest.approx(100 * flops / 0.5 / peak["bf16_flops"])

    decode = importlib.import_module("layer_metrics.decode_step_roofline")
    tb = [{"decode_chunk_hist": {"8": 100}, "decode_steps": 100, "batch_occupancy": 0.5, "max_batch": 8}]
    ta = [{"decode_chunk_hist": {"8": 110, "4": 10}, "decode_steps": 120, "batch_occupancy": 0.5, "max_batch": 8}]
    trace = {"modules": {"jit_decode_n": {"time_s": 1.0, "count": 15}, "jit_verify_k": {"time_s": 0.2, "count": 5}},
             "counters_before": tb, "counters_after": ta}
    need = family.decode_step_bytes(cell["config"], live_kv_tokens=4 * 438.0)
    want = 100 * (15 * 6 + 5) * need / peak["hbm_bytes_per_s"] / 1.2
    # the window's own counters (first two arguments) play no part
    assert decode.read([{"decode_chunk_hist": {"1": 0}}], [{"decode_chunk_hist": {"1": 999}}], responses, trace, cell) == pytest.approx(want)
    assert decode.read([], [], responses, {"modules": trace["modules"]}, cell) is None


def bench_config():
    with open(os.path.join(REPO, bench()["configs"][0]["file"])) as f:
        return json.load(f)
