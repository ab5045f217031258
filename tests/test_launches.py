"""The launch ledger (ISSUE 52): the timing rule on a fake clock, the engine's
launch counters as sums over it against what the parent's hand-bumped counters
gave on the same scenario, and the benchmark's three readers of ``launches``.

``tests/data/launches_parent_pr51.json`` holds what the parent commit
(0a1798e, PR 51) published under each legacy key after ``scenario`` below, on
this CPU backend with seeded weights: what this file prints when run as a
script with that commit's ``agentainer_tpu`` first on the path."""

import asyncio
import importlib
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from agentainer_tpu.utils.launches import FIELDS, Launches

DATA = os.path.join(os.path.dirname(__file__), "data")
BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
LEGACY = ("prefill_launches", "prefill_tokens", "decode_steps", "decode_chunk_hist", "mixed_launches",
          "mixed_decode_lanes", "batch_occupancy", "spec_verify_hist", "forward_passes")
DISPATCH_SPANS = ("engine.prefill_dispatch", "engine.decode_dispatch", "engine.verify_dispatch",
                  "engine.first_token_sample")  # engine.mixed_dispatch lies inside engine.prefill_dispatch


def _rows(ledger: dict) -> dict:
    """``{(program, key): row}`` of a ``launches`` document."""
    return {(p, k): row for p, keys in ledger.items() if p != "shared" for k, row in keys.items()}


def _device_s(ledger: dict) -> float:
    return sum(row["device_s"] for row in _rows(ledger).values())


# -- the timing rule, on a fake clock --------------------------------------
class Clock:
    def __init__(self) -> None:
        self.now = 100.0

    def __call__(self) -> float:
        return self.now


def _play(script: list) -> tuple[Launches, Clock]:
    """``script``: ("d", t, name, program, key, steps) dispatches a launch
    at ``t``, ("r", t, name) reads it back, ("cut", t) / ("reset", t)."""
    clock = Clock()
    ledger, opened = Launches(clock), {}
    for what, t, *rest in script:
        clock.now = 100.0 + t
        if what == "d":
            name, program, key, steps = rest
            opened[name] = ledger.dispatched(program, key, steps=steps, rows=4, lanes=2)
        elif what == "r":
            ledger.ready(opened[rest[0]])
        else:
            getattr(ledger, what)()
    return ledger, clock


RULE_CASES = {
    # one launch in flight: the interval is its own, from its dispatch
    "one_in_flight": (
        [("d", 0, "a", "jit_decode_n", 8, 8), ("r", 5, "a")],
        {("jit_decode_n", "8"): (1, 8, 5.0)}, 0,
    ),
    # two pipelined: the second could not start before the first ended
    "two_pipelined": (
        [("d", 0, "a", "jit_decode_n", 8, 8), ("d", 1, "b", "jit_decode_n", 8, 8),
         ("r", 5, "a"), ("r", 9, "b")],
        {("jit_decode_n", "8"): (2, 16, 9.0)}, 0,
    ),
    # an idle gap before a dispatch is the host's, and left out
    "idle_gap": (
        [("d", 0, "a", "jit_decode_n", 8, 8), ("r", 5, "a"),
         ("d", 7, "b", "jit_decode_n", 1, 1), ("r", 10, "b")],
        {("jit_decode_n", "8"): (1, 8, 5.0), ("jit_decode_n", "1"): (1, 1, 3.0)}, 0,
    ),
    # a chunk nobody reads between two launches that are read: the interval
    # is shared, and no program's
    "untimed_between": (
        [("d", 0, "a", "jit_decode_n", 8, 8), ("r", 5, "a"),
         ("d", 5, "c", "jit_prefill", 256, 1), ("d", 6, "f", "jit_first_token", 1, 0),
         ("r", 9, "f"), ("d", 9, "b", "jit_decode_n", 8, 8), ("r", 12, "b")],
        {("jit_decode_n", "8"): (2, 16, 8.0), ("jit_prefill", "256"): (0, 0, 0.0),
         ("jit_first_token", "1"): (0, 0, 0.0)}, 1,
    ),
    # a launch nobody came to read falls into the next interval
    "never_read": (
        [("d", 0, "f", "jit_first_token", 1, 0), ("d", 1, "a", "jit_decode_n", 8, 8), ("r", 6, "a")],
        {("jit_first_token", "1"): (0, 0, 0.0), ("jit_decode_n", "8"): (0, 0, 0.0)}, 1,
    ),
    # a cut breaks the chain: what was in flight is dropped, the next launch
    # has no predecessor and starts at its own dispatch
    "cut_breaks_the_chain": (
        [("d", 0, "a", "jit_decode_n", 8, 8), ("r", 5, "a"), ("d", 5, "b", "jit_decode_n", 8, 8),
         ("cut", 6), ("r", 8, "b"), ("d", 20, "c", "jit_decode_n", 8, 8), ("r", 24, "c")],
        {("jit_decode_n", "8"): (2, 16, 9.0)}, 0,
    ),
    # a reset (warm-up's) zeroes the rows too
    "reset_zeroes": (
        [("d", 0, "a", "jit_decode_n", 8, 8), ("r", 5, "a"), ("reset", 6),
         ("d", 7, "b", "jit_verify", 4, 1), ("r", 9, "b")],
        {("jit_verify", "4"): (1, 1, 2.0)}, 0,
    ),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_timing_rule(case):
    script, timed, shared = RULE_CASES[case]
    ledger, clock = _play(script)
    doc = ledger.snapshot()
    got = {at: (row["timed_n"], row["timed_steps"], row["device_s"]) for at, row in _rows(doc).items()}
    assert got == {k: pytest.approx(v) for k, v in timed.items()}
    assert doc["shared"] == {"n": shared}
    # every launch is counted where it is dispatched, read back or not
    dispatched = [s for s in script if s[0] == "d"]
    if not any(s[0] == "reset" for s in script):
        assert sum(row["n"] for row in _rows(doc).values()) == len(dispatched)
    # intervals never overlap: their sum cannot pass the wall time they lie in
    assert _device_s(doc) <= clock.now - 100.0 + 1e-9


def test_a_launch_record_carries_its_facts():
    ledger = Launches(Clock())
    opened = ledger.dispatched("jit_prefill_with_decode", 256, rows=260, lanes=4)
    assert opened.attrs() == {"program": "jit_prefill_with_decode", "key": "256", "steps": 1, "rows": 260, "lanes": 4}
    row = ledger.snapshot()["jit_prefill_with_decode"]["256"]
    assert tuple(row) == FIELDS and row["n"] == 1 and row["rows"] == 260 and row["timed_n"] == 0
    assert ledger.total("lanes", "jit_prefill_with_decode") == 4 and ledger.total("steps") == 1
    assert ledger.by_key("jit_prefill_with_decode", "jit_prefill") == {"256": 1}


def test_device_time_never_passes_the_wall_time():
    """Intervals do not overlap and lie between the ledger's zero and the
    newest readback: at any scrape, pipelined or not, read or not, the
    programs' ``device_s`` sum to no more than the time that has passed."""
    clock = Clock()
    ledger = Launches(clock)
    pending, seen = [], 0.0
    for i in range(200):
        clock.now += 0.003 + 0.001 * (i % 5)
        pending.append((ledger.dispatched("jit_decode_n", 1 << (i % 4), steps=1 << (i % 4)), i % 7 != 3))
        if len(pending) > 1:
            clock.now += 0.002 * (i % 3)
            first, read = pending.pop(0)
            if read:
                ledger.ready(first)
        if i % 9 == 0:
            now = _device_s(ledger.snapshot())
            assert seen <= now <= clock.now - 100.0 + 1e-9
            seen = now
    assert seen > 0.5 * (clock.now - 100.0)  # the case is a busy device, not an empty sum


def test_snapshot_is_safe_against_the_writing_thread():
    ledger = Launches()
    stop = threading.Event()
    done = [0]

    def worker() -> None:
        i = 0
        while not stop.is_set():
            opened = ledger.dispatched(f"jit_p{i % 7}", i % 50, steps=2)  # new rows keep arriving
            ledger.ready(opened)
            i += 1
        done[0] = i

    th = threading.Thread(target=worker)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        th.start()
        deadline, last = time.monotonic() + 1.0, 0
        while time.monotonic() < deadline:
            doc = ledger.snapshot()
            n = sum(row["n"] for row in _rows(doc).values())
            assert n >= last and ledger.total("steps") >= 2 * n
            last = n
    finally:
        stop.set()
        th.join(timeout=10)
        sys.setswitchinterval(interval)
    assert not th.is_alive() and ledger.total("n") == done[0] > 0


def test_importing_launches_does_not_import_jax():
    code = "import sys; import agentainer_tpu.utils.launches; print('jax' in sys.modules)"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=60)
    assert out.stdout.strip() == "False", out


# -- an engine's counters are sums over its ledger -------------------------
OPTS = {"max_batch": 4, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 32, "skip_warmup": True}
JSON_LOOP = '{"tool": "search", "args": {"q": "w", "n": 5}}\n' * 4


def _together(eng, *calls) -> list:
    """Requests that reach the worker in ONE drain of its queue, so that what
    it launches for them does not follow the instant each arrived at."""
    gate = threading.Event()
    pump = eng._pump_queue

    def held(block_s):
        gate.wait(timeout=30)
        pump(block_s)

    async def drive():
        eng._pump_queue = held
        try:
            await asyncio.sleep(0.5)  # the worker leaves its blocking get and stops at the gate
            tasks = [asyncio.ensure_future(eng.generate(p, ignore_eos=True, **kw)) for p, kw in calls]
            await asyncio.sleep(0.2)  # each has put its request
        finally:
            gate.set()
            eng._pump_queue = pump
        return [r["tokens"] for r in await asyncio.gather(*tasks)]

    return asyncio.run(drive())


def scenario() -> dict:
    """Plain chunks, mixed launches, every decode rung and verify rounds, on
    the dense ``tiny`` engine: ``{"plain": ..., "spec": ...}``, each the
    engine's ``/metrics`` after its part and the tokens it answered."""
    from agentainer_tpu.engine.llm import LLMEngine

    out = {}
    # a short prompt that decodes for long beside a four-chunk prompt whose
    # chunks carry its step; then lone requests, one for each rung of the ladder
    eng = LLMEngine.create("tiny", options=dict(OPTS, speculative=False))
    try:
        tokens = _together(
            eng,
            ("hello there", {"max_tokens": 40}),
            ("longer than one prefill chunk " * 4, {"max_tokens": 5}),
        )
        for budget in (2, 3, 5, 16):  # the rungs 1, 2, 4, and 8 twice
            tokens.append(asyncio.run(eng.generate("one more", max_tokens=budget, ignore_eos=True))["tokens"])
        time.sleep(0.3)
        out["plain"] = {"metrics": eng.metrics(), "tokens": tokens, "forward_passes": eng.forward_passes}
    finally:
        eng.shutdown()
    # repetitive text: prompt-lookup drafts and verify rounds
    eng = LLMEngine.create("tiny", options=dict(OPTS))
    try:
        tokens = [asyncio.run(eng.generate(JSON_LOOP, max_tokens=24, ignore_eos=True))["tokens"]]
        time.sleep(0.3)
        out["spec"] = {"metrics": eng.metrics(), "tokens": tokens, "forward_passes": eng.forward_passes}
    finally:
        eng.shutdown()
    return out


def _legacy(part: dict) -> dict:
    return {k: part["forward_passes"] if k == "forward_passes" else part["metrics"][k] for k in LEGACY}


@pytest.fixture(scope="module")
def served():
    return scenario()


@pytest.fixture(scope="module")
def parent():
    with open(os.path.join(DATA, "launches_parent_pr51.json")) as f:
        return json.load(f)


def test_the_scenario_launches_every_program_and_rung(served):
    plain, spec = served["plain"]["metrics"]["launches"], served["spec"]["metrics"]["launches"]
    assert set(plain["jit_decode_n"]) == {"1", "2", "4", "8"}
    assert plain["jit_prefill"]["32"]["n"] >= 2 and plain["jit_prefill_with_decode"]["32"]["n"] >= 2
    assert plain["jit_first_token"]["1"]["n"] == 6
    assert sum(row["n"] for row in spec["jit_verify"].values()) >= 1
    # a plain chunk is never timed; a launch that is read back alone is
    assert all(row["timed_n"] == 0 for row in plain["jit_prefill"].values())
    assert plain["jit_prefill_with_decode"]["32"]["timed_n"] > 0 and plain["jit_decode_n"]["8"]["timed_n"] > 0
    assert sum(row["timed_n"] for row in spec["jit_verify"].values()) >= 1
    assert served["plain"]["metrics"]["last_capture"] is None


@pytest.mark.parametrize("part", ["plain", "spec"])
def test_the_scenario_answers_the_parents_tokens(served, parent, part):
    assert served[part]["tokens"] == parent[part]["tokens"]


@pytest.mark.parametrize("key", LEGACY)
@pytest.mark.parametrize("part", ["plain", "spec"])
def test_each_legacy_key_is_the_parents_value(served, parent, part, key):
    assert _legacy(served[part])[key] == parent[part]["legacy"][key]


@pytest.mark.parametrize("part", ["plain", "spec"])
def test_launches_are_the_dispatch_spans(served, part):
    m = served[part]["metrics"]
    launched = sum(row["n"] for row in _rows(m["launches"]).values())
    assert launched == sum(m["phases"][s]["n"] for s in DISPATCH_SPANS if s in m["phases"]) > 0
    mixed = sum(row["n"] for row in m["launches"].get("jit_prefill_with_decode", {}).values())
    assert mixed == m["phases"].get("engine.mixed_dispatch", {"n": 0})["n"] == m["mixed_launches"]
    # the ledger's time in service lies inside the worker's own
    assert 0.0 < _device_s(m["launches"]) <= m["loop_s"]
    # every readback is a launch timed alone or a shared interval
    timed = sum(row["timed_n"] for row in _rows(m["launches"]).values())
    assert m["host_syncs_per_token"] == round((timed + m["launches"]["shared"]["n"]) / m["tokens_generated"], 4)


def test_a_worker_fault_cuts_the_chain():
    from agentainer_tpu import faults
    from agentainer_tpu.engine.llm import LLMEngine

    eng = LLMEngine.create("tiny", options=dict(OPTS, speculative=False))
    try:
        asyncio.run(eng.generate("warm", max_tokens=4, ignore_eos=True))
        faults.arm("engine.decode_step", error="TimeoutError", count=1)
        with pytest.raises(Exception):
            asyncio.run(eng.generate("struck", max_tokens=12, ignore_eos=True))
        assert eng._launches._prev_ready is None and not eng._launches._open
        before = eng.launches()["jit_decode_n"]
        asyncio.run(eng.generate("after", max_tokens=9, ignore_eos=True))
        assert eng.launches()["jit_decode_n"]["8"]["timed_n"] == before.get("8", {"timed_n": 0})["timed_n"] + 1
        assert eng.metrics()["worker_errors"] == 1
    finally:
        faults.disarm_all()
        eng.shutdown()


def test_the_step_programs_are_named_once():
    """The readers name the programs they read as the engine does."""
    from agentainer_tpu.engine import llm

    sys.path.insert(0, BENCH)
    try:
        ride = importlib.import_module("layer_metrics.mixed_ride_share")
    finally:
        sys.path.remove(BENCH)
    assert (ride.MIXED, ride.DECODE) == (llm.JIT_PREFILL_WITH_DECODE, llm.JIT_DECODE_N)


# -- the benchmark's three readers ------------------------------------------
def _reader(name: str):
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module("layer_metrics." + name).read
    finally:
        sys.path.remove(BENCH)


def _doc(launches: dict | None, **keys) -> dict:
    doc = {"decode_chunk": 8, "max_batch": 4, **keys}
    if launches is not None:
        doc["launches"] = launches
    return doc


def _row(n, steps, device_s, timed=True):
    return {"n": n, "steps": steps, "rows": 0, "lanes": 0,
            "timed_n": n if timed else 0, "timed_steps": steps if timed else 0, "device_s": device_s}


BEFORE = _doc({
    "jit_decode_n": {"1": _row(10, 10, 0.1), "8": _row(5, 40, 0.4)},
    "jit_prefill_with_decode": {"128": _row(2, 2, 0.02), "256": _row(4, 4, 0.1)},
    "jit_prefill": {"256": _row(3, 3, 0.0, timed=False)},
    "shared": {"n": 1},
}, decode_steps=21)
AFTER = _doc({
    "jit_decode_n": {"1": _row(30, 30, 0.3), "8": _row(25, 200, 2.4)},
    "jit_prefill_with_decode": {"128": _row(4, 4, 0.04), "256": _row(104, 104, 2.6)},
    "jit_prefill": {"256": _row(9, 9, 0.0, timed=False)},
    "jit_verify": {"4": _row(10, 10, 0.2)},
    "shared": {"n": 7},
}, decode_steps=173)
PARENTS = _doc(None, decode_steps=173)  # a /metrics document of the parent commit: no ``launches``

READINGS = {
    "mixed_launch_ms": 1000.0 * (2.6 - 0.1) / 100,  # the largest bucket's launches alone
    "decode_step_ms": 1000.0 * (2.4 - 0.4) / 160,  # the configured rung's steps alone
    "mixed_ride_share": (108 - 6) / (173 - 21),
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_a_reader_reads_the_window(name):
    assert _reader(name)([BEFORE], [AFTER], [], None, {}) == pytest.approx(READINGS[name])
    # two engines of a fleet: sums of both
    assert _reader(name)([BEFORE, BEFORE], [AFTER, AFTER], [], None, {}) == pytest.approx(READINGS[name])


@pytest.mark.parametrize("name", sorted(READINGS))
def test_a_reader_finds_nothing_in_a_parents_document(name):
    read = _reader(name)
    assert read([PARENTS], [PARENTS], [], None, {}) is None
    assert read([], [], [], None, {}) is None
    assert read([BEFORE], [BEFORE], [], None, {}) in (None, 0.0)  # nothing launched in the window


def test_the_readers_read_an_engines_own_document(served):
    """The recorded documents above have the shape an engine publishes."""
    m = served["plain"]["metrics"]
    zero = {**m, "launches": {"shared": {"n": 0}}, "decode_steps": 0}
    assert _reader("decode_step_ms")([zero], [m], [], None, {}) > 0.0
    assert _reader("mixed_launch_ms")([zero], [m], [], None, {}) > 0.0
    assert 0.0 < _reader("mixed_ride_share")([zero], [m], [], None, {}) < 1.0


if __name__ == "__main__":  # from the parent's checkout: what its counters gave
    got = scenario()
    print(json.dumps({part: {"legacy": _legacy(got[part]), "tokens": got[part]["tokens"]} for part in got}, indent=1))
