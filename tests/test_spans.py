"""Phase spans and what reads them (ISSUE 23): the span facility itself, the
engine worker's phases tiling its loop, the counters beside them, the
profiler capture that holds the same spans, the journal layer's dispatch
timing through both front doors, the seconds beside the compile counts, and
the XLA module names the benchmark's readers match by prefix."""

import asyncio
import glob
import json
import os
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from agentainer_tpu.core.protocol import ACCEPTED_NS_HEADER, REQUEST_ID_HEADER
from agentainer_tpu.engine import llm
from agentainer_tpu.engine.llm import LLMEngine
from agentainer_tpu.engine.llm_serve import LLMServeApp
from agentainer_tpu.utils.compile_cache import enable_compile_cache
from agentainer_tpu.utils.spans import Spans
from tests.conftest import _native_available

TINY = {"max_batch": 4, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 32, "skip_warmup": True}
SHARED = "the quick brown fox jumps over the lazy dog " * 2  # ~90 tokens: buckets 32 and 64


# -- the facility ----------------------------------------------------------
def test_self_time_is_total_less_children():
    spans = Spans()
    with spans.span("outer", request_id="r1"):
        time.sleep(0.02)
        with spans.span("inner", tokens=3):
            time.sleep(0.03)
            with spans.span("leaf"):
                time.sleep(0.01)
        with spans.span("inner"):
            time.sleep(0.01)
    p = spans.snapshot()["phases"]
    assert {k: v["n"] for k, v in p.items()} == {"outer": 1, "inner": 2, "leaf": 1}
    assert p["leaf"]["self_s"] == p["leaf"]["total_s"] >= 0.01
    assert p["inner"]["self_s"] == pytest.approx(p["inner"]["total_s"] - p["leaf"]["total_s"], abs=1e-9)
    assert p["outer"]["self_s"] == pytest.approx(p["outer"]["total_s"] - p["inner"]["total_s"], abs=1e-9)
    assert p["outer"]["self_s"] >= 0.02 and p["outer"]["total_s"] >= 0.07
    # self times partition the covered time
    assert sum(v["self_s"] for v in p.values()) == pytest.approx(p["outer"]["total_s"], abs=1e-9)


def test_span_survives_an_exception_in_its_body():
    spans = Spans()
    with pytest.raises(ValueError):
        with spans.span("outer"):
            with spans.span("inner"):
                raise ValueError("boom")
    with spans.span("outer"):  # the stack unwound: this one is top-level again
        pass
    p = spans.snapshot()["phases"]
    assert p["outer"]["n"] == 2 and p["inner"]["n"] == 1
    assert p["outer"]["total_s"] >= p["inner"]["total_s"]


def test_loop_is_cut_where_the_last_top_level_span_ended():
    spans = Spans()
    with spans.loop():
        with spans.span("a"):
            time.sleep(0.01)
        time.sleep(0.01)  # glue: in the loop, under no span
        with spans.span("b"):
            time.sleep(0.01)
            mid = spans.snapshot()  # b is open: neither side counts it yet
        cut = spans.snapshot()
        time.sleep(0.02)
    done = spans.snapshot()
    assert set(mid["phases"]) == {"a"} and mid["loop_s"] == pytest.approx(mid["phases"]["a"]["total_s"], abs=2e-3)
    covered = sum(v["self_s"] for v in cut["phases"].values())
    assert 0.005 <= cut["loop_s"] - covered < 0.02  # the glue, and only it
    assert done["loop_s"] >= cut["loop_s"] + 0.02  # a finished loop counts to its end
    assert spans.snapshot() == done


def test_snapshot_is_safe_against_writing_threads():
    spans = Spans()
    stop = threading.Event()
    counts = [0] * 8

    def writer(i: int) -> None:
        while not stop.is_set():
            # new names keep arriving, as a phase's first occurrence does
            with spans.span(f"w{i}.{counts[i] % 50}"):
                with spans.span("shared"):
                    pass
            counts[i] += 1

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(len(counts))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 1.5
        last = 0
        while time.monotonic() < deadline:
            doc = spans.snapshot()
            for v in doc["phases"].values():
                assert v["total_s"] >= v["self_s"] >= 0.0
            n = doc["phases"].get("shared", {"n": 0})["n"]
            assert n >= last  # cumulative, never torn backwards
            last = n
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    # every thread's every span was kept: no update lost between threads
    assert spans.snapshot()["phases"]["shared"]["n"] == sum(counts) > 0


def test_importing_spans_does_not_import_jax():
    import subprocess

    code = "import sys; import agentainer_tpu.utils.spans; print('jax' in sys.modules)"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=60)
    assert out.stdout.strip() == "False", out


# -- the engine's worker ---------------------------------------------------
@pytest.fixture(scope="module")
def served():
    """A tiny engine that has served two rounds of six sessions, its replies
    and its metrics before and after."""
    eng = LLMEngine.create("tiny", options=dict(TINY))
    before = eng.metrics()

    async def drive():
        first = await asyncio.gather(*[
            eng.generate(SHARED + str(i), max_tokens=12, session=f"s{i}", request_id=f"a{i}", ignore_eos=True)
            for i in range(6)
        ])
        later = await asyncio.gather(*[
            eng.generate(f"and then {i}?", max_tokens=12, session=f"s{i}", request_id=f"b{i}", ignore_eos=True)
            for i in range(6)
        ])
        return first + later

    try:
        replies = asyncio.run(drive())
        time.sleep(0.5)  # the worker is back in its blocking get: a top-level span just ended
        yield eng, before, eng.metrics(), replies
    finally:
        eng.shutdown()


def test_top_level_phases_tile_the_loop(served):
    _, _, m, _ = served
    covered = sum(v["self_s"] for v in m["phases"].values())  # = the top-level spans' total time
    assert m["loop_s"] > 0
    assert abs(m["loop_s"] - covered) <= 0.02 * m["loop_s"], (m["loop_s"], covered)


def test_phases_nest_as_the_worker_runs_them(served):
    _, _, m, replies = served
    p = m["phases"]
    for name in ("engine.wait_request", "engine.admit", "engine.prefill_tick", "engine.prefill_dispatch",
                 "engine.first_token_sample", "engine.inject_lane", "engine.decode_dispatch",
                 "engine.wait_device", "engine.process_readback"):
        assert p[name]["n"] > 0, (name, sorted(p))
    assert p["engine.first_token_sample"]["n"] == len(replies)
    assert p["engine.prefill_dispatch"]["n"] == p["engine.prefill_tick"]["n"] == m["prefill_launches"]
    # a parent's total covers its children; a leaf's self time is its total
    children = sum(p[c]["total_s"] for c in ("engine.prefill_dispatch", "engine.prefix_register",
                                             "engine.first_token_sample"))
    assert p["engine.prefill_tick"]["total_s"] >= children
    assert p["engine.first_token_sample"]["self_s"] == p["engine.first_token_sample"]["total_s"]
    # 12 sessions' worth of admissions over 4 slots: sessions lost their slot
    assert p["engine.evict"]["n"] == m["session_evictions_total"] > 0


def test_prefill_counters_count_where_the_work_happens(served):
    _, before, m, replies = served
    assert before["prefill_launches"] == before["prefill_tokens"] == before["requests_finished"] == 0
    assert m["requests_finished"] == len(replies) == 12
    assert m["prefill_launches"] >= m["prefills"] == 12
    sent = sum(r["prompt_tokens"] for r in replies)
    # a returning turn also feeds the token its last reply held out; an
    # evicted session's returning turn starts a fresh context and feeds none
    fed = m["prefill_tokens"] + m["prefix_tokens_saved"]
    assert sent <= fed <= sent + 6, (sent, m["prefill_tokens"], m["prefix_tokens_saved"])
    assert m["prefix_tokens_saved"] > 0


def test_fresh_prompts_prefill_exactly_what_the_arena_did_not_serve():
    eng = LLMEngine.create("tiny", options=dict(TINY))
    try:
        async def drive():
            return [await eng.generate(SHARED + tail, max_tokens=4, ignore_eos=True) for tail in ("alpha", "beta", "gamma")]

        replies = asyncio.run(drive())
        m = eng.metrics()
        assert m["prefix_tokens_saved"] >= 128  # two forks of the 64-token level
        assert m["prefill_tokens"] == sum(r["prompt_tokens"] for r in replies) - m["prefix_tokens_saved"]
        assert m["requests_finished"] == 3
    finally:
        eng.shutdown()


# -- the same spans on the profiler's clock --------------------------------
def _host_events(trace_dir: str, stats: bool = False) -> list[tuple]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    assert files, f"no .xplane.pb under {trace_dir}"
    data = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    return [
        (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)) + ((dict(ev.stats),) if stats else ())
        for plane in data.planes if plane.name.startswith("/host:")
        for ln in plane.lines for ev in ln.events
    ]


@pytest.mark.parametrize("python_tracer", [None, True])
def test_profile_capture_holds_the_engine_spans(tmp_path, monkeypatch, python_tracer):
    monkeypatch.setenv("AGENTAINER_PROFILE_DIR", str(tmp_path))

    async def body():
        eng = LLMEngine.create("tiny", options=dict(TINY))
        serve = LLMServeApp(env={"AGENTAINER_AGENT_ID": "spans"})
        serve.engine = eng
        client = TestClient(TestServer(serve.app()))
        await client.start_server()
        try:
            # compile outside the capture
            resp = await client.post("/chat", json={"message": "warm", "session": "w", "max_tokens": 4})
            assert resp.status == 200, await resp.text()
            req = {"duration_s": 1.0} if python_tracer is None else {"duration_s": 1.0, "python_tracer": python_tracer}
            capture = asyncio.ensure_future(client.post("/profile", json=req))
            await asyncio.sleep(0.3)
            # the event loop answers while the profiler starts, runs and stops
            resp = await client.post(
                "/chat", json={"message": "hello spans", "session": "s", "max_tokens": 6},
                headers={REQUEST_ID_HEADER: "rid-1"},
            )
            assert resp.status == 200, await resp.text()
            prof = await capture
            assert prof.status == 200, await prof.text()
            return await prof.json()
        finally:
            await client.close()
            eng.shutdown()

    doc = asyncio.run(body())
    assert doc["python_tracer"] is bool(python_tracer)
    events = _host_events(doc["trace_dir"])
    by_name: dict[str, list[tuple[int, int]]] = {}
    for name, start, end in events:
        by_name.setdefault(name, []).append((start, end))
    ticks, samples = by_name.get("engine.prefill_tick", []), by_name.get("engine.first_token_sample", [])
    assert ticks and samples, sorted(n for n in by_name if n.startswith("engine."))
    assert all(any(t0 <= s0 and s1 <= t1 for t0, t1 in ticks) for s0, s1 in samples)
    frames = [n for n in by_name if re.search(r"\.py:\d+", n)]
    if python_tracer:
        assert frames  # the operator asked for Python frames and got them
    else:
        assert not frames, frames[:5]


def test_dispatch_spans_carry_their_launch_and_profile_answers_its_edges(tmp_path, monkeypatch):
    """Each dispatch span's trace event names the launch it made (ISSUE 52):
    ``program`` (the XLA module's name, as the device plane shows it), ``key``
    (bucket, rung, K), ``steps``, ``rows``, ``lanes``, from the one
    record the launch ledger counted. ``/profile`` answers the ledger at the
    capture's own two edges, and ``/metrics`` keeps them as ``last_capture``:
    the launches between them are the dispatch spans the capture holds."""
    monkeypatch.setenv("AGENTAINER_PROFILE_DIR", str(tmp_path))

    async def body():
        eng = LLMEngine.create("tiny", options=dict(TINY))
        serve = LLMServeApp(env={"AGENTAINER_AGENT_ID": "launches"})
        serve.engine = eng
        client = TestClient(TestServer(serve.app()))
        await client.start_server()
        try:
            for message in ("warm", "warm " * 40):  # compile outside the capture
                resp = await client.post("/chat", json={"message": message, "session": "w", "max_tokens": 20})
                assert resp.status == 200, await resp.text()
            assert (await (await client.get("/metrics")).json())["last_capture"] is None
            capture = asyncio.ensure_future(client.post("/profile", json={"duration_s": 1.5}))
            await asyncio.sleep(0.4)
            # two chunks of 32, then the ladder (and a verify round, if a lane drafts)
            resp = await client.post("/chat", json={"message": "hello spans " * 10, "session": "s", "max_tokens": 20})
            assert resp.status == 200, await resp.text()
            prof = await capture
            assert prof.status == 200, await prof.text()
            return await prof.json(), await (await client.get("/metrics")).json()
        finally:
            await client.close()
            eng.shutdown()

    doc, metrics = asyncio.run(body())
    assert metrics["last_capture"] == {k: doc[k] for k in ("captured_s", "launches_before", "launches_after")}
    assert doc["captured_s"] == doc["duration_s"]

    def launched(ledger, program):
        return sum(row["n"] for row in ledger.get(program, {}).values())

    spans = {
        "engine.prefill_dispatch": llm.JIT_PREFILL, "engine.decode_dispatch": llm.JIT_DECODE_N,
        "engine.first_token_sample": llm.JIT_FIRST_TOKEN, "engine.verify_dispatch": llm.JIT_VERIFY,
    }
    events = [(name, st) for name, _, _, st in _host_events(doc["trace_dir"], stats=True) if name in spans]
    for name, program in spans.items():
        mine = [st for n, st in events if n == name]
        assert len(mine) == launched(doc["launches_after"], program) - launched(doc["launches_before"], program)
        if name != "engine.verify_dispatch":
            assert mine, name
        for st in mine:
            assert set(st) >= {"program", "key", "steps", "rows", "lanes"}, (name, st)
            assert st["program"] == program
    chunks = [st for n, st in events if n == "engine.prefill_dispatch"]
    assert [(st["key"], st["steps"], st["lanes"]) for st in chunks] == [(32, 1, 0)] * len(chunks)
    assert sorted(st["rows"] for st in chunks)[-1] == 32
    rungs = [st for n, st in events if n == "engine.decode_dispatch"]
    assert all(st["steps"] == st["key"] and st["rows"] == st["lanes"] == 1 for st in rungs)
    assert [(st["steps"], st["rows"]) for n, st in events if n == "engine.first_token_sample"] == [(0, 1)]


def test_profile_capture_ends_with_the_layer_steps_it_may_hold(tmp_path, monkeypatch):
    """stop_trace's collection grows with the device events captured, and
    the callers in front wait 60 s (a traced run of a cell that got twice as
    fast timed out there): a capture ends at the duration asked for or once
    the engine has launched ``PROFILE_LAYER_STEPS``, whichever comes first,
    and says what it captured. An idle engine's capture runs its time."""
    from agentainer_tpu.engine import llm_serve

    monkeypatch.setenv("AGENTAINER_PROFILE_DIR", str(tmp_path))

    async def body():
        eng = LLMEngine.create("tiny", options=dict(TINY))
        serve = LLMServeApp(env={"AGENTAINER_AGENT_ID": "dense"})
        serve.engine = eng
        client = TestClient(TestServer(serve.app()))
        await client.start_server()
        try:
            resp = await client.post("/chat", json={"message": "warm", "session": "w", "max_tokens": 4})
            assert resp.status == 200, await resp.text()
            idle = await (await client.post("/profile", json={"duration_s": 0.4})).json()
            # room for 4 passes through tiny's layers: a chat's prefill and
            # decode steps fill it long before 20 s are over
            monkeypatch.setattr(llm_serve, "PROFILE_LAYER_STEPS", 4 * eng.cfg.n_layers)
            before = eng.forward_passes
            capture = asyncio.ensure_future(client.post("/profile", json={"duration_s": 20.0}))
            await asyncio.sleep(0.3)
            resp = await client.post("/chat", json={"message": "hello", "session": "s", "max_tokens": 24})
            assert resp.status == 200, await resp.text()
            dense = await (await capture).json()
            return idle, dense, eng.forward_passes - before
        finally:
            await client.close()
            eng.shutdown()

    idle, dense, passes = asyncio.run(body())
    assert 0.4 <= idle["duration_s"] < 2.0
    assert passes >= 4 and dense["duration_s"] < 10.0, (dense, passes)
    assert glob.glob(os.path.join(dense["trace_dir"], "**", "*.xplane.pb"), recursive=True)


# -- seconds beside the compile counts -------------------------------------
def test_compile_seconds_rise_on_a_fresh_function_only():
    stats = enable_compile_cache()  # one more listener pair on this process
    assert stats.as_dict()["trace_s"] == 0.0

    @jax.jit
    def fresh(x):
        return jnp.tanh(x) * 3.0 + x.sum()

    x = jnp.arange(7.0)
    fresh(x).block_until_ready()
    first = stats.as_dict()
    assert first["trace_s"] > 0 and first["lower_s"] > 0 and first["compile_s"] > 0
    assert first["compile_s"] >= first["retrieval_s"] >= 0  # a cache read is timed inside compile_s
    assert first["writes"] == first["misses"]
    fresh(x).block_until_ready()  # cached: nothing is traced, lowered or compiled
    again = stats.as_dict()
    assert {k: again[k] for k in ("trace_s", "lower_s", "compile_s", "retrieval_s")} == {
        k: first[k] for k in ("trace_s", "lower_s", "compile_s", "retrieval_s")
    }


# -- one dispatch timing for the journal layer -----------------------------
async def _python_front_door(tmp_path):
    from agentainer_tpu.config import Config
    from agentainer_tpu.daemon import build_services
    from agentainer_tpu.runtime.local import LocalBackend
    from agentainer_tpu.store import MemoryStore

    cfg = Config()
    cfg.auth_token = "spans-token"
    backend = LocalBackend(data_dir=str(tmp_path), ready_timeout_s=120.0)
    services = build_services(
        config=cfg, store=MemoryStore(), backend=backend, console_logs=False, data_dir=str(tmp_path)
    )
    client = TestClient(TestServer(services.app))
    await client.start_server()
    backend.set_control(f"http://127.0.0.1:{client.server.port}")

    async def close():
        await client.close()
        await asyncio.to_thread(backend.close)  # no engine_main process outlives the test

    return client, close


async def _native_front_door(tmp_path):
    from tests.test_dataplane import start_stack, teardown

    services, task, session = await start_stack(tmp_path)
    return session, lambda: teardown(services, task, session)


@pytest.mark.parametrize("front_door", ["python", "native"])
def test_journal_dispatch_is_sampled_through_the_front_door(tmp_path, front_door):
    if front_door == "native" and not _native_available():
        pytest.skip("native library unavailable")
    auth = {"Authorization": "Bearer " + ("spans-token" if front_door == "python" else "dp-token")}

    async def body():
        http, close = await (_python_front_door if front_door == "python" else _native_front_door)(tmp_path)
        try:
            resp = await http.post(
                "/agents",
                json={
                    "name": "spans-llm",
                    "model": {"engine": "llm", "config": "tiny",
                              "options": {"max_batch": 2, "max_seq": 128, "skip_warmup": True}},
                    "env": {"JAX_PLATFORMS": "cpu"},
                },
                headers=auth,
            )
            assert resp.status == 200, await resp.text()
            aid = (await resp.json())["data"]["id"]
            resp = await http.post(f"/agents/{aid}/start", headers=auth)
            assert resp.status == 200, await resp.text()
            doc = {}
            for _ in range(600):
                doc = await (await http.get(f"/agent/{aid}/metrics")).json()
                if doc.get("model_loaded"):
                    break
                await asyncio.sleep(0.2)
            assert doc.get("model_loaded"), doc
            assert doc["journal_dispatch_ms_samples"] == [] and doc["journal_dispatch_ms_p50"] is None

            # a stamp the client sends is dropped: the front door's own counts
            resp = await http.post(
                f"/agent/{aid}/chat",
                data=json.dumps({"message": "hello", "max_tokens": 4}),
                headers={ACCEPTED_NS_HEADER: "1"},
            )
            assert resp.status == 200, await resp.text()
            rid = resp.headers.get(REQUEST_ID_HEADER, "")
            assert rid
            doc = await (await http.get(f"/agent/{aid}/metrics")).json()
            samples = doc["journal_dispatch_ms_samples"]
            assert len(samples) == 1 and 0.0 <= samples[0] < 30_000.0, samples
            assert doc["journal_dispatch_ms_p50"] == samples[0]
            # the engine's own document rides the same surface
            assert doc["requests_finished"] == 1 and "engine.prefill_tick" in doc["phases"]
            assert {"trace_s", "lower_s", "compile_s", "retrieval_s", "misses"} <= set(doc["compile_cache"])

            # a replayed dispatch goes out with the journaled headers, which
            # carry no stamp: served, not sampled
            served = doc["requests_total"]
            resp = await http.post(f"/agents/{aid}/requests/{rid}/replay", headers=auth)
            assert resp.status == 200, await resp.text()
            doc = await (await http.get(f"/agent/{aid}/metrics")).json()
            assert doc["requests_total"] > served
            assert doc["journal_dispatch_ms_samples"] == samples
        finally:
            await close()

    asyncio.run(body())


# -- the XLA module names the benchmark's readers match by prefix ----------
@pytest.fixture(scope="module", params=["dense", "paged"])
def step_engine(request):
    eng = LLMEngine.create("tiny", options=dict(TINY, paged_kv=request.param == "paged"))
    yield eng
    eng.shutdown()


def _lower_step(eng: LLMEngine, step: str):
    B = eng.max_batch
    if step == "prefill":
        where = (jnp.asarray(eng._bt[0:1]),) if eng.paged else (jnp.int32(0),)
        return eng._prefill.lower(
            eng.params, eng.cache, *where, jnp.zeros((1, 32), jnp.int32), jnp.zeros((1, 32), jnp.int32), jnp.int32(5)
        )
    if step == "first_token":
        return eng._first_token.lower(
            jnp.zeros((eng.cfg.vocab_size,), jnp.float32), jax.random.PRNGKey(0),
            np.float32(0.0), np.int32(0), np.float32(1.0),
        )
    lanes = (eng._dtok, eng._dpos, eng._dtemps, eng._dtopk, eng._dtopp)
    if step == "decode_n":
        keys = jax.random.split(jax.random.PRNGKey(0), eng.decode_chunk)
        return eng._decode_n.lower(eng.params, eng.cache, *eng._bt_arg(), *lanes, keys)
    if step == "prefill_with_decode":
        if eng._prefill_with_decode is None:
            return None
        return eng._prefill_with_decode.lower(
            eng.params, eng.cache, jnp.int32(0), jnp.zeros((1, 32), jnp.int32), jnp.zeros((1, 32), jnp.int32),
            jnp.int32(5), *lanes, jax.random.split(jax.random.PRNGKey(0), 1),
        )
    if step == "fused":
        live = jnp.zeros((B,), bool)
        return eng._fused_fn().lower(
            eng.params, eng.cache, *eng._bt_arg(), *lanes, eng._dhist, eng._dhlen,
            eng._stok, eng._spos, eng._stemps, eng._stopk, eng._stopp, eng._shist, eng._shlen,
            live, live, jnp.zeros((B,), jnp.int32), live,
            jax.random.split(jax.random.PRNGKey(0), eng._fused_cap), jnp.int32(1),
        )
    return eng._verify_fn(2).lower(
        eng.params, eng.cache, *eng._bt_arg(), *lanes,
        jnp.zeros((B, 2), jnp.int32), jnp.zeros((B,), jnp.int32), jax.random.PRNGKey(0),
    )


STEP_MODULES = {
    "prefill": llm.JIT_PREFILL, "first_token": llm.JIT_FIRST_TOKEN, "decode_n": llm.JIT_DECODE_N,
    "verify": llm.JIT_VERIFY, "prefill_with_decode": llm.JIT_PREFILL_WITH_DECODE, "fused": llm.JIT_FUSED,
}


@pytest.mark.parametrize("step, name", sorted(STEP_MODULES.items()))
def test_step_module_names_the_benchmark_matches(step_engine, step, name):
    """``benchmark/layer_metrics/{decode,prefill}_step_roofline.py`` and
    ``prefill_dev_share.py`` find the steps' device time by prefixes of the XLA
    module name, and the launch ledger's rows (``/metrics`` ``launches``) join
    a trace's modules by the whole name: the engine's constants are the
    lowered programs' names, on the dense arena and on the page pool; a
    renamed step function must fail here, not turn a roofline into ``None``.
    ``jit_first_token`` is the sampler after the final prefill chunk: a module
    of its own, so that ``jit_prefill``'s device time stays the forward pass
    alone."""
    lowered = _lower_step(step_engine, step)
    if lowered is None:
        assert step == "prefill_with_decode" and step_engine.paged  # the page pool keeps two launches
        return
    assert re.search(r"module @(\w+)", lowered.as_text()).group(1) == name


# -- the ``attention`` block's fetch counters (ISSUE 33) -----------------------


def test_decode_block_counters_follow_the_lanes_positions():
    """``/metrics`` ``attention``: ``decode_blocks_live`` counts the K/V blocks
    ``flash_decode``'s clamped index map lets through (a lane at position p
    reads ``p // bk + 1``, a parked lane one), ``decode_blocks_stored`` the
    blocks the arena rows hold; both cumulative, counted on the host at each
    decode launch. Two lanes of 1,024 (two blocks of 512 each): a 601-token
    prompt decodes from position 601 (two blocks) beside a parked lane (one),
    then a 3-token prompt decodes in the first block beside a parked lane."""
    eng = LLMEngine.create(
        "tiny", options={"max_batch": 2, "max_seq": 1024, "decode_chunk": 8, "prefill_chunk": 256,
                         "speculative": False, "skip_warmup": True},
    )
    try:
        def snap():
            m = eng.metrics()
            assert m["mixed_launches"] == 0  # one request at a time: plain decode launches only
            return m["attention"], sum(int(c) * n for c, n in m["decode_chunk_hist"].items())

        att0, steps0 = snap()
        assert att0["decode_block_positions"] == 512
        assert "prefill_tile" not in att0  # the flash kernel's plan: named where that kernel serves (a TPU)
        assert att0["decode_blocks_live"] == att0["decode_blocks_stored"] == 0
        asyncio.run(eng.generate("word " * 120, max_tokens=8, ignore_eos=True))  # 601 tokens
        att1, steps1 = snap()
        asyncio.run(eng.generate("hi", max_tokens=8, ignore_eos=True))  # 3 tokens
        att2, steps2 = snap()
    finally:
        eng.shutdown()
    long_steps, short_steps = steps1 - steps0, steps2 - steps1
    assert long_steps >= 7 and short_steps >= 7
    assert att1["decode_blocks_live"] == (2 + 1) * long_steps
    assert att1["decode_blocks_stored"] == 2 * 2 * long_steps
    assert att2["decode_blocks_live"] - att1["decode_blocks_live"] == (1 + 1) * short_steps
    assert att2["decode_blocks_stored"] - att1["decode_blocks_stored"] == 2 * 2 * short_steps
    assert att2["decode_blocks_live"] <= att2["decode_blocks_stored"]


@pytest.mark.parametrize("positions, steps, live", [
    # lanes 0 and 2 step; 1 and 3 are parked (one block a step each)
    ([0, 511], 1, 1 + 1 + 2),
    # a lane crossing into its second block during an 8-step launch
    ([508], 8, 4 * 1 + 4 * 2 + 8 * 3),
    # a lane the host still steps that has reached the arena's last row is seen at row 0
    ([1020], 8, 3 * 2 + 5 * 1 + 8 * 3),
    ([], 2, 2 * 4),
], ids=["two-lanes", "crossing", "reaches-scratch", "all-parked"])
def test_decode_block_count_by_hand(positions, steps, live):
    eng = LLMEngine.create("tiny", options={**TINY, "max_seq": 1024, "speculative": False})
    try:
        eng._count_decode_blocks(positions, steps)
        att = eng.metrics()["attention"]
    finally:
        eng.shutdown()
    assert att["decode_blocks_live"] == live
    assert att["decode_blocks_stored"] == steps * 4 * 2


# -- the ``moe`` block of ``/metrics`` (ISSUE 26) ------------------------------


def test_moe_block_names_no_path_for_a_dense_model(served):
    _, before, m, _ = served
    for doc in (before, m):
        assert doc["moe"] == {
            "impl": "none", "experts": 0, "top_k": 0, "renormalize": False,
            "experts_held": 0, "shared_experts": 0, "router": None,
            "held": 0, "published": 0, "offset": 0,
            "prefill_impl": "none", "routed_from_rows": None,
            "assignments": 0, "rows_all_experts": 0, "rows_routed": 0, "rows_gathered_in_kernel": 0,
        }
        assert doc["model_arch"]["qk_norm"] is False


@pytest.mark.parametrize("config, options, impl, prefill_impl, cut", [
    # float32 experts on this CPU: the ridge of a 4-byte weight is 482 rows
    ("tiny-olmoe", {}, "all_experts_einsum", "sorted_grouped_ffn", 482),
    ("tiny-moe", {}, "all_experts_einsum", "sorted_grouped_ffn", 482),
    ("tiny-moe", {"quant": "int8"}, "all_experts_einsum", "sorted_grouped_ffn", 121),
    ("tiny-olmoe", {"routed": True}, "routed_dispatch", "routed_dispatch", None),
], ids=["olmoe", "mixtral", "mixtral_int8", "olmoe_routed"])
def test_moe_block_names_the_path_the_steps_trace(config, options, impl, prefill_impl, cut):
    """``impl`` is the path of the calls under ``routed_from_rows`` (what
    ``moe_rows_over_routed`` reads), ``prefill_impl`` that of the calls from
    there on; the counters count launches, and nothing has been launched
    (``skip_warmup``)."""
    eng = LLMEngine.create(config, options={**TINY, "speculative": False, **options})
    try:
        cfg, m = eng.cfg, eng.metrics()
    finally:
        eng.shutdown()
    assert m["moe"] == {
        "impl": impl, "experts": cfg.n_experts, "top_k": cfg.experts_per_token, "renormalize": cfg.moe_renormalize,
        # every expert is in the stack, none is shared, the router is a softmax (ISSUE 30's three keys)
        "experts_held": cfg.n_experts, "shared_experts": 0, "router": "softmax",
        # the share in the deployment's words: experts offset .. offset + held of the published (PR 57)
        "held": cfg.n_experts, "published": cfg.n_experts, "offset": 0,
        "prefill_impl": prefill_impl, "routed_from_rows": cut,
        "assignments": 0, "rows_all_experts": 0, "rows_routed": 0, "rows_gathered_in_kernel": 0,
    }
    assert m["model_arch"]["qk_norm"] is cfg.qk_norm and m["model_arch"]["head_dim"] == cfg.head_dim


def test_an_olmoe_engine_decodes_what_the_plain_scan_gives():
    """Prefill and decode through the engine's arena and ladders, with the
    QK-norm and the un-renormalised gates, give the plain greedy scan's
    tokens (same weights)."""
    from agentainer_tpu.models.configs import get_config
    from agentainer_tpu.models.llama import greedy_decode, init_params

    cfg = get_config("tiny-olmoe")
    eng = LLMEngine.create("tiny-olmoe", options={**TINY, "speculative": False})
    try:
        got = asyncio.run(eng.generate("hello there", max_tokens=12, ignore_eos=True))
    finally:
        eng.shutdown()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = jnp.asarray([eng.tokenizer.encode("hello there")], jnp.int32)
    want = greedy_decode(params, cfg, prompt, 12, 64, dtype=jnp.float32)[0]
    assert got["tokens"] == [int(t) for t in want]


# -- a slot of K/V rows and a recurrent state (the hybrid block's second family) ----
def test_an_olmo_hybrid_engine_names_its_kinds_its_leaves_and_what_a_snapshot_ships():
    """Names the benchmark's readers and a reader of a capture rely on:
    ``model_arch.layer_kinds`` with the kinds ``gdn`` and ``full``;
    ``attention.{gdn,full}_{prefill,decode}`` naming the implementation each
    call traces, with the reason; ``cache`` bytes by kind of leaf, ``k`` and
    ``v`` beside ``state`` and ``conv``; and ``engine.snapshot`` /
    ``engine.restore`` spans whose trace events carry ``bytes=`` by leaf."""
    eng = LLMEngine.create("tiny-olmo-hybrid", options={"max_batch": 2, "max_seq": 128, "decode_chunk": 4, "prefill_chunk": 32})
    seen = []
    span = eng._spans.span
    eng._spans.span = lambda name, **attrs: (seen.append((name, attrs)), span(name, **attrs))[1]
    try:
        async def drive():
            await eng.chat("s", "a session that will be snapshotted", max_tokens=5)
            eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
            blob = await eng.snapshot_session("s")
            return await eng.restore_session("t", blob)

        assert asyncio.run(drive()) is True
        time.sleep(0.2)
        m = eng.metrics()
    finally:
        eng.shutdown()
    assert m["model_arch"]["layer_kinds"] == {"full": 2, "gdn": 6}
    for key in ("gdn_prefill", "gdn_decode", "full_prefill", "full_decode", "reason"):
        assert m["attention"][key], key
    assert m["cache"]["kinds"] == ["k", "v", "state", "conv"]
    assert all(m["cache"][leaf + "_bytes"] > 0 for leaf in m["cache"]["kinds"])
    for name in ("engine.snapshot", "engine.restore", "engine.state_reset"):
        assert m["phases"][name]["n"] >= 1, (name, sorted(m["phases"]))
    carried = {name: attrs["bytes"] for name, attrs in seen if name in ("engine.snapshot", "engine.restore") and "bytes" in attrs}
    assert set(carried) == {"engine.snapshot", "engine.restore"}
    for text in carried.values():
        sizes = dict(part.split("=") for part in text.split(","))
        assert sorted(sizes) == ["conv", "k", "state", "v"] and all(int(v) > 0 for v in sizes.values())
    assert int(dict(p.split("=") for p in carried["engine.restore"].split(","))["state"]) == 6 * 12 * 6 * 24 * 4


# -- two kinds of attention in one model: k, v and a ring in one slot (Laguna) -------
def test_a_laguna_engine_names_both_kinds_the_gate_the_rotaries_and_the_rings_bytes():
    """Names the benchmark's readers and a reader of a capture rely on:
    ``model_arch.layer_kinds`` with ``full`` and ``swa``; ``attention.heads``
    and ``attention.rotary`` by kind, ``attention.gate``, ``attention.{full,
    swa}_{prefill,decode}``; ``cache`` bytes by leaf with the ring (``wk``,
    ``wv``) beside ``k`` and ``v`` and no state; the window and position
    counters and the ``moe`` block counted by this engine; ``engine.snapshot``
    / ``engine.restore`` spans carrying ``bytes=`` for the ring, which ships
    whole; the ``jax.named_scope``s of the layer body in the step's metadata."""
    options = {"max_batch": 2, "max_seq": 256, "prefill_chunk": 32}
    eng = LLMEngine.create("tiny-laguna", options=options)
    seen = []
    span = eng._spans.span
    eng._spans.span = lambda name, **attrs: (seen.append((name, attrs)), span(name, **attrs))[1]
    try:
        async def drive():
            await eng.chat("s", "a session whose context laps the ring of a window layer before it is snapshotted", max_tokens=9)
            eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
            blob = await eng.snapshot_session("s")
            return await eng.restore_session("t", blob)

        assert asyncio.run(drive()) is True
        time.sleep(0.2)
        m = eng.metrics()
        tokens = jnp.zeros((1, 32), jnp.int32)
        text = eng._prefill.lower(eng.params, eng.cache, jnp.int32(0), tokens, tokens, jnp.int32(4)).as_text(debug_info=True)
    finally:
        eng.shutdown()
    a, cache = m["attention"], m["cache"]
    assert m["model_arch"]["layer_kinds"] == {"full": 2, "swa": 6}
    assert a["heads"] == {"full": 6, "swa": 8} and a["gate"] == "per_head" and sorted(a["rotary"]) == ["full", "swa"]
    for key in ("full_prefill", "full_decode", "swa_prefill", "swa_decode", "reason"):
        assert a[key], key
    assert cache["kinds"] == ["k", "v", "wk", "wv"] and all(cache[leaf + "_bytes"] > 0 for leaf in cache["kinds"])
    for key in ("window_wraps", "window_decode_blocks_live", "window_decode_blocks_unbounded", "global_decode_rows",
                "window_decode_rows", "rows_positioned", "rows_past_original_max", "decode_blocks_live", "decode_blocks_stored"):
        assert a[key] > 0, key
    for key in ("experts_held", "shared_experts", "router", "assignments", "rows_routed", "rows_all_experts", "rows_gathered_in_kernel"):
        assert key in m["moe"], key
    for name in ("engine.snapshot", "engine.restore", "engine.state_reset"):
        assert m["phases"][name]["n"] >= 1, (name, sorted(m["phases"]))
    assert cache["state_resets"] == 0  # the lane was opened for its request; there is no state to zero
    carried = {name: attrs["bytes"] for name, attrs in seen if name in ("engine.snapshot", "engine.restore") and "bytes" in attrs}
    assert set(carried) == {"engine.snapshot", "engine.restore"}
    for text_ in carried.values():
        sizes = {k: int(v) for k, v in (part.split("=") for part in text_.split(","))}
        assert sorted(sizes) == ["k", "v", "wk", "wv"]
        assert sizes["wk"] == sizes["wv"] == 6 * (16 + 32) * 2 * 16 * 4  # the ring whole: 6 layers x R rows, float32 here
        assert sizes["k"] == sizes["v"] and sizes["k"] % (2 * 2 * 16 * 4) == 0  # rows of 2 layers x 2 heads of 16
    rows = {name: int(dict(p.split("=") for p in text_.split(","))["k"]) // (2 * 2 * 16 * 4) for name, text_ in carried.items()}
    assert rows["engine.snapshot"] == 128 and 48 < rows["engine.restore"] <= 128  # the position's bucket; the rows kept
    for scope in ("attn_global", "attn_window", "attn_gate", "rope_partial", "moe_shared_expert"):
        assert scope in text, scope


# -- chosen key blocks beside a conv-less linear state in one slot (MiniCPM-SALA) ------
def test_a_minicpm_sala_engine_names_both_kinds_the_selection_the_state_and_the_pooled_leafs_bytes():
    """Names the benchmark's readers and a reader of a capture rely on:
    ``model_arch.layer_kinds`` with ``sparse`` and ``lightning``;
    ``attention.{sparse,lightning}_{prefill,decode}``; the ``attention.sparse``
    block (the sizes and ``steps_dense`` / ``steps_sparse``, ``blocks_live`` /
    ``blocks_selected`` / ``blocks_forced``, ``rows_live`` / ``rows_read``,
    ``pooled_rows_scored``) and the ``linear`` block (``kind``,
    ``state_bytes_lane``, ``rows_chunked``, ``steps``); ``cache`` bytes by leaf
    with ``ck`` and the state and no conv; ``engine.snapshot`` /
    ``engine.restore`` spans carrying ``bytes=`` with the pooled leaf, a row
    every 4 positions of the snapshot's bucket; the ``jax.named_scope``s of
    the two mixers in the steps' metadata; and the launch ledger counting the
    step programs as it counts the others'."""
    options = {"max_batch": 2, "max_seq": 256, "prefill_chunk": 32, "decode_chunk": 4}
    eng = LLMEngine.create("tiny-minicpm-sala", options=options)
    seen = []
    span = eng._spans.span
    eng._spans.span = lambda name, **attrs: (seen.append((name, attrs)), span(name, **attrs))[1]
    try:
        async def drive():
            text = "a document long enough that the session passes the tiny dense_len of ninety-six rows and chooses its blocks"
            await eng.chat("s", text, max_tokens=9)
            eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
            blob = await eng.snapshot_session("s")
            return await eng.restore_session("t", blob)

        assert asyncio.run(drive()) is True
        time.sleep(0.2)
        m = eng.metrics()
        tokens = jnp.zeros((1, 32), jnp.int32)
        prefill = eng._prefill.lower(eng.params, eng.cache, jnp.int32(0), tokens, tokens, jnp.int32(4)).as_text(debug_info=True)
        decode = eng._decode_n.lower(
            eng.params, eng.cache, eng._dtok, eng._dpos, eng._dtemps, eng._dtopk, eng._dtopp,
            jax.random.split(jax.random.PRNGKey(0), 1),
        ).as_text(debug_info=True)
    finally:
        eng.shutdown()
    a, cache, lin = m["attention"], m["cache"], m["linear"]
    assert m["model_arch"]["layer_kinds"] == {"lightning": 4, "sparse": 4}
    for key in ("sparse_prefill", "sparse_decode", "lightning_prefill", "lightning_decode", "reason"):
        assert a[key], key
    sp = a["sparse"]
    assert {k: sp[k] for k in ("kernel", "stride", "block", "init_blocks", "window", "topk", "dense_len")} == {
        "kernel": 8, "stride": 4, "block": 16, "init_blocks": 1, "window": 32, "topk": 6, "dense_len": 96}
    for key in ("steps_dense", "steps_sparse", "blocks_live", "blocks_selected", "blocks_forced", "rows_live", "rows_read",
                "pooled_rows_scored"):
        assert sp[key] > 0, key
    assert sp["blocks_forced"] < sp["blocks_selected"] < sp["blocks_live"] and sp["rows_read"] < sp["rows_live"]
    assert lin["kind"] == "lightning" and lin["conv"] is False and lin["state_bytes_lane"] == 4 * 4 * 16 * 16 * 4
    assert lin["rows_chunked"] + lin["steps"] == sp["steps_dense"] + sp["steps_sparse"] and lin["steps"] >= 8
    assert cache["kinds"] == ["k", "v", "ck", "state"] and all(cache[leaf + "_bytes"] > 0 for leaf in cache["kinds"])
    assert cache["ck_bytes"] * 4 == cache["k_bytes"] and cache["state_resets"] >= 1
    for name in ("engine.snapshot", "engine.restore", "engine.state_reset"):
        assert m["phases"][name]["n"] >= 1, (name, sorted(m["phases"]))
    carried = {name: attrs["bytes"] for name, attrs in seen if name in ("engine.snapshot", "engine.restore") and "bytes" in attrs}
    assert set(carried) == {"engine.snapshot", "engine.restore"}
    sizes = {name: {k: int(v) for k, v in (part.split("=") for part in text.split(","))} for name, text in carried.items()}
    row = 4 * 2 * 16 * 4  # a K row of the 4 sparse layers: 2 heads of 16, float32 here
    assert sorted(sizes["engine.snapshot"]) == sorted(sizes["engine.restore"]) == ["ck", "k", "state", "v"]
    assert sizes["engine.snapshot"]["k"] == 128 * row and sizes["engine.snapshot"]["ck"] == 128 // 4 * row  # the position's bucket
    assert 96 * row < sizes["engine.restore"]["k"] <= 128 * row and sizes["engine.restore"]["ck"] == 128 // 4 * row
    assert sizes["engine.restore"]["state"] == sizes["engine.snapshot"]["state"] == 4 * 4 * 16 * 16 * 4
    for scope in ("sparse_index", "sparse_select", "sparse_attend", "lightning_chunk", "attn_gate"):
        assert scope in prefill, scope
    for scope in ("sparse_index", "sparse_select", "sparse_attend", "lightning_step"):
        assert scope in decode, scope
    ledger = m["launches"]
    assert any(name.startswith("jit_prefill") for name in ledger) and any(name.startswith("jit_decode_n") for name in ledger)


def test_a_solar_open2_engine_names_the_state_the_gate_the_share_and_every_leafs_bytes():
    """Names the benchmark's readers and a reader of a capture rely on (ISSUE
    57): ``model_arch.layer_kinds`` with ``kda`` and ``full``;
    ``attention.{kda,full}_{prefill,decode}``, ``attention.gate`` ("full": as
    wide as the output) and the K/V heads a row is stored with; the ``linear``
    block's ``heads``, ``head_dim``, ``neg_eigval`` beside ``state_bytes_lane``
    and the counters; the ``moe`` block's ``held`` / ``published`` / ``offset``
    for a chip that holds a share; ``cache`` bytes of each of the four leaf
    kinds; ``engine.snapshot`` / ``engine.restore`` spans carrying ``bytes=`` by
    leaf; the ``jax.named_scope``s of the rule's calls and the gate in the
    steps' metadata; and the launch ledger counting the step programs."""
    import dataclasses

    from agentainer_tpu.models import configs

    share = dataclasses.replace(configs.get_config("tiny-solar-open2"), name="tiny-solar-open2-share", experts_held=2, expert_offset=4)
    options = {"max_batch": 2, "max_seq": 256, "prefill_chunk": 32, "decode_chunk": 4}
    configs.register(share)
    try:
        eng = LLMEngine.create(share.name, options=options)
    finally:
        configs._REGISTRY.pop(share.name)  # the tables of other test files list the registry whole
    seen = []
    span = eng._spans.span
    eng._spans.span = lambda name, **attrs: (seen.append((name, attrs)), span(name, **attrs))[1]
    try:
        async def drive():
            await eng.chat("s", "a prompt of a few chunks, so that the chunked rule and then the step both run", max_tokens=9)
            eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
            blob = await eng.snapshot_session("s")
            return await eng.restore_session("t", blob)

        assert asyncio.run(drive()) is True
        time.sleep(0.2)
        m = eng.metrics()
        tokens = jnp.zeros((1, 32), jnp.int32)
        prefill = eng._prefill.lower(eng.params, eng.cache, jnp.int32(0), tokens, tokens, jnp.int32(4)).as_text(debug_info=True)
        decode = eng._decode_n.lower(
            eng.params, eng.cache, eng._dtok, eng._dpos, eng._dtemps, eng._dtopk, eng._dtopp,
            jax.random.split(jax.random.PRNGKey(0), 1),
        ).as_text(debug_info=True)
    finally:
        eng.shutdown()
    a, cache, lin, moe = m["attention"], m["cache"], m["linear"], m["moe"]
    assert m["model_arch"]["layer_kinds"] == {"full": 3, "kda": 6}
    for key in ("kda_prefill", "kda_decode", "full_prefill", "full_decode", "reason"):
        assert a[key], key
    assert a["gate"] == "full" and a["kv_heads_stored"] == 2
    assert (lin["kind"], lin["heads"], lin["head_dim"], lin["neg_eigval"], lin["conv"]) == ("kda", 4, 16, True, True)
    assert lin["state_bytes_lane"] == 6 * 4 * 16 * 16 * 4 and lin["rows_chunked"] > 0 and lin["steps"] >= 8
    assert (moe["held"], moe["published"], moe["offset"]) == (2, 8, 4) and moe["experts_held"] == 2 and moe["experts"] == 8
    assert cache["kinds"] == ["k", "v", "state", "conv"] and all(cache[leaf + "_bytes"] > 0 for leaf in cache["kinds"])
    assert cache["state_bytes"] == 2 * lin["state_bytes_lane"] and cache["k_bytes"] == 3 * 2 * 256 * 2 * 16 * 4
    assert cache["conv_bytes"] == 2 * 6 * 3 * 192 * 4 and cache["state_resets"] >= 1
    for name in ("engine.snapshot", "engine.restore", "engine.state_reset"):
        assert m["phases"][name]["n"] >= 1, (name, sorted(m["phases"]))
    carried = {name: attrs["bytes"] for name, attrs in seen if name in ("engine.snapshot", "engine.restore") and "bytes" in attrs}
    sizes = {name: {k: int(v) for k, v in (part.split("=") for part in text.split(","))} for name, text in carried.items()}
    assert sorted(sizes["engine.snapshot"]) == sorted(sizes["engine.restore"]) == ["conv", "k", "state", "v"]
    assert sizes["engine.restore"]["state"] == sizes["engine.snapshot"]["state"] == lin["state_bytes_lane"]
    for scope in ("kda_prepass", "kda_scan", "attn_gate", "moe_shared_expert"):
        assert scope in prefill, scope
    for scope in ("kda_step", "attn_gate"):
        assert scope in decode, scope
    assert "kda_scan" not in decode
    ledger = m["launches"]
    assert any(name.startswith("jit_prefill") for name in ledger) and any(name.startswith("jit_decode_n") for name in ledger)


def test_attention_names_the_gate_and_the_stored_heads_of_every_block_with_kv_rows():
    """``attention.gate`` and ``attention.kv_heads_stored`` (ISSUE 57) by the
    block's kind: a gate a head (Laguna), as wide as the output (a sparse
    layer's; Solar-Open2's), none (Olmo-Hybrid, whose 6 K/V heads are stored as
    8); absent where the cache has no K/V rows (Kimi-Linear's latent leaf)."""
    want = {"tiny-laguna": ("per_head", 2), "tiny-minicpm-sala": ("full", 2), "tiny-olmo-hybrid": ("none", 8),
            "tiny-solar-open2": ("full", 2), "tiny-kimi-linear": None}
    for name, said in want.items():
        eng = LLMEngine.create(name, options={"max_batch": 2, "max_seq": 256, "prefill_chunk": 32, "skip_warmup": True})
        try:
            a = eng.metrics()["attention"]
        finally:
            eng.shutdown()
        assert (a.get("gate"), a.get("kv_heads_stored")) == (said or (None, None)), name


def test_attention_names_the_prefill_tile_where_the_flash_kernel_serves(monkeypatch):
    """``/metrics`` ``attention.prefill_tile`` (ISSUE 49): where the plan says
    ``pallas:flash_prefill`` over the dense arena (a TPU engine; here the plan
    is handed the kernel's name over the XLA function), the engine names the
    tile a full chunk's call compiles to: ``prefill_plan`` of the chunk's rows,
    the model's heads and the arena's rows and dtype."""
    from agentainer_tpu.engine import llm
    from agentainer_tpu.ops.attention import CacheAttention, _reference_dense
    from agentainer_tpu.ops.pallas_attention import prefill_plan

    named = CacheAttention(_reference_dense, "pallas:flash_prefill", "pallas:flash_decode", "named for the test", "stack+layer")
    monkeypatch.setattr(llm, "plan_cache_attention", lambda *a, **kw: named)
    eng = LLMEngine.create(
        "tiny", options={"max_batch": 2, "max_seq": 1024, "prefill_chunk": 256, "speculative": False, "skip_warmup": True},
    )
    try:
        att = eng.metrics()["attention"]
        k = eng.cache.k
    finally:
        eng.shutdown()
    _, bq, bk, _ = prefill_plan(256, 4, 2, 16, 1024, k.dtype, k.dtype)
    assert att["prefill_tile"] == {"bq": bq, "bk": bk, "operands": str(k.dtype)} and (bq, bk) == (256, 256)
