"""The boot's timeline (ISSUE 34): ``boot.*`` spans from ``engine_main``'s
entry to ready in the engine's ``/metrics``, the spawn stamp, the first
dispatch after ready, compile seconds by program, the daemon's own start in
``/health``, and the benchmark's readers fed the live document."""

import asyncio
import importlib
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from agentainer_tpu.core.protocol import ACCEPTED_NS_HEADER, REQUEST_ID_HEADER, SPAWNED_NS_ENV
from agentainer_tpu.engine.llm_serve import LLMServeApp
from agentainer_tpu.utils.boot import BootTimeline, process_age_s
from agentainer_tpu.utils.compile_cache import CompileCacheStats, enable_compile_cache
from agentainer_tpu.utils.spans import Spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPTIONS = {"max_batch": 2, "max_seq": 128, "system_prompt": "You are a helper."}
STAGES = {"boot.import", "boot.backend", "boot.weights", "boot.engine_init", "boot.warmup", "boot.prewarm_prefix"}
WARMUP_PARTS = {
    "boot.warmup_serve", "boot.warmup_snapshot", "boot.warmup_prefix", "boot.warmup_verify", "boot.warmup_mixed",
}
INSIDE_CREATE = ("boot.backend", "boot.weights", "boot.engine_init", "boot.warmup")
READERS = [
    "engine_boot_s", "boot_import_backend_s", "boot_weights_s", "boot_warmup_s", "boot_jit_s", "boot_cache_misses",
]


def _serve(options: dict, drive, boot: BootTimeline | None = None):
    """A tiny engine behind its serve app, loaded by the app's own loader
    thread; ``drive(client, ready_doc)`` runs against it."""

    async def body():
        env = {"AGENTAINER_MODEL_CONFIG": "tiny", "AGENTAINER_MODEL_OPTIONS": json.dumps(options)}
        app = LLMServeApp(env=env, compile_stats=enable_compile_cache(), boot=boot)
        client = TestClient(TestServer(app.app()))
        await client.start_server()
        try:
            doc = {}
            for _ in range(1500):
                doc = await (await client.get("/metrics")).json()
                if doc["boot"]["ready_s"] is not None:
                    break
                await asyncio.sleep(0.1)
            assert doc.get("model_loaded"), doc.get("engine_error")
            return await drive(client, doc)
        finally:
            if app.engine is not None:
                app.engine.shutdown()
            await client.close()

    return asyncio.run(body())


@pytest.fixture(scope="module")
def booted():
    """``/metrics`` of a warmed tiny engine: at ready, again untouched, and
    after its first ``/chat`` (sent with no accept stamp, as a replay is)."""

    async def drive(client, ready):
        again = await (await client.get("/metrics")).json()
        resp = await client.post("/chat", json={"message": "hi", "max_tokens": 4})
        assert resp.status == 200, await resp.text()
        resp = await client.post("/chat", json={"message": "more", "max_tokens": 4}, headers={ACCEPTED_NS_HEADER: "1"})
        assert resp.status == 200, await resp.text()
        return ready, again, await (await client.get("/metrics")).json()

    # a spawned host's timeline: main's entry a moment ago, the backend's stamp before it
    started = time.perf_counter_ns(), time.time_ns()
    boot = BootTimeline.at_main(*started, {SPAWNED_NS_ENV: str(started[1] - 250_000_000)})
    boot.imported()
    return _serve(OPTIONS, drive, boot)


# -- the timeline ------------------------------------------------------------
def test_stage_names_are_pinned(booted):
    phases = booted[0]["boot"]["phases"]
    assert set(phases) == STAGES | WARMUP_PARTS
    assert all(phases[name]["n"] == 1 for name in STAGES | WARMUP_PARTS if name != "boot.import")
    assert phases["boot.import"]["n"] == 2  # main's thread up to the app, the loader's import of the engine
    assert set(booted[0]["boot"]) == {
        "spawned_unix_ns", "started_unix_ns", "spawn_to_main_s", "ready_s", "warm_boot", "phases", "stages",
        "compile_cache_at_ready", "first_dispatch_s", "first_dispatch_replayed",
    }


def test_warmup_parts_nest_under_warmup(booted):
    phases = booted[0]["boot"]["phases"]
    parts = sum(phases[name]["total_s"] for name in WARMUP_PARTS)
    assert phases["boot.warmup"]["total_s"] >= parts > 0
    assert phases["boot.warmup"]["self_s"] == pytest.approx(phases["boot.warmup"]["total_s"] - parts, abs=1e-6)
    assert all(phases[name]["self_s"] == phases[name]["total_s"] for name in STAGES - {"boot.warmup"})


def test_self_times_tile_main_to_ready(booted):
    boot = booted[0]["boot"]
    covered = sum(p["self_s"] for p in boot["phases"].values())
    assert covered <= boot["ready_s"] + 1e-6
    assert boot["ready_s"] - covered <= max(0.02 * boot["ready_s"], 0.05), (covered, boot["ready_s"])


def test_engine_load_s_is_the_stages_inside_create(booted):
    doc = booted[0]
    inside = sum(doc["boot"]["phases"][name]["total_s"] for name in INSIDE_CREATE)
    assert doc["warmup_skipped"] is False
    assert inside <= doc["engine_load_s"] + 0.005 and doc["engine_load_s"] - inside < 0.1, (inside, doc["engine_load_s"])


def test_block_is_frozen_at_ready(booted):
    ready, again, served = booted
    assert ready["boot"] == again["boot"]
    last = ("first_dispatch_s", "first_dispatch_replayed")
    assert {k: v for k, v in served["boot"].items() if k not in last} == {
        k: v for k, v in ready["boot"].items() if k not in last
    }
    assert [ready["boot"][k] for k in last] == [None, None]


def test_each_stage_has_the_compile_listeners_difference(booted):
    boot = booted[0]["boot"]
    assert set(boot["stages"]) == STAGES
    assert all(set(v) == {"jit_s", "retrieval_s", "cache_misses"} for v in boot["stages"].values())
    assert boot["stages"]["boot.warmup"]["jit_s"] > 0  # the step programs are traced there whatever the cache holds
    at_ready = boot["compile_cache_at_ready"]
    total = at_ready["trace_s"] + at_ready["lower_s"] + at_ready["compile_s"]
    # the listener is the process's: other tests' compiles sit in its totals, never in a stage's difference
    assert sum(v["jit_s"] for v in boot["stages"].values()) <= total + 1e-6
    assert booted[2]["compile_cache"]["misses"] >= at_ready["misses"]


def test_first_dispatch_is_written_once_by_the_first_chat_after_ready(booted):
    boot = booted[2]["boot"]
    # the fixture's first /chat carried no accept stamp (a replayed dispatch's mark), its second did
    assert boot["first_dispatch_replayed"] is True
    assert 0.0 <= boot["first_dispatch_s"] < 60.0


def test_spawn_stamp_gives_spawn_to_main(booted):
    boot = booted[0]["boot"]
    assert boot["spawn_to_main_s"] == pytest.approx(0.25)
    assert boot["started_unix_ns"] - boot["spawned_unix_ns"] == 250_000_000
    assert boot["warm_boot"] is False


def test_skip_warmup_leaves_no_warmup_stage():
    async def drive(client, ready):
        return ready

    doc = _serve({**OPTIONS, "skip_warmup": True}, drive)
    assert doc["warmup_skipped"] is True
    assert set(doc["boot"]["phases"]) == STAGES - {"boot.warmup"}
    # an engine nobody spawned: no stamp, and one boot.import (the loader's)
    assert doc["boot"]["spawned_unix_ns"] is None and doc["boot"]["spawn_to_main_s"] is None
    assert doc["boot"]["phases"]["boot.import"]["n"] == 1


@pytest.mark.parametrize("environ, spawned, warm", [
    ({}, None, False),
    ({SPAWNED_NS_ENV: "12345", "AGENTAINER_WARM_BOOT": "1"}, 12345, True),
    ({SPAWNED_NS_ENV: "not a stamp"}, None, False),
])
def test_timeline_at_main_reads_the_stamp_once(environ, spawned, warm):
    t0, unix0 = time.perf_counter_ns() - 40_000_000, 12345 + 2_000_000_000
    boot = BootTimeline.at_main(t0, unix0, environ)
    time.sleep(0.01)
    boot.imported()
    boot.imported()  # closed once
    doc = boot.as_dict()
    assert doc["spawned_unix_ns"] == spawned and doc["warm_boot"] is warm
    assert doc["spawn_to_main_s"] == (None if spawned is None else 2.0)
    # boot.import counts from main's entry, before the recorder existed
    (name, span), = doc["phases"].items()
    assert name == "boot.import" and span["n"] == 1 and 0.05 <= span["self_s"] == span["total_s"] < 5.0
    assert doc["ready_s"] is None and doc["compile_cache_at_ready"] is None
    boot.first_dispatch(time.perf_counter_ns(), replayed=True)  # before ready: not a dispatch the engine took
    assert boot.as_dict()["first_dispatch_s"] is None
    boot.ready()
    boot.first_dispatch(time.perf_counter_ns(), replayed=False)
    boot.first_dispatch(time.perf_counter_ns() + 10**9, replayed=True)
    doc = boot.as_dict()
    assert doc["ready_s"] >= 0.05 and doc["first_dispatch_replayed"] is False and doc["first_dispatch_s"] < 1.0


def test_only_a_top_level_span_is_a_stage():
    boot, stats = BootTimeline(), CompileCacheStats()
    boot.compile_stats = stats
    with boot.span("boot.outer"):
        stats.on_event("/jax/core/compile/backend_compile_duration", 2.0, fun_name="jit(f)")
        with boot.span("boot.inner"):
            stats.on_event("/jax/compilation_cache/cache_misses")
            stats.on_event("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    with boot.span("boot.outer"):
        stats.on_event("/jax/core/compile/jaxpr_trace_duration", 1.0, fun_name="f")
    doc = boot.as_dict()
    assert doc["stages"] == {"boot.outer": {"jit_s": 3.0, "retrieval_s": 0.5, "cache_misses": 1}}
    assert set(doc["phases"]) == {"boot.outer", "boot.inner"} and doc["phases"]["boot.outer"]["n"] == 2


def test_span_since_counts_from_an_earlier_reading():
    spans = Spans()
    with spans.span_since("early", time.perf_counter_ns() - 30_000_000):
        with spans.span("child"):
            time.sleep(0.01)
    p = spans.snapshot()["phases"]
    assert 0.04 <= p["early"]["total_s"] < 5.0
    assert p["early"]["self_s"] == pytest.approx(p["early"]["total_s"] - p["child"]["total_s"], abs=1e-9)


# -- compile seconds by program ---------------------------------------------
def test_programs_name_a_jitted_function():
    stats = enable_compile_cache()  # one more listener pair on this process

    @jax.jit
    def boot_named_program(x):
        return jnp.tanh(x) * 5.0 - x.sum()

    boot_named_program(jnp.arange(9.0)).block_until_ready()
    row = stats.as_dict()["programs"]["boot_named_program"]
    assert set(row) == {"n", "trace_s", "lower_s", "compile_s"}
    assert row["n"] == 1 and row["trace_s"] > 0 and row["lower_s"] > 0 and row["compile_s"] > 0
    boot_named_program(jnp.arange(9.0)).block_until_ready()  # cached: nothing more
    assert stats.as_dict()["programs"]["boot_named_program"] == row
    # a function only traced inside another is no program
    assert "tanh" not in stats.as_dict()["programs"]


def test_programs_stay_bounded():
    stats = CompileCacheStats()
    for i in range(100):
        stats.on_event("/jax/core/compile/jaxpr_trace_duration", 0.001, fun_name=f"p{i}")
        stats.on_event("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.002, fun_name=f"jit(p{i})")
        stats.on_event("/jax/core/compile/backend_compile_duration", 1.0 + i, fun_name=f"jit(p{i})")
    stats.on_event("/jax/core/compile/jaxpr_trace_duration", 50.0, fun_name="callee")  # never lowered
    programs = stats.as_dict()["programs"]
    assert len(programs) == CompileCacheStats.PROGRAMS_KEPT + 1 == 33
    assert list(programs)[:2] == ["p99", "p98"] and "callee" not in programs
    assert programs["other"]["n"] == 68 and programs["other"]["compile_s"] == pytest.approx(sum(1.0 + i for i in range(68)))
    assert sum(r["compile_s"] for r in programs.values()) == pytest.approx(stats.compile_s)
    assert json.dumps(stats.as_dict())  # the document /metrics serves


@pytest.mark.parametrize("module", ["utils.spans", "utils.compile_cache", "utils.boot", "runtime.local"])
def test_importing_does_not_import_jax(module):
    code = f"import sys; import agentainer_tpu.{module}; print('jax' in sys.modules)"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=60, cwd=REPO)
    assert out.stdout.strip() == "False", out


# -- the benchmark's readers against the live document ------------------------
@pytest.fixture(scope="module")
def readers():
    bench = os.path.join(REPO, "benchmark")
    if bench not in sys.path:
        sys.path.append(bench)  # last: it shadows nothing
    return {name: importlib.import_module("layer_metrics." + name) for name in READERS}


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_live_document_and_nothing_without_boot(booted, readers, name):
    live = booted[2]
    value = readers[name].read([booted[0]], [live], [], None, {})
    assert isinstance(value, float) and value >= 0.0
    boot = live["boot"]
    if name == "engine_boot_s":
        assert value == pytest.approx(boot["spawn_to_main_s"] + boot["ready_s"])
    if name == "boot_cache_misses":
        assert value == boot["compile_cache_at_ready"]["misses"]
    without = {k: v for k, v in live.items() if k != "boot"}
    assert readers[name].read([without], [without], [], None, {}) is None
    assert value == readers[name].read([without], [without, live], [], None, {})  # a fleet: the engine that says


# -- the spawn stamp and the first dispatch, through the front door -------------
def test_a_spawned_engine_is_stamped_and_its_first_dispatch_classified(tmp_path):
    from agentainer_tpu.config import Config
    from agentainer_tpu.daemon import build_services
    from agentainer_tpu.runtime.local import LocalBackend
    from agentainer_tpu.store import MemoryStore

    cfg = Config()
    cfg.auth_token = "boot-token"
    auth = {"Authorization": "Bearer boot-token"}
    backend = LocalBackend(data_dir=str(tmp_path), ready_timeout_s=120.0)
    services = build_services(
        config=cfg, store=MemoryStore(), backend=backend, console_logs=False, data_dir=str(tmp_path)
    )

    async def loaded(http, aid):
        resp = await http.post(f"/agents/{aid}/start", headers=auth)
        assert resp.status == 200, await resp.text()
        doc = {}
        for _ in range(600):
            doc = await (await http.get(f"/agent/{aid}/metrics")).json()
            if doc.get("model_loaded") and doc["boot"]["ready_s"] is not None:
                return doc
            await asyncio.sleep(0.2)
        raise AssertionError(doc)

    async def body():
        http = TestClient(TestServer(services.app))
        await http.start_server()
        backend.set_control(f"http://127.0.0.1:{http.server.port}")
        try:
            # the daemon's own start, for operators
            health = (await (await http.get("/health")).json())["data"]
            assert set(health["boot"]) == {"listening_s", "data_plane_s"}
            assert 0.0 < health["boot"]["listening_s"] == pytest.approx(process_age_s(), abs=5.0)
            assert (await (await http.get("/health")).json())["data"]["boot"] == health["boot"]  # the first answer's

            resp = await http.post(
                "/agents",
                json={
                    "name": "boot-llm",
                    "model": {"engine": "llm", "config": "tiny",
                              "options": {"max_batch": 2, "max_seq": 128, "skip_warmup": True}},
                    "env": {"JAX_PLATFORMS": "cpu"},
                },
                headers=auth,
            )
            assert resp.status == 200, await resp.text()
            aid = (await resp.json())["data"]["id"]
            before_spawn = time.time_ns()
            boot = (await loaded(http, aid))["boot"]
            # the stamp rode the environment of the spawn, and main read it
            stamps = [h.env[SPAWNED_NS_ENV] for h in backend._hosts.values()]
            assert stamps == [str(boot["spawned_unix_ns"])]
            assert before_spawn <= boot["spawned_unix_ns"] <= boot["started_unix_ns"]
            assert 0.0 <= boot["spawn_to_main_s"] < 60.0 and boot["warm_boot"] is False
            assert boot["phases"]["boot.import"]["n"] == 2 and "boot.warmup" not in boot["phases"]
            assert boot["first_dispatch_s"] is None

            # through the front door the request is stamped: not a replay
            resp = await http.post(f"/agent/{aid}/chat", data=json.dumps({"message": "hello", "max_tokens": 4}))
            assert resp.status == 200, await resp.text()
            rid = resp.headers.get(REQUEST_ID_HEADER, "")
            doc = await (await http.get(f"/agent/{aid}/metrics")).json()
            assert doc["boot"]["first_dispatch_replayed"] is False and doc["boot"]["first_dispatch_s"] >= 0.0
            assert {k: v for k, v in doc["boot"].items() if not k.startswith("first_dispatch")} == {
                k: v for k, v in boot.items() if not k.startswith("first_dispatch")
            }

            # a new process boots through the same code; the first thing it takes is a manual replay
            resp = await http.post(f"/agents/{aid}/stop", headers=auth)
            assert resp.status == 200, await resp.text()
            again = (await loaded(http, aid))["boot"]
            assert again["spawned_unix_ns"] > boot["spawned_unix_ns"] and again["first_dispatch_s"] is None
            resp = await http.post(f"/agents/{aid}/requests/{rid}/replay", headers=auth)
            assert resp.status == 200, await resp.text()
            doc = await (await http.get(f"/agent/{aid}/metrics")).json()
            assert doc["boot"]["first_dispatch_replayed"] is True
        finally:
            await http.close()
            await asyncio.to_thread(backend.close)  # no engine_main process outlives the test

    asyncio.run(body())
    assert all(h.proc is None or h.proc.poll() is not None for h in backend._hosts.values())
