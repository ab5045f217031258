"""The second agent personality: `assistant` engine flavor — persona'd,
history-flattened prompting (reference examples/gemini-agent/app.py:87-113
builds one prompt string from the last exchanges; gpt-agent threads
structured messages). Also covers the OPEN engine registry
(VERDICT r2 weak #8: known_engines() was a closed set).
"""

import asyncio
import json

from aiohttp.test_utils import TestClient, TestServer

from agentainer_tpu.config import Config
from agentainer_tpu.daemon import build_services
from agentainer_tpu.engine import engine_registry, known_engines, register_engine
from agentainer_tpu.runtime.local import LocalBackend
from agentainer_tpu.store import MemoryStore

TOKEN = "assistant-token"
AUTH = {"Authorization": f"Bearer {TOKEN}"}


def test_registry_is_open(monkeypatch):
    assert {"echo", "llm", "assistant"} <= known_engines()
    register_engine("custom", "my.pkg.engine")
    assert "custom" in known_engines()
    assert engine_registry()["custom"] == "my.pkg.engine"
    monkeypatch.setenv("ATPU_EXTRA_ENGINES", "envone:pkg.mod, envtwo:pkg.other")
    assert {"envone", "envtwo"} <= known_engines()
    from agentainer_tpu.engine import _EXTRA

    _EXTRA.pop("custom", None)


def test_assistant_persona_end_to_end(tmp_path):
    async def body():
        cfg = Config()
        cfg.auth_token = TOKEN
        backend = LocalBackend(data_dir=str(tmp_path), ready_timeout_s=120.0)
        services = build_services(
            config=cfg,
            store=MemoryStore(),
            backend=backend,
            console_logs=False,
            data_dir=str(tmp_path),
        )
        client = TestClient(TestServer(services.app))
        await client.start_server()
        backend.set_control(f"http://127.0.0.1:{client.server.port}")
        try:
            resp = await client.post(
                "/agents",
                json={
                    "name": "sage",
                    "model": {
                        "engine": "assistant",
                        "config": "tiny",
                        "options": {
                            "max_batch": 2,
                            "max_seq": 256,
                            "system_prompt": "You are Sage.",
                            "history_turns": 2,
                        },
                    },
                    "env": {"JAX_PLATFORMS": "cpu"},
                },
                headers=AUTH,
            )
            assert resp.status == 200, await resp.text()
            agent = (await resp.json())["data"]
            assert agent["model"]["engine"] == "assistant"
            resp = await client.post(f"/agents/{agent['id']}/start", headers=AUTH)
            assert resp.status == 200, await resp.text()

            for _ in range(300):
                resp = await client.get(f"/agent/{agent['id']}/metrics")
                doc = await resp.json()
                if doc.get("model_loaded"):
                    break
                await asyncio.sleep(0.2)
            assert doc.get("model_loaded"), doc

            # turn 1: persona surfaces in the response doc
            resp = await client.post(
                f"/agent/{agent['id']}/chat",
                data=json.dumps({"message": "hello there", "max_tokens": 6}),
            )
            assert resp.status == 200, await resp.text()
            doc = await resp.json()
            assert doc["persona"] == "You are Sage."
            assert doc["usage"]["completion_tokens"] == 6
            # flattened prompting: the prompt contains persona + history
            # scaffold, so prompt_tokens far exceed the bare message
            assert doc["usage"]["prompt_tokens"] > len("hello there") + 10

            # turn 2: history flattened in → prompt longer than turn 1's
            resp = await client.post(
                f"/agent/{agent['id']}/chat",
                data=json.dumps({"message": "again", "max_tokens": 4}),
            )
            doc2 = await resp.json()
            assert doc2["usage"]["prompt_tokens"] > doc["usage"]["prompt_tokens"]

            # history durable like any agent
            resp = await client.get(f"/agent/{agent['id']}/history")
            contents = [t["content"] for t in (await resp.json())["history"]]
            assert "hello there" in contents and "again" in contents
        finally:
            backend.close()
            await client.close()

    asyncio.run(body())
