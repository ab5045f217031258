"""Native data-plane e2e: the C++ front door serving /agent/* + the engine
store socket, with the Python management plane behind it.

Drives the same signature flow as test_e2e_local but through real TCP
sockets into the C++ listener: journal-before-dispatch, 202-queue on a down
agent, crash → replay → conversation intact, management forwarding, and the
UDS binary store path the echo engine uses for its conversation writes.
"""

import asyncio
import json
import os

import aiohttp
import pytest

from tests.conftest import _native_available

pytestmark = pytest.mark.skipif(
    not _native_available(), reason="native library unavailable"
)

TOKEN = "dp-token"
AUTH = {"Authorization": f"Bearer {TOKEN}"}


async def start_stack(tmp_path):
    from agentainer_tpu.config import Config
    from agentainer_tpu.daemon import build_services, run_daemon
    from agentainer_tpu.runtime.local import LocalBackend

    cfg = Config()
    cfg.auth_token = TOKEN
    cfg.server.host = "127.0.0.1"
    cfg.server.port = 0  # ephemeral
    cfg.store_url = f"native://{tmp_path}/store.aof"
    backend = LocalBackend(data_dir=str(tmp_path), ready_timeout_s=30.0)
    services = build_services(
        config=cfg, backend=backend, console_logs=False, data_dir=str(tmp_path)
    )
    task = asyncio.create_task(run_daemon(services))
    for _ in range(200):
        if services.dataplane is not None:
            break
        await asyncio.sleep(0.05)
    assert services.dataplane is not None, "native data plane did not start"
    base = f"http://127.0.0.1:{services.dataplane.port}"
    session = aiohttp.ClientSession(base_url=base)
    return services, task, session


async def teardown(services, task, session):
    await session.close()
    task.cancel()
    try:
        await task
    except asyncio.CancelledError:
        pass


def test_native_proxy_end_to_end(tmp_path):
    async def body():
        services, task, session = await start_stack(tmp_path)
        try:
            # management path is forwarded C++ → aiohttp
            resp = await session.get("/health")
            assert resp.status == 200
            doc = await resp.json()
            assert doc["data"]["status"] == "healthy"

            resp = await session.post(
                "/agents", json={"name": "dp-echo", "model": "echo"}, headers=AUTH
            )
            assert resp.status == 200, await resp.text()
            agent = (await resp.json())["data"]
            aid = agent["id"]
            resp = await session.post(f"/agents/{aid}/start", headers=AUTH)
            assert resp.status == 200, await resp.text()

            # the native proxy path: journal → engine → settle; the echo
            # engine writes its conversation over the UDS store socket
            resp = await session.post(
                f"/agent/{aid}/chat", data=json.dumps({"message": "native hello"})
            )
            assert resp.status == 200, await resp.text()
            # span continuity from the C++ proxy: journal id in the response
            span = resp.headers.get("X-Agentainer-Request-ID", "")
            assert span
            doc = await resp.json()
            assert doc["response"] == "Echo: native hello"
            assert doc["conversation_length"] == 2

            # journal visible through the Python management API (the settle
            # is deferred to a background thread — allow it a beat)
            for _ in range(50):
                resp = await session.get(
                    f"/agents/{aid}/requests?status=completed", headers=AUTH
                )
                reqs = (await resp.json())["data"]
                if reqs["stats"]["completed"]:
                    break
                await asyncio.sleep(0.05)
            assert reqs["stats"]["completed"] == 1
            assert reqs["stats"]["pending"] == 0
            assert reqs["requests"][0]["id"] == span
            rec = reqs["requests"][0]
            assert rec["method"] == "POST"
            assert rec["path"] == "/chat"
            assert rec["response"]["status_code"] == 200

            # unknown agent → 404 envelope from C++
            resp = await session.post("/agent/agent-nope/chat", data=b"{}")
            assert resp.status == 404
            assert (await resp.json())["success"] is False
        finally:
            await teardown(services, task, session)

    asyncio.run(body())


def test_native_crash_queue_resume_replay(tmp_path):
    async def body():
        services, task, session = await start_stack(tmp_path)
        try:
            resp = await session.post(
                "/agents", json={"name": "dp-crash", "model": "echo"}, headers=AUTH
            )
            aid = (await resp.json())["data"]["id"]
            await session.post(f"/agents/{aid}/start", headers=AUTH)

            resp = await session.post(
                f"/agent/{aid}/chat", data=json.dumps({"message": "before"})
            )
            assert resp.status == 200

            # hard-kill the engine (a real crash)
            agent = services.manager.get_agent(aid)
            services.backend.kill_engine_hard(agent.engine_id)

            # until the reconciler notices, dispatch fails connection-level →
            # entry stays pending (crash heuristic); once status flips to
            # stopped the proxy answers 202 queued. Both leave the request
            # pending for replay.
            resp = await session.post(
                f"/agent/{aid}/chat", data=json.dumps({"message": "during"})
            )
            assert resp.status in (202, 502), await resp.text()

            # resume re-creates the engine; replay worker drains the queue
            resp = await session.post(f"/agents/{aid}/resume", headers=AUTH)
            assert resp.status == 200, await resp.text()
            deadline = asyncio.get_event_loop().time() + 15
            while asyncio.get_event_loop().time() < deadline:
                stats = services.journal.stats(aid)
                if stats["pending"] == 0 and stats["completed"] >= 2:
                    break
                await asyncio.sleep(0.2)
            stats = services.journal.stats(aid)
            assert stats["pending"] == 0, stats
            assert stats["failed"] == 0, stats

            # conversation survived: both turns present after the crash
            resp = await session.get(f"/agent/{aid}/history")
            contents = [t["content"] for t in (await resp.json())["history"]]
            assert "before" in contents and "during" in contents
        finally:
            await teardown(services, task, session)

    asyncio.run(body())


async def _raw_http(port: int, payload: bytes, timeout: float = 8.0) -> bytes:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(payload)
    await writer.drain()
    data = await asyncio.wait_for(reader.read(1 << 20), timeout)
    writer.close()
    return data


def test_head_chunked_and_connection_close(tmp_path):
    """HTTP edge cases the proxy must not regress vs the aiohttp front door:
    HEAD responses carry Content-Length but no body (must not stall waiting
    for one), chunked request bodies are decoded, and Connection: close is
    honored on the /agent/* branch (server actually closes)."""

    async def body():
        services, task, session = await start_stack(tmp_path)
        try:
            resp = await session.post(
                "/agents", json={"name": "dp-edge", "model": "echo"}, headers=AUTH
            )
            aid = (await resp.json())["data"]["id"]
            await session.post(f"/agents/{aid}/start", headers=AUTH)
            port = services.dataplane.port

            # HEAD through the management forward: must answer fast, no body
            t0 = asyncio.get_event_loop().time()
            raw = await _raw_http(
                port, b"HEAD /health HTTP/1.1\r\nHost: x\r\nConnection: close\r\n\r\n"
            )
            assert raw.startswith(b"HTTP/1.1 200"), raw[:80]
            assert asyncio.get_event_loop().time() - t0 < 5.0  # no 30s body stall

            # chunked request body through the proxy path
            chat = json.dumps({"message": "chunked hello"}).encode()
            chunked = (
                b"POST /agent/" + aid.encode() + b"/chat HTTP/1.1\r\n"
                b"Host: x\r\nTransfer-Encoding: chunked\r\nConnection: close\r\n\r\n"
                + hex(len(chat))[2:].encode() + b"\r\n" + chat + b"\r\n0\r\n\r\n"
            )
            raw = await _raw_http(port, chunked)
            assert raw.startswith(b"HTTP/1.1 200"), raw[:200]
            assert b"Echo: chunked hello" in raw

            # Connection: close on /agent/*: response arrives AND peer closes
            # (read(1<<20) only returns on EOF — a pinned connection times out)
            raw = await _raw_http(
                port,
                b"GET /agent/" + aid.encode() + b"/health HTTP/1.1\r\n"
                b"Host: x\r\nConnection: close\r\n\r\n",
            )
            assert raw.startswith(b"HTTP/1.1 200"), raw[:80]
            assert b"Connection: close" in raw

            # malformed chunk-size line must fail the request, not silently
            # truncate the body into a smuggled follow-up request
            bad = (
                b"POST /agent/" + aid.encode() + b"/chat HTTP/1.1\r\n"
                b"Host: x\r\nTransfer-Encoding: chunked\r\n\r\n"
                b"zz\r\n" + b"GET /agent/x HTTP/1.1\r\n\r\n"
            )
            raw = await _raw_http(port, bad)
            assert not raw.startswith(b"HTTP/1.1 200"), raw[:80]

            # absurd chunk size is rejected instead of buffering terabytes
            huge = (
                b"POST /agent/" + aid.encode() + b"/chat HTTP/1.1\r\n"
                b"Host: x\r\nTransfer-Encoding: chunked\r\n\r\n"
                b"7fffffffffff\r\n"
            )
            raw = await _raw_http(port, huge)
            assert not raw.startswith(b"HTTP/1.1 200"), raw[:80]
        finally:
            await teardown(services, task, session)

    asyncio.run(body())


def test_uds_takes_a_blob_over_its_frame_cap_in_parts(tmp_path):
    """The store socket closes a connection on a frame over 64 MiB; a long
    session's snapshot is hundreds of MB. ``StoreClient.set_bytes`` sends such
    a blob as parts of 32 MiB and a manifest, raw bytes on the socket, and
    ``get_bytes`` reads it back whole; one frame over the cap still resets."""

    async def body():
        services, task, session = await start_stack(tmp_path)
        try:
            resp = await session.post("/agents", json={"name": "dp-blob", "model": "echo"}, headers=AUTH)
            aid = (await resp.json())["data"]["id"]
            await session.post(f"/agents/{aid}/start", headers=AUTH)

            from agentainer_tpu.runtime import store_client
            from agentainer_tpu.store.schema import Keys

            token = services.store.get(Keys.internal_token(aid))
            token = token.decode() if isinstance(token, bytes) else token
            client = store_client.StoreClient(store_sock=services.backend.store_sock, agent_id=aid, token=token, retries=0)
            try:
                key = f"agent:{aid}:kv:long"
                blob = os.urandom(1 << 20) * 70 + b"tail"  # 70 MiB: three parts
                await client.set_bytes(key, blob, ttl=60)
                assert await client.get_bytes(key) == blob
                parts = sorted(k for k in await client.keys(key + "*") if k != key)
                assert len(parts) == 3 and all(":part:" in k for k in parts)
                raw = services.store.get(key)
                assert raw.startswith(b"ATPU-PARTS1 ") and raw.endswith(f" 3 {len(blob)}".encode())
                await client.set_bytes(key, blob[:1000], ttl=60)  # a short one again: one value, no base64 on the way
                assert services.store.get(key) == blob[:1000] and await client.get_bytes(key) == blob[:1000]
                with pytest.raises((ConnectionError, asyncio.IncompleteReadError, OSError)):
                    await client._set_blob(key, blob, 60)  # one frame over the cap: the peer hangs up
            finally:
                await client.close()
        finally:
            await teardown(services, task, session)

    asyncio.run(body())


def test_uds_pipeline_namespace_is_atomic(tmp_path):
    """A pipeline containing one out-of-namespace key is rejected as a whole
    before anything executes — parity with the HTTP /internal/store 403."""

    async def body():
        services, task, session = await start_stack(tmp_path)
        try:
            resp = await session.post(
                "/agents", json={"name": "dp-ns", "model": "echo"}, headers=AUTH
            )
            aid = (await resp.json())["data"]["id"]
            await session.post(f"/agents/{aid}/start", headers=AUTH)

            from agentainer_tpu.runtime.store_client import StoreClient
            from agentainer_tpu.store.schema import Keys

            engine_token = services.store.get(Keys.internal_token(aid))
            assert engine_token, "engine credential missing"
            if isinstance(engine_token, bytes):
                engine_token = engine_token.decode()
            assert services.backend.store_sock, "UDS store socket not wired"
            client = StoreClient(
                store_sock=services.backend.store_sock,
                agent_id=aid,
                token=engine_token,
            )
            try:
                with pytest.raises(RuntimeError, match="namespace"):
                    await client.pipeline(
                        [
                            {"op": "set", "key": f"agent:{aid}:mine", "value": "1"},
                            {"op": "set", "key": "agent:other:theirs", "value": "2"},
                            {"op": "rpush", "key": f"agent:{aid}:lst", "values": ["x"]},
                        ]
                    )
                # nothing applied — not even the in-namespace prefix
                assert services.store.get(f"agent:{aid}:mine") is None
                assert services.store.get("agent:other:theirs") is None
                assert services.store.lrange(f"agent:{aid}:lst", 0, -1) == []
                # a fully in-namespace batch still works
                res = await client.pipeline(
                    [{"op": "set", "key": f"agent:{aid}:ok", "value": "9"}]
                )
                assert len(res) == 1
                ok = services.store.get(f"agent:{aid}:ok")
                assert (ok.decode() if isinstance(ok, bytes) else ok) == "9"
            finally:
                await client.close()
        finally:
            await teardown(services, task, session)

    asyncio.run(body())


def test_agent_records_survive_daemon_restart(tmp_path):
    """The durability tier the reference gets from Redis: stop the daemon,
    start a new one over the same AOF, agent records + journal remain."""

    async def body():
        services, task, session = await start_stack(tmp_path)
        aid = None
        try:
            resp = await session.post(
                "/agents", json={"name": "survivor", "model": "echo"}, headers=AUTH
            )
            aid = (await resp.json())["data"]["id"]
            await session.post(f"/agents/{aid}/start", headers=AUTH)
            await session.post(f"/agent/{aid}/chat", data=json.dumps({"message": "hi"}))
        finally:
            await teardown(services, task, session)
            services.backend.close()
            services.store.close()

        # second daemon over the same data dir
        services2, task2, session2 = await start_stack(tmp_path)
        try:
            resp = await session2.get("/agents", headers=AUTH)
            agents = (await resp.json())["data"]
            assert [a["id"] for a in agents] == [aid]
            # journal survived too
            resp = await session2.get(
                f"/agents/{aid}/requests?status=completed", headers=AUTH
            )
            assert (await resp.json())["data"]["stats"]["completed"] == 1
        finally:
            await teardown(services2, task2, session2)

    asyncio.run(body())


async def _slow_engine(delay_s: float, seen: list):
    """An upstream that holds its socket open and answers ``delay_s`` after
    it has read a request: a healthy engine in a long generation."""

    async def handle(reader, writer):
        try:
            while True:
                head = await reader.readuntil(b"\r\n\r\n")
                length = int(
                    [ln.split(b":")[1] for ln in head.split(b"\r\n") if ln.lower().startswith(b"content-length")][0]
                )
                await reader.readexactly(length)
                seen.append(head)
                await asyncio.sleep(delay_s)
                body = b'{"response": "done"}'
                writer.write(
                    b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\nContent-Length: "
                    + str(len(body)).encode() + b"\r\n\r\n" + body
                )
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            writer.close()

    server = await asyncio.start_server(handle, "127.0.0.1", 0)
    return server, server.sockets[0].getsockname()[1]


@pytest.mark.parametrize(
    "deadline_ms, delay_s, status",
    [(4000, 1.2, 200), (300, 3.0, 504), (None, 1.2, 200)],
    ids=["answered_inside_its_deadline", "deadline_runs_out", "no_deadline"],
)
def test_dispatch_waits_as_long_as_the_request_says(tmp_path, deadline_ms, delay_s, status):
    """The wait for the engine's answer follows the request's deadline, not a
    fixed 30 s; a wait that runs out is a failed dispatch (504, one retry
    charged), never read as a stale keepalive: the request reaches the engine
    once and is not reported as "agent unreachable"."""

    async def body():
        services, task, session = await start_stack(tmp_path)
        seen: list = []
        server, port = await _slow_engine(delay_s, seen)
        try:
            services.dataplane.route_set("agent-slow", f"http://127.0.0.1:{port}", "running", True)
            headers = {"X-Agentainer-Deadline-Ms": str(deadline_ms)} if deadline_ms else {}
            # twice: the second dispatch reuses the kept-alive upstream socket
            for _ in range(2):
                t0 = asyncio.get_event_loop().time()
                resp = await session.post("/agent/agent-slow/chat", data=b'{"message": "write"}', headers=headers)
                took = asyncio.get_event_loop().time() - t0
                doc = await resp.json()
                assert resp.status == status, doc
                if status == 200:
                    assert doc == {"response": "done"} and took >= delay_s
                else:
                    assert "retry recorded" in doc["message"] and 0.3 <= took < delay_s
            assert len(seen) == 2  # no request was sent twice
        finally:
            server.close()
            await teardown(services, task, session)

    asyncio.run(body())


def test_management_forward_waits_long_only_for_a_profile(tmp_path):
    """The management backend answers at once or is broken, so its forward
    waits 30 s — but for ``POST /agents/{id}/profile``, which answers after
    the capture and the collection of its trace: that one is waited for like
    a dispatch. A backend that takes 32 s: the profile call gets its answer,
    any other call its 502 at 30 s (both at once, each on a connection of
    its own, so the test takes the longer of the two)."""
    from agentainer_tpu.runtime.dataplane import NativeDataPlane
    from agentainer_tpu.store.native import NativeStore

    async def body():
        seen: list = []
        server, port = await _slow_engine(32.0, seen)
        store = NativeStore(str(tmp_path / "store.aof"))
        dp = NativeDataPlane(store, "127.0.0.1", 0, "127.0.0.1", port)
        loop = asyncio.get_event_loop()

        async def call(path):
            t0 = loop.time()
            async with aiohttp.ClientSession(base_url=f"http://127.0.0.1:{dp.port}") as session:
                resp = await session.post(path, data=b"{}", headers=AUTH)
                return resp.status, await resp.json(), loop.time() - t0

        try:
            profile, other = await asyncio.gather(
                call("/agents/agent-x/profile?why=trace"), call("/agents/agent-x/start")
            )
            assert profile[0] == 200 and profile[1] == {"response": "done"}, profile
            assert profile[2] >= 32.0
            assert other[0] == 502 and "management backend unavailable" in other[1]["message"], other
            assert 29.0 <= other[2] < 32.0
            assert len(seen) == 2  # neither was sent twice
        finally:
            server.close()
            await loop.run_in_executor(None, dp.stop)
            store.close()

    asyncio.run(body())
