"""Native load generator (native/loadgen.cc) against the real stack: the
proxy bench's measurement tool must itself be trustworthy — keep-alive
reuse, Content-Length framing, latency accounting."""

import asyncio
import json
import subprocess

import pytest

from agentainer_tpu import native

from .test_e2e_local import AUTH, run, start_stack, teardown

LOADGEN = native.loadgen_path()


@pytest.mark.skipif(native.ensure_built() is not None, reason="native loadgen not buildable")
def test_loadgen_drives_proxy_e2e(tmp_path):
    async def body():
        services, client = await start_stack(tmp_path)
        try:
            resp = await client.post(
                "/agents", json={"name": "lg", "model": "echo"}, headers=AUTH
            )
            agent = (await resp.json())["data"]
            resp = await client.post(f"/agents/{agent['id']}/start", headers=AUTH)
            assert resp.status == 200, await resp.text()
            port = client.server.port
            path = f"/agent/{agent['id']}/chat"

            def drive():
                return subprocess.run(
                    [str(LOADGEN), "127.0.0.1", str(port), path, "200", "8"],
                    capture_output=True,
                    text=True,
                    timeout=120,
                )

            proc = await asyncio.to_thread(drive)
            assert proc.returncode == 0, proc.stderr
            stats = json.loads(proc.stdout.strip().splitlines()[-1])
            assert stats["n"] == 200
            assert stats["wall_s"] > 0
            assert 0 < stats["p50_ms"] <= stats["p99_ms"]
            # every request really went through the journaled proxy path
            jstats = services.journal.stats(agent["id"])
            assert jstats["completed"] >= 200
            assert jstats["failed"] == 0
        finally:
            await teardown(services, client)

    run(body())
