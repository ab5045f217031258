"""Chaos soak: a live daemon + real engine subprocesses driven through a
SEEDED fault schedule, asserting the resilience invariants end to end.

The five mechanisms behind the durability guarantee (journal, replay,
health, reconciler, deadline plane — docs/RESILIENCE.md) are each unit-
tested, but control-plane/data-plane reliability splits break down where
they *cooperate* under failure. This harness runs the real stack —
control plane, proxy, journal, replay worker, restart watcher, engine
subprocesses — through deterministic fault phases:

  engine_sigkill    SIGKILL the echo engine mid-traffic (watcher respawn,
                    crash heuristic, replay drain)
  store_blip        seeded-probability store.get/set failpoints (breaker,
                    serve-through degradation, loop survival)
  slow_dispatch     proxy.dispatch delay failpoint (latency, not loss)
  poisoned_prefill  engine.prefill failpoint inside a real LLM engine
                    subprocess: the typed poison signal dead-letters the
                    failing request after two fast strikes (reason
                    recorded, requeue-able) while the engine survives and
                    keeps serving the healthy traffic behind it
  llm_sigkill       SIGKILL the LLM host process, then token-identical
                    session resume from the KV snapshot
  fused_inject      SIGKILL a fused+in-loop-spec engine while a second
                    session's lane is STAGED into the running loop and the
                    loop carries unverified device drafts: both journaled
                    turns settle token-identical on the respawned engine
  replica_failover  2-replica LLM fleet: SIGKILL the replica serving a
                    session MID-DECODE; the journaled turn settles on the
                    SURVIVOR with a token-identical continuation (restored
                    from the store-durable snapshot), and the next live
                    turn matches the control session bit for bit
  stream_kill       SIGKILL the replica serving a live SSE stream
                    mid-decode: the client's single connection sees one
                    gapless, duplicate-free offset sequence bit-for-bit
                    equal to the undisturbed control — the proxy splices
                    the survivor's stream at last_acked_offset + 1
  lease_flap        replica.lease failpoint starves heartbeat refreshes on
                    a healthy 2-replica echo fleet: replicas flap SUSPECT
                    (excluded from routing) and return ALIVE when the
                    budget is spent — service never degrades below 200s
  route_dead        router.pick failpoint returns stale (dead) replica
                    choices while one echo replica is down: the bounded
                    retry-on-next-replica absorbs every stale pick
  torn_aof          truncate the native store's AOF mid-record; reopen
                    recovers every complete record and keeps appending

Invariants asserted (exit nonzero on violation):

  * no acked request lost — every 202-acked id settles COMPLETED, every
    200 was delivered synchronously;
  * no double execution — no chat message appears twice in the agent's
    recorded history, acked ones appear exactly once;
  * journal pending converges to 0 for every agent;
  * sessions resume token-identical after an engine SIGKILL;
  * per-fault-class recovery time (MTTR) is recorded.

Deterministic: the schedule, failpoint probabilities, and traffic are all
derived from ATPU_CHAOS_SEED (default 1337). ATPU_CHAOS_SMOKE=1 shortens
traffic volumes (make chaos). Emits one JSON line; the committed artifact
is BENCH_chaos.json.

Usage: JAX_PLATFORMS=cpu python scripts/chaos_soak.py
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from agentainer_tpu import faults  # noqa: E402
from agentainer_tpu.config import Config  # noqa: E402
from agentainer_tpu.daemon import (  # noqa: E402
    build_services,
    start_background,
    stop_background,
)
from agentainer_tpu.runtime.local import LocalBackend  # noqa: E402
from agentainer_tpu.store import MemoryStore  # noqa: E402

SEED = int(os.environ.get("ATPU_CHAOS_SEED", "1337"))
SMOKE = os.environ.get("ATPU_CHAOS_SMOKE", "") not in ("", "0", "false")
TOKEN = "chaos-token"
AUTH = {"Authorization": f"Bearer {TOKEN}"}
RECOVERY_CAP_S = 90.0


class Soak:
    def __init__(self, tmpdir: str):
        self.tmpdir = tmpdir
        self.services = None
        self.client = None
        self.seq = 0
        # message -> ack kind ("sync" 200 | "queued" 202 rid | "refused")
        self.acks: dict[str, dict] = {}
        self.mttr: dict[str, float] = {}
        self.counts = {"sent": 0, "ok": 0, "queued": 0, "refused": 0, "error5xx": 0}
        self.violations: list[str] = []

    # -- stack lifecycle --------------------------------------------------
    async def start(self) -> None:
        from aiohttp.test_utils import TestClient, TestServer

        cfg = Config()
        cfg.auth_token = TOKEN
        # tight cadences so the soak observes recovery, not scan timers
        cfg.cadences.replay_scan_s = 1.0
        cfg.cadences.state_sync_s = 2.0
        cfg.cadences.metrics_interval_s = 5.0
        cfg.resilience.restart_backoff_base_s = 0.2
        cfg.resilience.breaker_cooldown_s = 0.5
        # fleet: tight lease windows so replica death detection is observed
        # within the soak's budget, not the production 3s/6s defaults.
        # fleet.replicas stays 1 — only the explicitly-pinned fleet agents
        # run multi-replica, every other agent is the pre-fleet baseline.
        cfg.fleet.lease_interval_s = 0.25
        cfg.fleet.suspect_after_s = 1.0
        cfg.fleet.dead_after_s = 2.0
        # SSE token streaming through the proxy: the stream_kill phase
        # asserts the mid-stream failover splice end to end
        cfg.features.streaming = True
        os.environ["ATPU_JITTER_SEED"] = str(SEED)
        backend = LocalBackend(
            data_dir=self.tmpdir,
            ready_timeout_s=90.0,
            restart_backoff_base_s=cfg.resilience.restart_backoff_base_s,
            restart_backoff_max_s=2.0,
            restart_window_s=cfg.resilience.restart_window_s,
            restart_max_rapid=cfg.resilience.restart_max_rapid,
        )
        self.services = build_services(
            config=cfg,
            store=MemoryStore(),
            backend=backend,
            console_logs=False,
            data_dir=self.tmpdir,
        )
        self.client = TestClient(TestServer(self.services.app))
        await self.client.start_server()
        backend.set_control(f"http://127.0.0.1:{self.client.server.port}", TOKEN)
        await start_background(self.services)

    async def stop(self) -> None:
        faults.disarm_all()
        if self.services is not None:
            await stop_background(self.services)
            self.services.backend.close()
        if self.client is not None:
            await self.client.close()

    async def deploy(
        self, name: str, model, auto_restart: bool = True, env=None, replicas: int = 0
    ) -> str:
        resp = await self.client.post(
            "/agents",
            json={
                "name": name,
                "model": model,
                "auto_restart": auto_restart,
                "env": env or {},
                "replicas": replicas,
            },
            headers=AUTH,
        )
        doc = await resp.json()
        assert resp.status == 200, doc
        agent_id = doc["data"]["id"]
        resp = await self.client.post(f"/agents/{agent_id}/start", headers=AUTH)
        assert resp.status == 200, await resp.text()
        return agent_id

    # -- traffic ----------------------------------------------------------
    async def chat(self, agent_id: str, track: bool = True, session: str | None = None):
        """One proxied chat with a unique message; records the ack kind."""
        self.seq += 1
        msg = f"chaos-{SEED}-{self.seq}"
        body = {"message": msg}
        if session is not None:
            body["session"] = session
        resp = await self.client.post(
            f"/agent/{agent_id}/chat", data=json.dumps(body)
        )
        raw = await resp.read()
        self.counts["sent"] += 1
        rec = {"status": resp.status, "agent_id": agent_id, "rid": ""}
        if resp.status == 200:
            self.counts["ok"] += 1
            rec["kind"] = "sync"
        elif resp.status == 202:
            self.counts["queued"] += 1
            rec["kind"] = "queued"
            try:
                rec["rid"] = json.loads(raw)["data"]["request_id"]
            except Exception:
                pass
        elif resp.status >= 500 or resp.status == 429:
            self.counts["refused"] += 1
            if resp.status >= 500:
                self.counts["error5xx"] += 1
            rec["kind"] = "refused"
        if track:
            self.acks[msg] = rec
        return resp.status, msg

    async def probe_until_ok(self, agent_id: str, label: str) -> float:
        """MTTR probe: wall time until the agent serves a 200 again."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < RECOVERY_CAP_S:
            status, _ = await self.chat(agent_id, track=True)
            if status == 200:
                mttr = time.monotonic() - t0
                self.mttr[label] = round(mttr, 3)
                return mttr
            await asyncio.sleep(0.2)
        self.violations.append(f"{label}: no recovery within {RECOVERY_CAP_S}s")
        self.mttr[label] = -1.0
        return -1.0

    async def drain_pending(self, agent_id: str, cap_s: float = 45.0) -> bool:
        """Wait for the replay worker to drain the agent's queue to 0."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < cap_s:
            stats = self.services.journal.stats(agent_id)
            if stats["pending"] == 0:
                return True
            await asyncio.sleep(0.25)
        return False

    # -- phases -----------------------------------------------------------
    async def phase_baseline(self, echo_id: str, n: int) -> None:
        for _ in range(n):
            status, msg = await self.chat(echo_id)
            if status != 200:
                self.violations.append(f"baseline: {msg} got {status}")

    async def phase_engine_sigkill(self, echo_id: str) -> None:
        engine_id = self.services.manager.get_agent(echo_id).engine_id
        self.services.backend.kill_engine_hard(engine_id)
        # fire into the dead window: these ack 502 (left pending) or 202
        for _ in range(3):
            await self.chat(echo_id)
            await asyncio.sleep(0.05)
        await self.probe_until_ok(echo_id, "engine_sigkill")

    async def phase_store_blip(self, echo_id: str, n: int) -> None:
        # seeded 50% store read/write failures, budget-bounded so the blip
        # ENDS deterministically even under the background loops' traffic
        faults.arm("store.get", error="ConnectionError", probability=0.5, seed=SEED, count=60)
        faults.arm("store.set", error="ConnectionError", probability=0.5, seed=SEED + 1, count=40)
        t0 = time.monotonic()
        for _ in range(n):
            await self.chat(echo_id)
            await asyncio.sleep(0.05)
        # burn any remaining budget through the store, then disarm
        while any(fp["count"] != 0 for fp in faults.active()):
            try:
                self.services.store.get("chaos:burn")
                self.services.store.set("chaos:burn", "x")
            except ConnectionError:
                pass
            await asyncio.sleep(0)  # the background loops keep breathing
            if time.monotonic() - t0 > 30:
                break
        faults.disarm_all()
        await self.probe_until_ok(echo_id, "store_blip")

    async def phase_slow_dispatch(self, echo_id: str, n: int) -> None:
        faults.arm("proxy.dispatch", error="none", delay_ms=250, count=n)
        t0 = time.monotonic()
        for _ in range(n):
            status, msg = await self.chat(echo_id)
            if status != 200:
                self.violations.append(f"slow_dispatch: {msg} got {status}")
        faults.disarm_all()
        self.mttr["slow_dispatch"] = round((time.monotonic() - t0) / max(1, n), 3)

    async def phase_poisoned_prefill(self, poison_id: str) -> bool:
        """One deterministically failing request (engine.prefill armed with
        count=2) on a HEALTHY engine. Repair-path contract: the engine's
        typed poison signal (PREFILL_POISON_HEADER on the 500) charges the
        tightened poison budget instead of archiving the 500 or walking the
        full retry ladder — the entry dead-letters in seconds with the
        reason recorded, stays requeue-able, and the engine serves the
        traffic behind it throughout. MTTR here is first-5xx → dead-letter:
        the repair decision latency, not the model-load wall clock the old
        probe conflated it with."""
        agent = self.services.manager.get_agent(poison_id)
        t_warm = time.monotonic()
        while time.monotonic() - t_warm < RECOVERY_CAP_S:
            stats = self.services.backend.stats(agent.engine_id) or {}
            if stats.get("model_loaded"):
                break
            await asyncio.sleep(0.5)
        else:
            self.violations.append("poisoned_prefill: engine never loaded")
            self.mttr["poisoned_prefill"] = -1.0
            return False
        resp = await self.client.post(
            f"/agent/{poison_id}/chat",
            data=json.dumps({"message": f"poison-{SEED}"}),
        )
        await resp.read()
        t0 = time.monotonic()
        rid = resp.headers.get("X-Agentainer-Request-ID", "")
        if resp.status < 500 or not rid:
            self.violations.append(
                f"poisoned_prefill: failpoint never fired (got {resp.status})"
            )
            self.mttr["poisoned_prefill"] = -1.0
            return False
        # strike 1 was the live dispatch; the next replay tick is strike 2
        req = None
        while time.monotonic() - t0 < RECOVERY_CAP_S:
            req = self.services.journal.get(poison_id, rid)
            if req is not None and req.status == "failed":
                break
            await asyncio.sleep(0.05)
        if req is None or req.status != "failed":
            self.violations.append(
                "poisoned_prefill: entry never dead-lettered "
                f"({None if req is None else req.status})"
            )
            self.mttr["poisoned_prefill"] = -1.0
            return False
        self.mttr["poisoned_prefill"] = round(time.monotonic() - t0, 3)
        ok = True
        if "poisoned prefill" not in (req.error or ""):
            self.violations.append(
                f"poisoned_prefill: reason not recorded ({req.error!r})"
            )
            ok = False
        # the dead letter is an operator artifact: requeue must revive it,
        # and with the failpoint's count=2 consumed it now completes
        if self.services.journal.requeue(poison_id, rid) is None:
            self.violations.append("poisoned_prefill: dead letter not requeue-able")
            ok = False
        else:
            t_rq = time.monotonic()
            while time.monotonic() - t_rq < RECOVERY_CAP_S:
                req = self.services.journal.get(poison_id, rid)
                if req is not None and req.status == "completed":
                    break
                await asyncio.sleep(0.25)
            if req is None or req.status != "completed":
                self.violations.append(
                    "poisoned_prefill: requeued entry never completed "
                    f"({None if req is None else req.status})"
                )
                ok = False
        # the engine was healthy the whole time: live traffic still serves
        status, _ = await self.chat(poison_id, track=False)
        if status != 200:
            self.violations.append(
                f"poisoned_prefill: healthy traffic got {status} after dead-letter"
            )
            ok = False
        return ok

    async def phase_page_exhaustion(self, paged_id: str) -> bool:
        """Paged-KV backpressure invariant: the paged agent runs a tiny
        page pool AND its engine armed engine.page_alloc (count=1) from its
        env, so both injected and ORGANIC pool exhaustion fire during this
        phase. Every exhaustion must surface as 429/202 backpressure —
        journal entries stay replayable (no acked loss, settled like any
        other phase's traffic) — never a 5xx crash; the engine serves on
        and its metrics count the exhaustions."""
        # the paged engine may still be LOADING (five tiny-LLM hosts boot
        # concurrently in this soak): wait until the model is loaded before
        # asserting on backpressure — a 502 during model load is the
        # loading contract, not a pool-exhaustion crash. Readiness is read
        # from /metrics, NOT by serving probe chats: a probe would burn the
        # armed engine.page_alloc fire budget before the phase's own
        # traffic gets to observe the injected exhaustion.
        agent = self.services.manager.get_agent(paged_id)
        t_warm = time.monotonic()
        while time.monotonic() - t_warm < 60.0:
            stats = self.services.backend.stats(agent.engine_id) or {}
            if stats.get("model_loaded"):
                break
            await asyncio.sleep(0.5)
        else:
            self.violations.append("page_exhaustion: paged engine never loaded")
            return False
        saw_backpressure = False
        for i in range(6):
            # distinct sessions grow the pool toward organic exhaustion;
            # the armed failpoint covers the deterministic half
            status, msg = await self.chat(paged_id, session=f"pool-{i}")
            if status >= 500:
                self.violations.append(
                    f"page_exhaustion: {msg} got {status} (crash, not backpressure)"
                )
            if status in (202, 429):
                saw_backpressure = True
            await asyncio.sleep(0.1)
        # the engine must still be serving (fresh small session)
        await self.probe_until_ok(paged_id, "page_exhaustion")
        # engine-side accounting: the exhaustions were counted, not hidden
        agent = self.services.manager.get_agent(paged_id)
        stats = self.services.backend.stats(agent.engine_id) or {}
        exhausted = int(stats.get("page_exhausted_total", 0) or 0)
        if stats.get("paged_kv") is not True:
            self.violations.append("page_exhaustion: agent is not serving paged KV")
        if exhausted < 1:
            self.violations.append(
                "page_exhaustion: no exhaustion counted (failpoint not wired?)"
            )
        self.counts["page_exhausted"] = exhausted
        return saw_backpressure and exhausted >= 1

    async def phase_llm_resume(self, llm_id: str) -> bool:
        """Token-identical resume: control session runs turn1+turn2 clean;
        victim session runs turn1, the engine is SIGKILLed, and after the
        watcher respawns it the victim's turn2 (restored from the KV
        snapshot) must match the control's turn2 bit for bit."""

        async def turn(session: str, message: str) -> tuple[int, str]:
            resp = await self.client.post(
                f"/agent/{llm_id}/chat",
                data=json.dumps(
                    {"message": message, "session": session, "max_tokens": 12}
                ),
            )
            doc = await resp.json()
            return resp.status, doc.get("response", "")

        status, _ = await turn("ctl", "alpha alpha alpha")
        assert status == 200, f"llm ctl turn1 got {status}"
        status, ctl_t2 = await turn("ctl", "beta beta")
        assert status == 200, f"llm ctl turn2 got {status}"
        status, _ = await turn("vic", "alpha alpha alpha")
        assert status == 200, f"llm vic turn1 got {status}"
        # The resume guarantee is conditional on a snapshot EXISTING: the
        # engine's limiter defers stagings (durability floor 30 s from the
        # session's first attempt). Wait for the victim's snapshot to land
        # durably — never landing inside the floor is itself a violation.
        kv_key = f"agent:{llm_id}:kvcache:vic"
        t_snap = time.monotonic()
        while self.services.store.get(kv_key) is None:
            if time.monotonic() - t_snap > 45.0:
                self.violations.append(
                    "llm resume: KV snapshot never landed within the "
                    "durability floor"
                )
                return False
            await asyncio.sleep(0.25)

        engine_id = self.services.manager.get_agent(llm_id).engine_id
        self.services.backend.kill_engine_hard(engine_id)
        # recovery probes use a THROWAWAY session: a probe that 502s leaves
        # a pending journal entry that later REPLAYS — pointed at the
        # victim session it would append extra turns and desync the
        # context the token-identical comparison depends on
        t0 = time.monotonic()
        recovered = False
        while time.monotonic() - t0 < RECOVERY_CAP_S:
            status, _ = await turn("probe-resume", "ping")
            if status == 200:
                recovered = True
                break
            await asyncio.sleep(0.5)
        self.mttr["llm_sigkill"] = round(time.monotonic() - t0, 3) if recovered else -1.0
        if not recovered:
            self.violations.append("llm_sigkill: engine never served again")
            return False
        status, vic_t2 = await turn("vic", "beta beta")
        if status != 200:
            self.violations.append(f"llm resume: vic turn2 got {status}")
            return False
        if vic_t2 != ctl_t2:
            self.violations.append(
                f"token-identical resume violated: {vic_t2!r} != {ctl_t2!r}"
            )
            return False
        return True

    async def phase_park_kill(self, tiered_id: str) -> bool:
        """SIGKILL with a session PARKED in the tiered-KV hierarchy: the
        victim is demoted off-device (host tier + cold store blob) before
        the kill, so the respawned engine has never held its pages — the
        next turn must resume token-identically from the cold tier alone.
        Pins that parking loses nothing a snapshot wouldn't: the cold
        blob is packed from the exact staged arrays BEFORE any int8
        host-tier quantization."""

        async def turn(session: str, message: str) -> tuple[int, str]:
            resp = await self.client.post(
                f"/agent/{tiered_id}/chat",
                data=json.dumps(
                    {"message": message, "session": session, "max_tokens": 12}
                ),
            )
            doc = await resp.json()
            return resp.status, doc.get("response", "")

        status, _ = await turn("pctl", "gamma gamma gamma")
        assert status == 200, f"tiered ctl turn1 got {status}"
        status, ctl_t2 = await turn("pctl", "delta delta")
        assert status == 200, f"tiered ctl turn2 got {status}"
        status, _ = await turn("pvic", "gamma gamma gamma")
        assert status == 200, f"tiered vic turn1 got {status}"
        # explicit park (the proxy's linger policy would get here on its
        # own clock; the soak forces the timing): device pages free, host
        # tier holds the session, and the serve layer writes the exact
        # cold blob durably to the store
        resp = await self.client.post(
            f"/agent/{tiered_id}/park", data=json.dumps({"session": "pvic"})
        )
        doc = await resp.json()
        if resp.status != 200 or not doc.get("parked"):
            self.violations.append(
                f"park_kill: park failed ({resp.status}: {doc})"
            )
            return False
        kv_key = f"agent:{tiered_id}:kvcache:pvic"
        if self.services.store.get(kv_key) is None:
            self.violations.append("park_kill: cold-tier blob missing after park")
            return False
        engine_id = self.services.manager.get_agent(tiered_id).engine_id
        self.services.backend.kill_engine_hard(engine_id)
        t0 = time.monotonic()
        recovered = False
        while time.monotonic() - t0 < RECOVERY_CAP_S:
            status, _ = await turn("probe-park", "ping")
            if status == 200:
                recovered = True
                break
            await asyncio.sleep(0.5)
        self.mttr["park_kill"] = (
            round(time.monotonic() - t0, 3) if recovered else -1.0
        )
        if not recovered:
            self.violations.append("park_kill: engine never served again")
            return False
        status, vic_t2 = await turn("pvic", "delta delta")
        if status != 200:
            self.violations.append(f"park_kill: vic turn2 got {status}")
            return False
        if vic_t2 != ctl_t2:
            self.violations.append(
                f"park_kill token parity violated: {vic_t2!r} != {ctl_t2!r}"
            )
            return False
        return True

    async def phase_fused_resume(self, fused_id: str) -> bool:
        """SIGKILL mid-FUSED-loop: the same token-identical contract as
        phase_llm_resume, but on a ``fused_decode=true`` engine whose armed
        ``engine.fused_decode`` delay (150 ms per loop dispatch) stretches
        the victim's in-flight turn so the kill lands INSIDE a compiled
        while_loop window. The loop's single packed readback dies with the
        process — nothing of the partial loop was ever on the host — and
        the journaled turn must be rebuilt on the respawned engine from
        the KV snapshot, token-identical to the control's."""

        async def turn(session: str, message: str, n: int = 32):
            resp = await self.client.post(
                f"/agent/{fused_id}/chat",
                data=json.dumps(
                    {
                        "message": message,
                        "session": session,
                        "max_tokens": n,
                        "ignore_eos": True,
                    }
                ),
            )
            doc = await resp.json()
            rid = resp.headers.get("X-Agentainer-Request-ID", "")
            return resp.status, doc.get("response", ""), rid

        engine_id = self.services.manager.get_agent(fused_id).engine_id
        t_warm = time.monotonic()
        while time.monotonic() - t_warm < 90.0:
            stats = self.services.backend.stats(engine_id) or {}
            if stats.get("model_loaded"):
                break
            await asyncio.sleep(0.5)
        else:
            self.violations.append("fused_resume: engine never loaded")
            return False
        if stats.get("fused_decode") is not True:
            self.violations.append("fused_resume: agent is not serving fused decode")
            return False

        status, _, _ = await turn("fuctl", "alpha alpha alpha")
        assert status == 200, f"fused ctl turn1 got {status}"
        status, ctl_t2, _ = await turn("fuctl", "beta beta")
        assert status == 200, f"fused ctl turn2 got {status}"
        status, ctl_t3, _ = await turn("fuctl", "gamma", n=12)
        assert status == 200, f"fused ctl turn3 got {status}"
        status, _, _ = await turn("fuvic", "alpha alpha alpha")
        assert status == 200, f"fused vic turn1 got {status}"
        # resume is conditional on a durable snapshot (same contract as
        # phase_llm_resume — never landing is itself a violation)
        kv_key = f"agent:{fused_id}:kvcache:fuvic"
        t_snap = time.monotonic()
        while self.services.store.get(kv_key) is None:
            if time.monotonic() - t_snap > 45.0:
                self.violations.append("fused_resume: KV snapshot never landed")
                return False
            await asyncio.sleep(0.25)

        # fire turn2 and kill MID-LOOP: the armed fused-dispatch delay
        # makes each while_loop window take >= 150 ms, so 0.25 s into the
        # 32-token turn the process is past prefill and inside (or between)
        # fused loops whose results the host has never seen
        t2_task = asyncio.ensure_future(turn("fuvic", "beta beta"))
        await asyncio.sleep(0.25)
        t_kill = time.monotonic()
        self.services.backend.kill_engine_hard(engine_id)
        status, live_t2, rid = await t2_task
        if status == 200:
            # kill landed after the turn completed — still a valid A/B
            if live_t2 != ctl_t2:
                self.violations.append(
                    f"fused_resume: live turn2 diverged: {live_t2!r} != {ctl_t2!r}"
                )
                return False
        else:
            if not rid:
                self.violations.append(
                    f"fused_resume: turn2 got {status} with no request id"
                )
                return False
            # the acked-by-journal turn replays onto the respawned engine
            # and must settle COMPLETED with the token-identical text
            deadline = time.monotonic() + RECOVERY_CAP_S
            req = None
            while time.monotonic() < deadline:
                req = self.services.journal.get(fused_id, rid)
                if req is not None and req.status == "completed":
                    break
                await asyncio.sleep(0.25)
            if req is None or req.status != "completed":
                self.violations.append(
                    "fused_resume: mid-loop turn never settled "
                    f"({None if req is None else req.status})"
                )
                return False
            import base64 as _b64

            body = _b64.b64decode((req.response or {}).get("body_b64", "") or "")
            try:
                archived = json.loads(body).get("response", "")
            except Exception:
                archived = ""
            if archived != ctl_t2:
                self.violations.append(
                    f"fused_resume: archived turn2 diverged: "
                    f"{archived!r} != {ctl_t2!r}"
                )
                return False
        # recovery probes on a THROWAWAY session (a 502'd probe pointed at
        # fuvic would journal-replay an extra turn and desync the context)
        t0 = time.monotonic()
        recovered = False
        while time.monotonic() - t0 < RECOVERY_CAP_S:
            s, _, _ = await turn("fuprobe", "ping", n=4)
            if s == 200:
                recovered = True
                break
            await asyncio.sleep(0.5)
        self.mttr["fused_sigkill"] = (
            round(time.monotonic() - t_kill, 3) if recovered else -1.0
        )
        if not recovered:
            self.violations.append("fused_resume: engine never served again")
            return False
        # the next LIVE victim turn continues the spliced session exactly
        status, vic_t3, _ = await turn("fuvic", "gamma", n=12)
        if status != 200:
            self.violations.append(f"fused_resume: vic turn3 got {status}")
            return False
        if vic_t3 != ctl_t3:
            self.violations.append(
                f"fused_resume: post-respawn turn diverged: "
                f"{vic_t3!r} != {ctl_t3!r}"
            )
            return False
        self.counts["fused_loops_after_resume"] = int(
            (
                self.services.backend.stats(
                    self.services.manager.get_agent(fused_id).engine_id
                )
                or {}
            ).get("fused_loops_total", 0)
            or 0
        )
        return True

    async def phase_fused_inject_resume(self, fid: str) -> bool:
        """SIGKILL while a lane is being INJECTED into a running fused loop
        that also holds unverified in-loop speculation state. A long
        repetitive victim turn keeps the device n-gram drafter firing
        (accepted drafts the host has NOT read back yet); 0.15 s in, a
        second session's prefill stages itself into the running loop
        (double-buffered lane injection); 0.15 s later the process is
        SIGKILLed. Everything in flight — the armed staging slot, the
        loop's packed readback, the drafted tokens — dies with the
        process. Both journaled turns must settle COMPLETED on the
        respawned engine token-identical to the controls, and the next
        LIVE victim turn must match the control's bit for bit (extends
        ``fused_resume_token_identical`` to the injection + in-loop-spec
        composition)."""

        async def turn(session: str, message: str, n: int = 32):
            resp = await self.client.post(
                f"/agent/{fid}/chat",
                data=json.dumps(
                    {
                        "message": message,
                        "session": session,
                        "max_tokens": n,
                        "ignore_eos": True,
                    }
                ),
            )
            doc = await resp.json()
            rid = resp.headers.get("X-Agentainer-Request-ID", "")
            return resp.status, doc.get("response", ""), rid

        async def settle_identical(task, want: str, label: str) -> bool:
            status, live, rid = await task
            if status == 200:
                if live != want:
                    self.violations.append(
                        f"fused_inject: live {label} diverged: {live!r} != {want!r}"
                    )
                    return False
                return True
            if not rid:
                self.violations.append(
                    f"fused_inject: {label} got {status} with no request id"
                )
                return False
            deadline = time.monotonic() + RECOVERY_CAP_S
            req = None
            while time.monotonic() < deadline:
                req = self.services.journal.get(fid, rid)
                if req is not None and req.status == "completed":
                    break
                await asyncio.sleep(0.25)
            if req is None or req.status != "completed":
                self.violations.append(
                    f"fused_inject: {label} never settled "
                    f"({None if req is None else req.status})"
                )
                return False
            import base64 as _b64

            body = _b64.b64decode((req.response or {}).get("body_b64", "") or "")
            try:
                archived = json.loads(body).get("response", "")
            except Exception:
                archived = ""
            if archived != want:
                self.violations.append(
                    f"fused_inject: archived {label} diverged: "
                    f"{archived!r} != {want!r}"
                )
                return False
            return True

        engine_id = self.services.manager.get_agent(fid).engine_id
        t_warm = time.monotonic()
        while time.monotonic() - t_warm < 90.0:
            stats = self.services.backend.stats(engine_id) or {}
            if stats.get("model_loaded"):
                break
            await asyncio.sleep(0.5)
        else:
            self.violations.append("fused_inject: engine never loaded")
            return False
        if stats.get("fused_decode") is not True or stats.get("inloop_spec") is not True:
            self.violations.append(
                "fused_inject: agent is not serving fused decode + in-loop spec"
            )
            return False

        # repetitive text keeps the trailing-n-gram drafter matching, so
        # the loop is actually carrying accepted-draft state when killed
        rep = "tick tock tick tock tick tock tick tock"
        status, _, _ = await turn("fictl", rep)
        assert status == 200, f"fused_inject ctl turn1 got {status}"
        status, ctl_t2, _ = await turn("fictl", rep)
        assert status == 200, f"fused_inject ctl turn2 got {status}"
        status, ctl_t3, _ = await turn("fictl", "gamma", n=12)
        assert status == 200, f"fused_inject ctl turn3 got {status}"
        status, ctl_b, _ = await turn("fictl-b", "omega omega omega", n=12)
        assert status == 200, f"fused_inject ctl lane-b got {status}"

        status, _, _ = await turn("fivic", rep)
        assert status == 200, f"fused_inject vic turn1 got {status}"
        kv_key = f"agent:{fid}:kvcache:fivic"
        t_snap = time.monotonic()
        while self.services.store.get(kv_key) is None:
            if time.monotonic() - t_snap > 45.0:
                self.violations.append("fused_inject: KV snapshot never landed")
                return False
            await asyncio.sleep(0.25)

        # fire the long victim turn, let its fused loop get in flight
        # (>= one armed 150 ms dispatch), then fire the second session so
        # its prefill stages into the RUNNING loop, then kill with both
        # the staged lane and the loop's packed readback undelivered
        t2_task = asyncio.ensure_future(turn("fivic", rep))
        await asyncio.sleep(0.15)
        tb_task = asyncio.ensure_future(turn("fivic-b", "omega omega omega", n=12))
        await asyncio.sleep(0.15)
        # sample the DOOMED engine's counters just before the kill: the
        # respawned process starts from zero, so this is the only record
        # of what was actually in flight when the SIGKILL landed
        pre_kill = self.services.backend.stats(engine_id) or {}
        t_kill = time.monotonic()
        self.services.backend.kill_engine_hard(engine_id)
        ok_a = await settle_identical(t2_task, ctl_t2, "vic turn2")
        ok_b = await settle_identical(tb_task, ctl_b, "injected lane")
        if not (ok_a and ok_b):
            return False

        # recovery probes on a THROWAWAY session (same reasoning as
        # phase_fused_resume)
        t0 = time.monotonic()
        recovered = False
        while time.monotonic() - t0 < RECOVERY_CAP_S:
            s, _, _ = await turn("fiprobe", "ping", n=4)
            if s == 200:
                recovered = True
                break
            await asyncio.sleep(0.5)
        self.mttr["fused_inject_sigkill"] = (
            round(time.monotonic() - t_kill, 3) if recovered else -1.0
        )
        if not recovered:
            self.violations.append("fused_inject: engine never served again")
            return False
        status, vic_t3, _ = await turn("fivic", "gamma", n=12)
        if status != 200:
            self.violations.append(f"fused_inject: vic turn3 got {status}")
            return False
        if vic_t3 != ctl_t3:
            self.violations.append(
                f"fused_inject: post-respawn turn diverged: "
                f"{vic_t3!r} != {ctl_t3!r}"
            )
            return False
        stats = (
            self.services.backend.stats(
                self.services.manager.get_agent(fid).engine_id
            )
            or {}
        )
        # pre-kill: what the dead process had absorbed (injections +
        # staged arms + drafts in flight); post-respawn: the replayed
        # turns' in-loop drafting on the fresh process
        self.counts["fused_inject_injections_pre_kill"] = int(
            pre_kill.get("fused_injections_total", 0) or 0
        ) + int(pre_kill.get("fused_inject_fallbacks_total", 0) or 0)
        self.counts["fused_inject_drafted_pre_kill"] = int(
            pre_kill.get("inloop_spec_drafted", 0) or 0
        )
        self.counts["fused_inject_drafted"] = int(
            stats.get("inloop_spec_drafted", 0) or 0
        )
        return True

    def _affine_replica(self, agent_id: str, session: str) -> str:
        """Which replica the router pinned a session to (the kill target)."""
        router = self.services.router
        with router._lock:
            return router._affinity.get((agent_id, session), "")

    async def phase_replica_failover(self, fleet_id: str) -> bool:
        """Mid-decode failover on a 2-replica LLM fleet. The control
        session runs turn1+turn2 clean. The victim session runs turn1,
        then turn2 is fired and the replica SERVING it is SIGKILLed while
        the decode is in flight. The journaled turn must settle COMPLETED
        on the SURVIVOR (session restored from the store-durable snapshot)
        with a response token-identical to the control's, and the next
        LIVE turn must match the control's turn3 bit for bit."""

        async def turn(session: str, message: str, n: int = 12):
            resp = await self.client.post(
                f"/agent/{fleet_id}/chat",
                data=json.dumps(
                    {
                        "message": message,
                        "session": session,
                        "max_tokens": n,
                        "ignore_eos": True,
                    }
                ),
            )
            doc = await resp.json()
            rid = resp.headers.get("X-Agentainer-Request-ID", "")
            return resp.status, doc.get("response", ""), rid

        # both replicas must be past model load: the phase's very first
        # turn asserts a 200, and a replica still LOADING would 502 it
        agent = self.services.manager.get_agent(fleet_id)
        t_warm = time.monotonic()
        for eid in agent.all_engine_ids():
            while time.monotonic() - t_warm < 90.0:
                stats = self.services.backend.stats(eid) or {}
                if stats.get("model_loaded"):
                    break
                await asyncio.sleep(0.5)
            else:
                self.violations.append(
                    f"replica_failover: replica {eid} never loaded"
                )
                return False

        status, _, _ = await turn("fctl", "alpha alpha alpha")
        assert status == 200, f"fleet ctl turn1 got {status}"
        status, ctl_t2, _ = await turn("fctl", "beta beta", n=32)
        assert status == 200, f"fleet ctl turn2 got {status}"
        status, ctl_t3, _ = await turn("fctl", "gamma", n=12)
        assert status == 200, f"fleet ctl turn3 got {status}"
        status, _, _ = await turn("fvic", "alpha alpha alpha")
        assert status == 200, f"fleet vic turn1 got {status}"
        # the failover resume restores from the durable snapshot: wait for
        # the victim session's snapshot to land (same contract as
        # phase_llm_resume — never landing is itself a violation)
        kv_key = f"agent:{fleet_id}:kvcache:fvic"
        t_snap = time.monotonic()
        while self.services.store.get(kv_key) is None:
            if time.monotonic() - t_snap > 45.0:
                self.violations.append(
                    "replica_failover: KV snapshot never landed"
                )
                return False
            await asyncio.sleep(0.25)

        victim_replica = self._affine_replica(fleet_id, "fvic")
        if not victim_replica:
            self.violations.append("replica_failover: no session affinity recorded")
            return False
        # fire turn2 and kill the serving replica MID-DECODE: the armed
        # decode_step delay makes the 32-token turn take >= 0.6 s, so
        # 0.25 s in the request is past prefill and inside the decode loop
        t2_task = asyncio.ensure_future(turn("fvic", "beta beta", n=32))
        await asyncio.sleep(0.25)
        t_kill = time.monotonic()
        self.services.backend.kill_engine_hard(victim_replica)
        status, live_t2, rid = await t2_task
        # two legitimate outcomes: the dispatch died mid-flight (5xx; the
        # journaled entry replays onto the survivor) or the kill landed
        # before/after the forward and the bounded retry served it live
        if status == 200:
            if live_t2 != ctl_t2:
                self.violations.append(
                    f"replica_failover: live turn2 diverged: {live_t2!r} != {ctl_t2!r}"
                )
                return False
        else:
            if not rid:
                self.violations.append(
                    f"replica_failover: turn2 got {status} with no request id"
                )
                return False
            # the acked-by-journal turn must settle COMPLETED on the
            # survivor with the token-identical continuation
            deadline = time.monotonic() + RECOVERY_CAP_S
            req = None
            while time.monotonic() < deadline:
                req = self.services.journal.get(fleet_id, rid)
                if req is not None and req.status == "completed":
                    break
                await asyncio.sleep(0.25)
            if req is None or req.status != "completed":
                self.violations.append(
                    "replica_failover: mid-decode turn never settled "
                    f"({None if req is None else req.status})"
                )
                return False
            import base64 as _b64

            body = _b64.b64decode((req.response or {}).get("body_b64", "") or "")
            try:
                archived = json.loads(body).get("response", "")
            except Exception:
                archived = ""
            if archived != ctl_t2:
                self.violations.append(
                    f"replica_failover: archived turn2 diverged: "
                    f"{archived!r} != {ctl_t2!r}"
                )
                return False
        # fleet-level MTTR: the agent as a whole keeps serving through the
        # survivor — measured as time-to-next-200 on a throwaway session
        t0 = time.monotonic()
        recovered = False
        while time.monotonic() - t0 < RECOVERY_CAP_S:
            s, _, _ = await turn("fprobe", "ping", n=4)
            if s == 200:
                recovered = True
                break
            await asyncio.sleep(0.2)
        self.mttr["replica_failover"] = (
            round(time.monotonic() - t_kill, 3) if recovered else -1.0
        )
        if not recovered:
            self.violations.append("replica_failover: fleet never served again")
            return False
        # the next LIVE victim turn continues the spliced session exactly.
        # Routing is deterministic here because EVERY dispatcher (including
        # the replay worker that settled turn2) parses the session hint:
        # fvic's affinity follows the replica that actually executed the
        # failover turn — usually the survivor; the respawned victim only
        # if it came back in time to execute turn2 itself, in which case
        # ITS resident context is equally correct. Either way turn3 lands
        # on the replica holding turn1+turn2, never on a stale restore.
        if not self._affine_replica(fleet_id, "fvic"):
            self.violations.append(
                "replica_failover: failover dispatch recorded no affinity"
            )
            return False
        status, vic_t3, _ = await turn("fvic", "gamma", n=12)
        if status != 200:
            self.violations.append(f"replica_failover: vic turn3 got {status}")
            return False
        if vic_t3 != ctl_t3:
            self.violations.append(
                f"replica_failover: post-failover turn diverged: "
                f"{vic_t3!r} != {ctl_t3!r}"
            )
            return False
        return True

    async def phase_stream_kill(self, fleet_id: str) -> bool:
        """SIGKILL the replica SERVING a live SSE stream mid-decode. The
        tentpole invariant: the client's single connection sees one
        gapless, duplicate-free offset sequence 0..n-1 whose token stream
        is bit-for-bit the undisturbed control's — the proxy fails over to
        the survivor and splices at exactly last_acked_offset + 1, no
        client reconnect involved. The journaled entry settles COMPLETED
        with its stream cursor at the final offset."""

        def parse_frames(raw: bytes):
            frames = []
            for block in raw.split(b"\n\n"):
                if not block.strip() or block.lstrip().startswith(b":"):
                    continue  # keep-alive comments carry no offset
                event, eid, data = "", None, None
                for ln in block.split(b"\n"):
                    if ln.startswith(b"event:"):
                        event = ln[6:].strip().decode()
                    elif ln.startswith(b"id:"):
                        eid = int(ln[3:].strip())
                    elif ln.startswith(b"data:"):
                        data = json.loads(ln[5:].strip())
                frames.append((event, eid, data))
            return frames

        async def turn(session: str, message: str, n: int = 12, stream: bool = False):
            # control and victim MUST send byte-identical prompts (the
            # token comparison is bit-for-bit), so no self.chat sequencing
            resp = await self.client.post(
                f"/agent/{fleet_id}/chat",
                data=json.dumps(
                    {
                        "message": message,
                        "session": session,
                        "stream": stream,
                        "max_tokens": n,
                        "ignore_eos": True,
                    }
                ),
            )
            return resp

        # both replicas past model load (an earlier phase may have killed
        # and respawned one of them)
        agent = self.services.manager.get_agent(fleet_id)
        t_warm = time.monotonic()
        for eid in agent.all_engine_ids():
            while time.monotonic() - t_warm < 90.0:
                stats = self.services.backend.stats(eid) or {}
                if stats.get("model_loaded"):
                    break
                await asyncio.sleep(0.5)
            else:
                self.violations.append(f"stream_kill: replica {eid} never loaded")
                return False

        # undisturbed control: same two turns the victim will run
        resp = await turn("sctl", "epsilon epsilon epsilon")
        await resp.read()
        if resp.status != 200:
            self.violations.append(f"stream_kill: ctl turn1 got {resp.status}")
            return False
        resp = await turn("sctl", "delta delta", n=24, stream=True)
        if resp.status != 200 or not resp.headers.get("Content-Type", "").startswith(
            "text/event-stream"
        ):
            self.violations.append(
                f"stream_kill: ctl stream got {resp.status} "
                f"({resp.headers.get('Content-Type', '')!r})"
            )
            return False
        ctl_frames = parse_frames(await resp.read())
        ctl_tokens = [f[2]["token"] for f in ctl_frames if f[0] == "token"]
        ctl_done = [f[2] for f in ctl_frames if f[0] == "done"]
        if not ctl_tokens or len(ctl_done) != 1:
            self.violations.append("stream_kill: control stream malformed")
            return False

        # victim session: turn1 pins affinity and lands a durable snapshot
        # (the failover resume restores from it, same as replica_failover)
        resp = await turn("svic", "epsilon epsilon epsilon")
        await resp.read()
        if resp.status != 200:
            self.violations.append(f"stream_kill: vic turn1 got {resp.status}")
            return False
        kv_key = f"agent:{fleet_id}:kvcache:svic"
        t_snap = time.monotonic()
        while self.services.store.get(kv_key) is None:
            if time.monotonic() - t_snap > 45.0:
                self.violations.append("stream_kill: KV snapshot never landed")
                return False
            await asyncio.sleep(0.25)
        victim_replica = self._affine_replica(fleet_id, "svic")
        if not victim_replica:
            self.violations.append("stream_kill: no session affinity recorded")
            return False

        # open the victim stream, read a few live events, then SIGKILL the
        # serving replica with the rest of the decode still in flight
        resp = await turn("svic", "delta delta", n=24, stream=True)
        if resp.status != 200:
            self.violations.append(f"stream_kill: vic stream got {resp.status}")
            return False
        rid = resp.headers.get("X-Agentainer-Request-ID", "")
        raw = b""
        seen_tokens = 0
        try:
            while seen_tokens < 3:
                raw += await asyncio.wait_for(
                    resp.content.readuntil(b"\n\n"), timeout=RECOVERY_CAP_S
                )
                seen_tokens = sum(1 for f in parse_frames(raw) if f[0] == "token")
        except (asyncio.TimeoutError, asyncio.IncompleteReadError):
            self.violations.append("stream_kill: stream stalled before the kill")
            return False
        t_kill = time.monotonic()
        self.services.backend.kill_engine_hard(victim_replica)
        try:
            raw += await asyncio.wait_for(resp.content.read(), timeout=RECOVERY_CAP_S)
        except asyncio.TimeoutError:
            self.violations.append("stream_kill: stream never finished after kill")
            self.mttr["stream_kill"] = -1.0
            return False
        frames = parse_frames(raw)
        tokens = [f for f in frames if f[0] == "token"]
        dones = [f[2] for f in frames if f[0] == "done"]
        errors = [f for f in frames if f[0] == "error"]
        ok = True
        # THE invariant: gapless, duplicate-free, bit-for-bit the control
        offsets = [f[1] for f in tokens]
        if offsets != list(range(len(offsets))):
            self.violations.append(f"stream_kill: offsets not gapless: {offsets}")
            ok = False
        if [f[2]["token"] for f in tokens] != ctl_tokens:
            self.violations.append("stream_kill: spliced token stream diverged")
            ok = False
        if len(dones) != 1 or errors:
            self.violations.append(
                f"stream_kill: terminal frames wrong (done={len(dones)}, "
                f"error={len(errors)})"
            )
            ok = False
        elif dones[0].get("response") != ctl_done[0].get("response"):
            self.violations.append("stream_kill: done payload diverged from control")
            ok = False
        self.mttr["stream_kill"] = round(time.monotonic() - t_kill, 3) if ok else -1.0
        # journal: archived COMPLETED with the cursor at the final offset
        if rid:
            req = self.services.journal.get(fleet_id, rid)
            if req is None or req.status != "completed":
                self.violations.append(
                    "stream_kill: streamed entry not archived "
                    f"({None if req is None else req.status})"
                )
                ok = False
            elif req.stream_offset != len(ctl_tokens) - 1:
                self.violations.append(
                    f"stream_kill: cursor {req.stream_offset} != "
                    f"{len(ctl_tokens) - 1}"
                )
                ok = False
        else:
            self.violations.append("stream_kill: no request id on stream")
            ok = False
        return ok

    async def phase_lease_flap(self, fleet_echo_id: str) -> bool:
        """Heartbeat starvation without a death: the replica.lease
        failpoint fails refreshes until its budget is spent, so healthy
        replicas flap SUSPECT (routing excludes them; the pick falls back
        to try-anyway when every replica is excluded). Service must stay
        at 200s throughout, and every replica must return ALIVE."""
        mon = self.services.replica_monitor
        before = mon.suspects_total
        # budget sizing: the monitor refreshes EVERY multi-replica lease
        # each 0.25s tick (4 replicas across both fleets = 16 fires/s), so
        # 24 fires ≈ 1.5s of starvation — past suspect_after_s (1.0) but
        # safely short of dead_after_s (2.0): flapping, not death
        faults.arm(
            "replica.lease", error="ConnectionError", probability=1.0, count=24
        )
        t0 = time.monotonic()
        while time.monotonic() - t0 < 4.0:
            status, msg = await self.chat(fleet_echo_id, session="flap")
            if status != 200:
                self.violations.append(f"lease_flap: {msg} got {status}")
            await asyncio.sleep(0.25)
        faults.disarm("replica.lease")
        if mon.suspects_total <= before:
            self.violations.append(
                "lease_flap: no SUSPECT transition observed (lease seam not wired?)"
            )
            return False
        # refreshes resume: every replica must settle back to ALIVE
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            states = mon.states(fleet_echo_id)
            if states and set(states.values()) == {"alive"}:
                return True
            await asyncio.sleep(0.25)
        self.violations.append(
            f"lease_flap: replicas never returned ALIVE: {mon.states(fleet_echo_id)}"
        )
        return False

    async def phase_route_dead(self, fleet_echo_id: str) -> bool:
        """Stale routing state: one replica is SIGKILLed, the monitor is
        given time to mark it SUSPECT, then router.pick is armed (seeded
        50%) to hand the dead/excluded replica back anyway. Requests in
        the window must be absorbed by the bounded retry-on-next-replica
        (200 via the survivor) or at worst take the durable 502-pending
        path and drain later — never lost. The DEAD transition then fires
        fleet repair, which respawns the victim (the agent has no
        auto_restart watcher, so repair IS the recovery path here)."""
        agent = self.services.manager.get_agent(fleet_echo_id)
        victim = agent.all_engine_ids()[-1]
        router = self.services.router
        stale_before = router.stale_picks_total
        self.services.backend.kill_engine_hard(victim)
        # lease must age past suspect_after_s (1.0) so the victim is
        # actually EXCLUDED — that's what makes a fired pick "stale"
        await asyncio.sleep(1.3)
        faults.arm(
            "router.pick", error="FaultInjected", probability=0.5, seed=SEED, count=12
        )
        ok200 = 0
        for i in range(8):
            status, msg = await self.chat(fleet_echo_id, session=f"rd-{i}")
            if status == 200:
                ok200 += 1
            elif status not in (202, 502):
                self.violations.append(f"route_dead: {msg} got {status}")
            await asyncio.sleep(0.1)
        faults.disarm("router.pick")
        if router.stale_picks_total <= stale_before:
            self.violations.append(
                "route_dead: failpoint never produced a stale pick "
                "(seam not wired?)"
            )
            return False
        if ok200 == 0:
            self.violations.append(
                "route_dead: no request reached the survivor during the window"
            )
            return False
        # repair (DEAD at 2s) respawns the victim: the fleet heals itself
        await self.probe_until_ok(fleet_echo_id, "route_dead")
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            states = self.services.replica_monitor.states(fleet_echo_id)
            if states and set(states.values()) == {"alive"}:
                return True
            await asyncio.sleep(0.5)
        self.violations.append(
            "route_dead: victim replica never repaired to ALIVE: "
            f"{self.services.replica_monitor.states(fleet_echo_id)}"
        )
        return False

    # -- invariant settlement ---------------------------------------------
    async def settle(self, agent_ids: list[str]) -> dict:
        inv = {}
        pending_zero = True
        for aid in agent_ids:
            if not await self.drain_pending(aid):
                pending_zero = False
                self.violations.append(
                    f"pending did not converge to 0 for {aid}: "
                    f"{self.services.journal.stats(aid)}"
                )
        inv["pending_converges_to_zero"] = pending_zero

        # every QUEUED ack must have settled COMPLETED (no acked loss)
        lost = []
        for msg, rec in self.acks.items():
            if rec["kind"] == "queued" and rec["rid"]:
                req = self.services.journal.get(rec["agent_id"], rec["rid"])
                if req is None or req.status != "completed":
                    lost.append((msg, None if req is None else req.status))
        if lost:
            self.violations.append(f"acked-but-lost requests: {lost[:5]}")
        inv["no_acked_request_lost"] = not lost

        # history-based exactly-once: NO message may appear twice (double
        # execution). Presence is required only for QUEUED acks — a 202's
        # work executes via replay once engine+store are healthy. A sync
        # 200 during a store blip is DELIVERED but its conversation record
        # is best-effort (the echo engine explicitly chooses availability
        # over convo durability when the store is dark) — counted as
        # degradation, not loss.
        doubles, missing, degraded = [], [], 0
        by_agent: dict[str, list[str]] = {}
        for msg, rec in self.acks.items():
            by_agent.setdefault(rec["agent_id"], []).append(msg)
        for aid, msgs in by_agent.items():
            resp = await self.client.get(f"/agent/{aid}/history")
            if resp.status != 200:
                continue  # llm resume agent history is session-keyed; checked above
            hist = (await resp.json()).get("history", [])
            contents = [t.get("content", "") for t in hist]
            for msg in msgs:
                n = contents.count(msg)
                if n > 1:
                    doubles.append((msg, n))
                elif n == 0 and self.acks[msg]["kind"] == "queued":
                    missing.append(msg)
                elif n == 0 and self.acks[msg]["kind"] == "sync":
                    degraded += 1
        if doubles:
            self.violations.append(f"double execution: {doubles[:5]}")
        if missing:
            self.violations.append(f"queued-acked messages missing from history: {missing[:5]}")
        inv["no_double_execution"] = not doubles
        inv["queued_messages_recorded"] = not missing
        self.counts["history_degraded"] = degraded
        return inv


def torn_aof_check(tmpdir: str) -> dict | None:
    """Native-store AOF torn-tail invariant: truncating mid-record loses
    ONLY the torn record; reopen keeps every complete one AND post-recovery
    appends survive the next reopen (the truncate-before-append fix)."""
    try:
        from agentainer_tpu.native import available

        if not available():
            return None
        from agentainer_tpu.store.native import NativeStore
    except Exception:
        return None
    path = os.path.join(tmpdir, "chaos.aof")
    s = NativeStore(aof_path=path)
    for i in range(8):
        s.set(f"k{i}", f"v{i}")
    s.rpush("torn-list", "x", "y")
    s.close()
    with open(path, "r+b") as f:
        f.seek(0, os.SEEK_END)
        f.truncate(f.tell() - 3)  # tear the last record mid-bytes
    t0 = time.monotonic()
    s2 = NativeStore(aof_path=path)
    recovered = all(s2.get(f"k{i}") == f"v{i}".encode() for i in range(8))
    torn_dropped = s2.lrange("torn-list", 0, -1) == []
    s2.set("after-recovery", "ok")
    s2.close()
    s3 = NativeStore(aof_path=path)
    continue_ok = s3.get("after-recovery") == b"ok" and s3.get("k0") == b"v0"
    s3.close()
    return {
        "recovered_complete_records": recovered,
        "torn_record_dropped": torn_dropped,
        "reopen_and_continue": continue_ok,
        "mttr_s": round(time.monotonic() - t0, 3),
    }


async def run_soak(tmpdir: str) -> dict:
    soak = Soak(tmpdir)
    n_base = 4 if SMOKE else 8
    n_blip = 6 if SMOKE else 12
    n_slow = 3 if SMOKE else 6
    try:
        await soak.start()
        echo_id = await soak.deploy("chaos-echo", "echo")
        llm_id = await soak.deploy(
            "chaos-llm",
            {
                "engine": "llm",
                "config": "tiny",
                "options": {
                    "max_batch": 2,
                    "max_seq": 256,
                    "prefill_chunk": 64,
                    "kv_snapshot_interval_s": 0.5,
                },
            },
        )
        poison_id = await soak.deploy(
            "chaos-poison",
            {
                "engine": "llm",
                "config": "tiny",
                # distinct options → distinct share key → its OWN host
                # process, so the poison env cannot leak into chaos-llm
                "options": {"max_batch": 1, "max_seq": 128, "prefill_chunk": 32},
            },
            env={"ATPU_FAULTS": "engine.prefill:error=RuntimeError,count=2"},
        )
        # 2-replica fleets: the echo fleet exercises lease flapping and
        # stale routing (auto_restart OFF — fleet repair must be the thing
        # that revives a dead replica); the LLM fleet exercises mid-decode
        # failover with token-identical resume on the survivor. Fleet
        # replicas of one agent never share a host process (replica
        # ordinal is in the share key), so killing one leaves the other.
        fleet_echo_id = await soak.deploy(
            "chaos-fleet-echo", "echo", auto_restart=False, replicas=2
        )
        fleet_llm_id = await soak.deploy(
            "chaos-fleet-llm",
            {
                "engine": "llm",
                "config": "tiny",
                # speculative OFF: prompt-lookup drafting can finish a
                # 32-token repetitive turn in <0.15s, turning the phase's
                # "mid-decode" kill into a completed-but-not-yet-durable
                # kill (the PR-5 durability-floor window, asserted by the
                # llm_sigkill phase instead). Plain decode makes the kill
                # land deterministically inside the decode loop, which is
                # the failover case this phase exists to pin.
                "options": {
                    "max_batch": 2,
                    "max_seq": 256,
                    "prefill_chunk": 64,
                    "kv_snapshot_interval_s": 0.5,
                    "speculative": False,
                    # incremental emission on: stream_kill SIGKILLs the
                    # replica serving a live SSE stream mid-decode
                    "streaming": True,
                },
            },
            replicas=2,
            # delay-only decode failpoint in BOTH replicas' engines: the
            # tiny CPU model decodes 32 plain tokens in well under the
            # 0.15s kill offset, so without it the "mid-decode" kill
            # lands after completion (the PR-5 durability-floor window,
            # already pinned by llm_sigkill). 150 ms per decode chunk
            # makes a 32-token turn take >= 0.6 s on every machine —
            # the kill deterministically interrupts the decode loop.
            # Symmetric across replicas and delay-only: greedy token
            # streams are unchanged, so the control comparison holds.
            env={"ATPU_FAULTS": "engine.decode_step:error=none,delay_ms=150"},
        )
        fused_id = await soak.deploy(
            "chaos-fused",
            {
                "engine": "llm",
                "config": "tiny",
                # fused on-device decode loop: up to decode_chunk forwards +
                # in-loop sampling per dispatch, ONE readback at loop exit.
                # speculative OFF for the same reason as chaos-fleet-llm:
                # the kill must land inside plain fused decode, not after a
                # prompt-lookup round already finished the turn.
                "options": {
                    "max_batch": 2,
                    "max_seq": 256,
                    "decode_chunk": 8,
                    "prefill_chunk": 64,
                    "kv_snapshot_interval_s": 0.5,
                    "speculative": False,
                    "fused_decode": True,
                },
            },
            # delay-only failpoint on the FUSED dispatch seam (warmup
            # exempt): 150 ms per while_loop window makes the 32-token
            # victim turn take >= 0.6 s on every machine, so the 0.25 s
            # kill offset deterministically interrupts a window whose
            # packed readback the host has not seen yet. Delay-only: the
            # greedy token stream is unchanged, the control holds.
            env={"ATPU_FAULTS": "engine.fused_decode:error=none,delay_ms=150"},
        )
        fused_inject_id = await soak.deploy(
            "chaos-fused-inject",
            {
                "engine": "llm",
                "config": "tiny",
                # fused loop WITH in-loop speculation (speculative on, so
                # the device n-gram drafter runs inside the loop) and lane
                # injection enabled: the composition whose in-flight state
                # is the largest thing a SIGKILL can vaporize. Distinct
                # options → its own host process.
                "options": {
                    "max_batch": 2,
                    "max_seq": 256,
                    "decode_chunk": 8,
                    "prefill_chunk": 32,
                    "kv_snapshot_interval_s": 0.5,
                    "speculative": True,
                    "fused_decode": True,
                },
            },
            # same delay-only fused-dispatch failpoint as chaos-fused: each
            # while_loop window takes >= 150 ms, so the staggered second
            # session reliably stages into a RUNNING loop and the kill
            # lands with that loop's readback undelivered
            env={"ATPU_FAULTS": "engine.fused_decode:error=none,delay_ms=150"},
        )
        paged_id = await soak.deploy(
            "chaos-paged",
            {
                "engine": "llm",
                "config": "tiny",
                # paged arena with a DELIBERATELY tiny pool (6 pages = 192
                # tokens across all sessions) so organic exhaustion joins
                # the armed engine.page_alloc failpoint below
                "options": {
                    "max_batch": 1,
                    "max_seq": 128,
                    "prefill_chunk": 32,
                    "paged_kv": True,
                    "page_size": 32,
                    "kv_pages": 6,
                },
            },
            env={"ATPU_FAULTS": "engine.page_alloc:error=RuntimeError,count=1"},
        )
        tiered_id = await soak.deploy(
            "chaos-tiered",
            {
                "engine": "llm",
                "config": "tiny",
                # paged arena + tiered-KV hierarchy: sessions park off the
                # device into pinned host RAM (int8) and a cold store
                # blob. park_kill SIGKILLs the engine while a session is
                # parked and asserts its journaled turn resumes
                # token-identically from the cold tier alone.
                "options": {
                    "max_batch": 2,
                    "max_seq": 256,
                    "prefill_chunk": 64,
                    "paged_kv": True,
                    "page_size": 32,
                    "kv_pages": 32,
                    "kv_tiering": True,
                    "kv_snapshot_interval_s": 0.5,
                },
            },
        )

        await soak.phase_baseline(echo_id, n_base)
        await soak.phase_engine_sigkill(echo_id)
        await soak.phase_store_blip(echo_id, n_blip)
        await soak.phase_slow_dispatch(echo_id, n_slow)
        poison_ok = await soak.phase_poisoned_prefill(poison_id)
        backpressured = await soak.phase_page_exhaustion(paged_id)
        token_identical = await soak.phase_llm_resume(llm_id)
        park_identical = await soak.phase_park_kill(tiered_id)
        fused_identical = await soak.phase_fused_resume(fused_id)
        inject_identical = await soak.phase_fused_inject_resume(fused_inject_id)
        lease_ok = await soak.phase_lease_flap(fleet_echo_id)
        route_ok = await soak.phase_route_dead(fleet_echo_id)
        failover_ok = await soak.phase_replica_failover(fleet_llm_id)
        stream_ok = await soak.phase_stream_kill(fleet_llm_id)

        inv = await soak.settle(
            [
                echo_id,
                poison_id,
                paged_id,
                llm_id,
                tiered_id,
                fused_id,
                fused_inject_id,
                fleet_echo_id,
                fleet_llm_id,
            ]
        )
        inv["token_identical_resume"] = token_identical
        inv["park_kill_token_identical"] = park_identical
        inv["fused_resume_token_identical"] = fused_identical
        inv["fused_inject_resume_token_identical"] = inject_identical
        inv["page_exhaustion_backpressure"] = backpressured
        inv["lease_flap_recovers"] = lease_ok
        inv["route_dead_absorbed"] = route_ok
        inv["replica_failover_token_identical"] = failover_ok
        inv["stream_kill_gapless"] = stream_ok
        inv["poisoned_dead_letter"] = poison_ok
    finally:
        await soak.stop()
    aof = torn_aof_check(tmpdir)
    if aof is not None:
        inv["aof_torn_tail_recovery"] = all(
            v for k, v in aof.items() if k != "mttr_s"
        )
        soak.mttr["torn_aof"] = aof["mttr_s"]
    return {
        "invariants": inv,
        "mttr_s": soak.mttr,
        "counts": soak.counts,
        "violations": soak.violations,
        "aof": aof,
    }


def main() -> int:
    t0 = time.monotonic()
    tmpdir = tempfile.mkdtemp(prefix="atpu-chaos-")
    result = asyncio.run(run_soak(tmpdir))
    ok = not result["violations"] and all(result["invariants"].values())
    doc = {
        "metric": "chaos_soak_invariants",
        "value": 1 if ok else 0,
        "unit": "pass",
        "seed": SEED,
        "smoke": SMOKE,
        "platform": os.environ.get("JAX_PLATFORMS", ""),
        **result,
        "wall_s": round(time.monotonic() - t0, 1),
    }
    # one JSON line on stdout, and the same line as the record at the root
    line = json.dumps(doc)
    print(line, flush=True)
    with open(os.path.join(REPO_ROOT, "BENCH_chaos.json"), "w") as f:
        f.write(line + "\n")
    if not ok:
        print(f"CHAOS SOAK FAILED: {result['violations']}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
