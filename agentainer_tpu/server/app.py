"""Control-plane HTTP server: management REST API + per-agent reverse proxy.

Re-implements the reference API server (internal/api/server.go) on aiohttp:

- one port serves a public ``/health``, the **unauthenticated** proxy under
  ``/agent/{id}/...``, and a bearer-token-authed management surface under
  ``/agents/*`` plus metrics/logs/audit/backups (route table parity:
  server.go:69-107; auth middleware parity: server.go:449-478);
- every response uses the ``{success, message, data}`` envelope
  (server.go:50-54);
- the proxy journals each request before dispatch, answers ``202`` with a
  request id when the agent is not running ("queue for replay",
  server.go:525-541), rewrites the path by stripping ``/agent/{id}``
  (server.go:553-557), and classifies outcomes exactly like the reference's
  interceptTransport (server.go:583-615): success → archive response;
  connection-refused/engine-gone → leave pending for the replay worker
  (crash heuristic); other errors → retry-count/dead-letter;
- replayed requests carry ``X-Agentainer-Request-ID`` +
  ``X-Agentainer-Replay: true`` and are not re-journaled (server.go:506-522).

Engines whose endpoint is ``http(s)://`` are reached over localhost HTTP
(the Docker-bridge-DNS analogue); fake test engines are dispatched in-process.
"""

from __future__ import annotations

import asyncio
import json
import os
import time
from typing import TYPE_CHECKING

from aiohttp import ClientSession, ClientTimeout, web

from .. import faults, native
from ..core.errors import AgentainerError, AgentNotFound
from ..core.resilience import CircuitBreaker, retry_after_jitter
from ..core.spec import AgentStatus, HealthCheckConfig, ModelRef, Resources
from ..manager.journal import RequestStatus, StreamGapError
from ..store.schema import Keys
from ..utils.boot import process_age_s
from .router import ReplicaChoice, ReplicaRouter

if TYPE_CHECKING:
    from ..daemon import Services

# wire-protocol constants live in core/protocol.py (shared with the replay
# worker and engine serve layer); re-exported here for existing importers
from ..core.protocol import (  # noqa: F401  (re-export)
    ACCEPTED_NS_HEADER,
    DEADLINE_HEADER,
    DISPATCH_ENGINE_GONE,
    DISPATCH_EXPIRED,
    DISPATCH_FAILED,
    DISPATCH_IN_FLIGHT,
    DISPATCH_WAIT_S,
    DRAINING_HEADER,
    EXPIRED_HEADER,
    LAST_EVENT_ID_HEADER,
    LOADING_HEADER,
    PREFILL_POISON_HEADER,
    REPLAY_HEADER,
    REQUEST_ID_HEADER,
    STREAM_CONTENT_TYPE,
    STREAM_EVENT_DONE,
    STREAM_EVENT_ERROR,
    STREAM_EVENT_TOKEN,
)

_STORE_OPS = {
    "get",
    "set",
    "set_b64",
    "get_b64",
    "delete",
    "expire",
    "rpush",
    "lrange",
    "ltrim",
    "llen",
    "hincrby",
    "hgetall",
    "keys",
}

_HOP_BY_HOP = {
    "connection",
    "keep-alive",
    "proxy-authenticate",
    "proxy-authorization",
    "te",
    "trailers",
    "transfer-encoding",
    "upgrade",
    "host",
    "content-length",
    # aiohttp auto-decompresses upstream bodies; forwarding the original
    # Content-Encoding would label a plain body as compressed
    "content-encoding",
}


class _StreamClientGone(Exception):
    """The SSE consumer's transport died mid-write. Distinct type on
    purpose: a ConnectionResetError from ``resp.write`` (client side) must
    never be classified like an upstream reset (engine side) — one aborts
    the request, the other fails over to a survivor."""


def _parse_sse_frame(raw: bytes) -> tuple[str, int | None, bytes]:
    """One ``\\n\\n``-delimited SSE block → (event, id, data). A pure
    comment block (keep-alive heartbeat) parses as event ``""``."""
    event, eid, data = "", None, b""
    comment = True
    for ln in raw.split(b"\n"):
        if ln.startswith(b":") or not ln.strip():
            continue
        comment = False
        if ln.startswith(b"event:"):
            event = ln[6:].strip().decode("utf-8", "replace")
        elif ln.startswith(b"id:"):
            try:
                eid = int(ln[3:].strip())
            except (TypeError, ValueError):
                eid = None
        elif ln.startswith(b"data:"):
            data = ln[5:].strip()
    return ("" if comment else (event or "message")), eid, data


def _tail_snapshot(path: str, tail: int) -> tuple[list[bytes], int]:
    """Last ``tail`` complete lines of ``path`` plus the follow offset.

    One consistent snapshot: lines and offset come from the same read, so
    the follow loop resumes exactly after the last line served. A trailing
    partial line (a write in flight) is NOT returned; the offset rewinds to
    its start so it streams whole once complete. Splits on ``\\n`` only —
    CR-progress lines (tqdm-style) are content, not terminators. Reads a
    bounded window from the end, growing only if it holds too few lines.
    """
    size = os.path.getsize(path)
    window = 256 << 10
    with open(path, "rb") as f:
        while True:
            start = max(0, size - window)
            f.seek(start)
            data = f.read(size - start)
            lines = data.split(b"\n")
            if data.endswith(b"\n"):
                lines.pop()  # split's trailing empty piece
                offset = start + len(data)
            else:
                partial = lines.pop()
                offset = start + len(data) - len(partial)
            if start > 0:
                lines = lines[1:]  # first piece may be a mid-line fragment
            if start == 0 or len(lines) >= tail:
                return (lines[-tail:] if tail > 0 else []), offset
            window *= 4


def envelope(data=None, message: str = "", success: bool = True) -> dict:
    return {"success": success, "message": message, "data": data}


def ok(data=None, message: str = "", status: int = 200) -> web.Response:
    return web.json_response(envelope(data, message), status=status)


def fail(
    message: str, status: int = 500, headers: dict[str, str] | None = None
) -> web.Response:
    return web.json_response(
        envelope(None, message, success=False), status=status, headers=headers
    )


class ControlPlaneApp:
    def __init__(self, services: "Services"):
        self.s = services
        self.app = web.Application(middlewares=[self._error_mw, self._auth_mw])
        self._routes()
        self._client: ClientSession | None = None
        self._listening_s: float | None = None  # /health boot: set by the first answer
        # global pending depth is a store SCAN — cached briefly so the shed
        # check stays O(1) per proxied request (staleness bound: a burst can
        # overshoot the global ceiling by ~one cache window of arrivals)
        self._global_pending_cache: tuple[float, int] = (0.0, 0)
        # store circuit breaker: when journaling flaps, the proxy answers
        # fast (503 + Retry-After, or serve-through for a running agent)
        # instead of stacking store timeouts on every request
        res = getattr(services.config, "resilience", None)
        self._store_breaker = CircuitBreaker(
            failure_threshold=getattr(res, "breaker_failures", 5),
            cooldown_s=getattr(res, "breaker_cooldown_s", 2.0),
        )
        # fleet routing tier: engages only for agents with >1 replica; the
        # single-replica dispatch path is byte-identical to pre-fleet.
        # ATPU_JITTER_SEED pins BOTH the p2c sample sequence and the
        # Retry-After jitter (chaos/bench determinism); unset = entropy.
        import random as _random

        fleet_cfg = getattr(services.config, "fleet", None)
        seed_raw = os.environ.get("ATPU_JITTER_SEED", "")
        self.router = ReplicaRouter(
            services.manager,
            fleet_cfg,
            seed=int(seed_raw) if seed_raw else _random.randrange(1 << 30),
        )
        # seeded Retry-After jitter: synchronized clients shed in the same
        # instant must not retry in the same instant (re-stampeding exactly
        # the replica that was recovering)
        self._retry_rng = _random.Random(int(seed_raw)) if seed_raw else _random.Random()
        self.journal_errors_total = 0
        self.journal_skipped_total = 0
        self.abort_cancel_errors_total = 0
        # SSE streaming data path (features.streaming): per-event forwards,
        # mid-stream failovers (upstream died → survivor re-spliced), CAS-
        # suppressed duplicate emissions, and dropped consumers
        self.stream_requests_total = 0
        self.stream_events_total = 0
        self.stream_failovers_total = 0
        self.stream_dup_suppressed_total = 0
        self.stream_client_disconnects_total = 0
        self.stream_write_errors_total = 0
        # tiered-KV proxy policy (features.kv_tiering): the proxy SEES the
        # agent's conversation — it parks a session after its response
        # settles (plus a linger window for fast tool-call round-trips)
        # and prewarms on the next arrival so the engine's swap-in
        # overlaps the queue-wait phase. Hints ride dispatch_to_agent, so
        # fleet routing/affinity semantics apply to them unchanged.
        self._tier_parked: set[tuple[str, str]] = set()
        self._tier_linger_tasks: dict[tuple[str, str], asyncio.Task] = {}
        self._tier_bg: set[asyncio.Task] = set()
        self.tier_parks_total = 0
        self.tier_park_failures_total = 0
        self.tier_prewarms_total = 0
        self.app.on_startup.append(self._on_startup)
        self.app.on_cleanup.append(self._on_cleanup)

    async def _on_startup(self, app) -> None:
        self._client = ClientSession(timeout=ClientTimeout(total=30))

    async def _on_cleanup(self, app) -> None:
        if self._client:
            await self._client.close()

    # -- middleware ------------------------------------------------------
    @web.middleware
    async def _error_mw(self, request: web.Request, handler):
        try:
            return await handler(request)
        except web.HTTPException:
            raise
        except AgentainerError as e:
            return fail(str(e), status=e.http_status)
        except Exception as e:  # pragma: no cover - defensive
            self.s.logs.error("api", f"unhandled error on {request.path}: {e!r}")
            return fail(f"internal error: {e}", status=500)

    @web.middleware
    async def _auth_mw(self, request: web.Request, handler):
        """Bearer auth on the management surface only; the proxy and /health
        are public (server.go:75-107,449-478)."""
        path = request.path
        # /internal/* authenticates with per-engine tokens in its handlers
        public = (
            path == "/health"
            or path.startswith("/agent/")
            or path == "/internal/store"
            or path == "/internal/engines/ready"
        )
        if not public:
            import hmac as _hmac

            header = request.headers.get("Authorization", "")
            token = header.removeprefix("Bearer ").strip()
            if not header.startswith("Bearer ") or not _hmac.compare_digest(
                token.encode(), self.s.config.auth_token.encode()
            ):
                self.s.logs.audit(
                    user="unknown",
                    action="auth",
                    resource=path,
                    result="denied",
                    ip=request.remote or "",
                    user_agent=request.headers.get("User-Agent", ""),
                )
                return fail("unauthorized", status=401)
        return await handler(request)

    # -- routes (server.go:69-107 parity) -------------------------------
    def _routes(self) -> None:
        r = self.app.router
        r.add_get("/health", self.h_server_health)
        r.add_route("*", "/agent/{agent_id}/{tail:.*}", self.h_proxy)
        r.add_route("*", "/agent/{agent_id}", self.h_proxy)

        r.add_post("/agents", self.h_deploy)
        r.add_get("/agents", self.h_list)
        r.add_get("/agents/{agent_id}", self.h_get)
        r.add_delete("/agents/{agent_id}", self.h_remove)
        for op in ("start", "stop", "restart", "pause", "resume"):
            r.add_post(f"/agents/{{agent_id}}/{op}", self._lifecycle_handler(op))
        r.add_get("/agents/{agent_id}/logs", self.h_logs)
        r.add_get("/agents/{agent_id}/requests", self.h_requests)
        r.add_post("/agents/{agent_id}/requests/{request_id}/replay", self.h_manual_replay)
        r.add_post("/agents/{agent_id}/requests/{request_id}/requeue", self.h_requeue)
        r.add_post("/agents/{agent_id}/profile", self.h_profile)
        r.add_get("/agents/{agent_id}/health", self.h_agent_health)
        r.add_get("/agents/{agent_id}/metrics", self.h_agent_metrics)
        r.add_get("/agents/{agent_id}/metrics/history", self.h_agent_metrics_history)
        r.add_get("/metrics", self.h_all_metrics)
        r.add_get("/logs", self.h_get_logs)
        r.add_get("/audit", self.h_get_audit)
        r.add_get("/slice", self.h_slice)
        r.add_post("/internal/store", self.h_internal_store)
        r.add_post("/internal/engines/ready", self.h_engine_ready)
        # fault-injection plane: NOT in the public path list, so the admin
        # bearer middleware guards it — arming failpoints is an operator act
        r.add_get("/internal/faults", self.h_faults_get)
        r.add_post("/internal/faults", self.h_faults_post)
        r.add_post("/artifacts", self.h_artifact_build)
        r.add_get("/artifacts", self.h_artifact_list)
        r.add_delete("/artifacts/{name}", self.h_artifact_remove)
        r.add_post("/backups", self.h_backup_create)
        r.add_get("/backups", self.h_backup_list)
        r.add_post("/backups/{backup_id}/restore", self.h_backup_restore)
        r.add_post("/backups/{backup_id}/export", self.h_backup_export)
        r.add_delete("/backups/{backup_id}", self.h_backup_delete)

    # -- helpers ---------------------------------------------------------
    def _audit(self, request: web.Request, action: str, resource: str, result: str) -> None:
        self.s.logs.audit(
            user="api-token",
            action=action,
            resource=resource,
            result=result,
            ip=request.remote or "",
            user_agent=request.headers.get("User-Agent", ""),
        )

    async def _mgr(self, fn, *args, **kw):
        """Lifecycle ops run in a thread: engine spawn can block (JAX init)."""
        return await asyncio.to_thread(fn, *args, **kw)

    # -- management handlers ---------------------------------------------
    async def h_server_health(self, request: web.Request) -> web.Response:
        if self._listening_s is None:
            self._listening_s = process_age_s()
        return ok(
            {
                "status": "healthy",
                "agents": len(self.s.manager.agent_ids()),
                "slice": self.s.scheduler.topology.name,
                "slice_chips": self.s.scheduler.topology.total_chips,
                # which front door answers /agent/*: the C++ data plane, or
                # the aiohttp proxy it falls back to when the native
                # library did not build — visible, so a fallback is a fact
                # an operator (and chip_smoke.py) can read, not a silence
                "data_plane": "native" if self.s.dataplane is not None else "python",
                # the daemon's own start: process start → this surface's
                # first answer, and inside it the native library's build or
                # load (None: never tried)
                "boot": {"listening_s": self._listening_s, "data_plane_s": native.load_seconds()},
                "time": time.time(),
            }
        )

    async def h_deploy(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return fail("invalid JSON body", status=400)
        model = body.get("model", body.get("image", "echo"))
        # artifact reference: {"artifact": "name"} or checkpoint
        # "artifact://name" resolves through the registry (manager/artifacts)
        if isinstance(model, dict):
            art_name = model.get("artifact", "") or (
                model.get("checkpoint", "").removeprefix("artifact://")
                if str(model.get("checkpoint", "")).startswith("artifact://")
                else ""
            )
            if art_name:
                doc = self.s.artifacts.get(art_name)
                if doc is None:
                    return fail(f"unknown artifact: {art_name}", status=404)
                model = dict(model)
                model.pop("artifact", None)
                model["checkpoint"] = doc["path"]
                model.setdefault("engine", "llm")
        try:
            replicas = int(body.get("replicas", 0) or 0)
        except (TypeError, ValueError):
            return fail("replicas must be an integer", status=400)
        agent = await self._mgr(
            self.s.manager.deploy,
            name=body.get("name", ""),
            model=model,
            env=body.get("env", {}),
            resources=Resources.from_dict(body.get("resources")),
            auto_restart=bool(body.get("auto_restart", False)),
            token=body.get("token", ""),
            health_check=HealthCheckConfig.from_dict(body.get("health_check")),
            replicas=replicas,
        )
        self._audit(request, "deploy", agent.id, "success")
        return ok(self.s.manager.summary(agent), message="Agent deployed successfully")

    async def h_list(self, request: web.Request) -> web.Response:
        agents = await self._mgr(self.s.manager.list_agents)
        return ok([self.s.manager.summary(a) for a in agents])

    async def h_get(self, request: web.Request) -> web.Response:
        agent = self.s.manager.get_agent(request.match_info["agent_id"])
        return ok(self.s.manager.summary(agent))

    def _lifecycle_handler(self, op: str):
        async def handler(request: web.Request) -> web.Response:
            agent_id = request.match_info["agent_id"]
            fn = getattr(self.s.manager, op)
            agent = await self._mgr(fn, agent_id)
            if op in ("start", "restart", "resume") and agent.health_check:
                self.s.health.start_monitoring(agent.id)
            if op in ("stop", "pause"):
                self.s.health.stop_monitoring(agent_id)
            self._audit(request, op, agent_id, "success")
            return ok(self.s.manager.summary(agent), message=f"Agent {op} successful")

        return handler

    async def h_remove(self, request: web.Request) -> web.Response:
        agent_id = request.match_info["agent_id"]
        self.s.health.stop_monitoring(agent_id)
        await self._mgr(self.s.manager.remove, agent_id)
        self._audit(request, "remove", agent_id, "success")
        return ok(message="Agent removed successfully")

    async def h_logs(self, request: web.Request) -> web.StreamResponse:
        agent_id = request.match_info["agent_id"]
        tail = int(request.query.get("tail", "100"))
        if request.query.get("follow", "").lower() not in ("", "0", "false"):
            return await self._follow_logs(request, agent_id, tail)
        lines = await self._mgr(self.s.manager.logs, agent_id, tail)
        return ok({"logs": lines})

    async def _follow_logs(
        self, request: web.Request, agent_id: str, tail: int
    ) -> web.StreamResponse:
        """Stream engine log lines until the client disconnects
        (agent.go:411-429 GetLogs(follow) / docker logs -f parity)."""
        path = await self._mgr(self.s.manager.log_path, agent_id)
        resp = web.StreamResponse(
            headers={"Content-Type": "text/plain; charset=utf-8"}
        )
        await resp.prepare(request)
        # exactly-once: snapshot the size first and serve the tail from the
        # SAME read, capped at that offset — lines appended concurrently are
        # picked up by the follow loop only, never sent twice. A trailing
        # partial line is excluded and the offset rewound past it, so the
        # follow loop later delivers it whole, never split mid-write.
        offset = 0
        if path:
            try:
                lines, offset = await asyncio.to_thread(_tail_snapshot, path, tail)
                for line in lines:
                    await resp.write(line + b"\n")
            except OSError:
                pass
        else:
            for line in await self._mgr(self.s.manager.logs, agent_id, tail):
                await resp.write(line.encode() + b"\n")
        try:
            while True:
                if not path:
                    await asyncio.sleep(0.5)
                    # agent may not have an engine yet (created/stopped);
                    # removal mid-follow ends the stream cleanly
                    path = await self._mgr(self.s.manager.log_path, agent_id)
                    continue
                try:
                    size = os.path.getsize(path)
                except OSError:
                    await asyncio.sleep(0.5)
                    continue
                if size < offset:
                    offset = 0  # rotated/truncated: restart from the top
                if size > offset:
                    with open(path, "rb") as f:
                        f.seek(offset)
                        chunk = f.read(min(size - offset, 1 << 20))
                    offset += len(chunk)
                    await resp.write(chunk)
                else:
                    await asyncio.sleep(0.5)  # idle only when caught up
        except (ConnectionResetError, asyncio.CancelledError):
            pass
        except Exception:
            pass  # agent removed / backend error: close the stream cleanly
        return resp

    async def h_requests(self, request: web.Request) -> web.Response:
        agent_id = request.match_info["agent_id"]
        self.s.manager.get_agent(agent_id)  # 404 check
        status = request.query.get("status", RequestStatus.PENDING)
        reqs = self.s.journal.by_status(agent_id, status)
        return ok(
            {
                "requests": [r.to_dict() for r in reqs],
                "stats": self.s.journal.stats(agent_id),
            }
        )

    async def h_manual_replay(self, request: web.Request) -> web.Response:
        """Manual single-request replay (server.go:681-751)."""
        agent_id = request.match_info["agent_id"]
        request_id = request.match_info["request_id"]
        req = self.s.journal.get(agent_id, request_id)
        if req is None:
            return fail("request not found", status=404)
        if req.expired() or req.status == RequestStatus.EXPIRED:
            # covers disconnect-expired entries too (dead-lettered with no
            # deadline set): replaying one would land the same id on both
            # the expired and completed lists
            return fail(
                "request deadline has passed; use requeue to reset and replay",
                status=410,
            )
        # force: a manual replay deliberately re-dispatches settled entries
        # (the engine's idempotency memo returns the stored result)
        status, _, body = await self.dispatch_to_agent(
            agent_id,
            req.method,
            req.path,
            req.headers,
            req.body,
            request_id=request_id,
            force=True,
        )
        if status == DISPATCH_ENGINE_GONE:
            self._audit(request, "replay", f"{agent_id}/{request_id}", "engine-unreachable")
            return fail("agent unreachable; request left pending for replay", status=502)
        if status == DISPATCH_FAILED:
            self._audit(request, "replay", f"{agent_id}/{request_id}", "failed")
            return fail("replay dispatch failed; retry recorded", status=504)
        self._audit(request, "replay", f"{agent_id}/{request_id}", "success")
        return ok(
            {"request_id": request_id, "status_code": status, "body": body.decode("utf-8", "replace")},
            message="Request replayed",
        )

    async def h_requeue(self, request: web.Request) -> web.Response:
        """Operator recovery for dead letters: reset a failed/expired entry
        (retry_count zeroed, deadline cleared) back onto the pending list,
        then kick the replay worker — transient-outage victims drain without
        hand-editing the store."""
        agent_id = request.match_info["agent_id"]
        request_id = request.match_info["request_id"]
        self.s.manager.get_agent(agent_id)  # 404 check
        req = self.s.journal.requeue(agent_id, request_id)
        if req is None:
            existing = self.s.journal.get(agent_id, request_id)
            if existing is None:
                return fail("request not found", status=404)
            return fail(
                f"request is {existing.status}; only failed/expired entries requeue",
                status=409,
            )
        if self.s.replay is not None:
            self.s.replay.kick()
        self._audit(request, "requeue", f"{agent_id}/{request_id}", "success")
        return ok(req.to_dict(), message="Request requeued for replay")

    async def h_profile(self, request: web.Request) -> web.Response:
        """Capture a jax.profiler trace on the agent's engine (SURVEY §5.1:
        the reference had only a logging middleware; profiling is a
        first-class requirement here). Body: {"duration_s": N ≤ 60}. The
        trace lands under the daemon's data dir; the response carries the
        path for tensorboard / xprof."""
        agent_id = request.match_info["agent_id"]
        try:
            agent = self.s.manager.get_agent(agent_id)
        except AgentNotFound:
            return fail(f"agent not found: {agent_id}", status=404)
        if agent.status != AgentStatus.RUNNING:
            return fail("agent is not running", status=409)
        body = await request.read()
        status, _, resp_body = await self.dispatch_to_agent(
            agent_id, "POST", "/profile", {"Content-Type": "application/json"}, body
        )
        if status in (DISPATCH_ENGINE_GONE, DISPATCH_FAILED):
            return fail("engine unreachable for profiling", status=502)
        self._audit(request, "profile", agent_id, "success" if status == 200 else "failed")
        try:
            doc = json.loads(resp_body)
        except json.JSONDecodeError:
            doc = {"raw": resp_body.decode("utf-8", "replace")}
        return ok(doc) if status == 200 else fail(str(doc), status=status)

    async def h_agent_health(self, request: web.Request) -> web.Response:
        agent_id = request.match_info["agent_id"]
        self.s.manager.get_agent(agent_id)
        return ok(self.s.health.get_status(agent_id))

    def _fleet_stats(self, agent) -> dict | None:
        """Routing/per-replica breaker view for a multi-replica agent; None
        for single-replica agents (their metrics doc stays pre-fleet)."""
        if len(agent.all_engine_ids()) <= 1:
            return None
        return self.router.stats(agent)

    # what an operator asks of ONE replica: which process it is, which
    # chips it was bound to and which device it computes on, whether its
    # model is up, and whether traffic reaches it
    _REPLICA_ENGINE_KEYS = (
        "replica",
        "pid",
        "chips",
        "visible_chips",
        "device",
        "engine_devices",
        "model_loaded",
        "engine_error",
        "engine_load_s",
        "compile_cache",
        "requests_total",
        "tokens_generated",
    )

    async def h_agent_metrics(self, request: web.Request) -> web.Response:
        agent_id = request.match_info["agent_id"]
        agent = self.s.manager.get_agent(agent_id)
        doc = self.s.metrics.current(agent_id) or {}
        fleet = self._fleet_stats(agent)
        if fleet is not None:
            doc = dict(doc)
            doc["fleet"] = fleet
            # the sampled engine block above is the primary's; the proxy
            # routes /agent/{id}/metrics by affinity, so this is the one
            # place that reads every replica's own live answer
            eids = list(fleet["replicas"])
            answers = await asyncio.gather(
                *(asyncio.to_thread(self.s.backend.stats, eid) for eid in eids)
            )
            for eid, stats in zip(eids, answers):
                if stats:
                    fleet["replicas"][eid]["engine"] = {
                        k: stats[k] for k in self._REPLICA_ENGINE_KEYS if k in stats
                    }
        return ok(doc)

    async def h_agent_metrics_history(self, request: web.Request) -> web.Response:
        agent_id = request.match_info["agent_id"]
        self.s.manager.get_agent(agent_id)
        since = float(request.query.get("since", time.time() - 3600))
        until = float(request.query.get("until", time.time()))
        return ok(self.s.metrics.history(agent_id, since, until))

    async def h_all_metrics(self, request: web.Request) -> web.Response:
        out = {}
        for agent_id in self.s.manager.agent_ids():
            doc = self.s.metrics.current(agent_id)
            agent = self.s.manager.try_get(agent_id)
            fleet = self._fleet_stats(agent) if agent is not None else None
            if fleet is not None:
                doc = dict(doc or {})
                doc["fleet"] = fleet
            out[agent_id] = doc
        return ok(out)

    async def h_get_logs(self, request: web.Request) -> web.StreamResponse:
        q = request.query
        if q.get("follow", "").lower() not in ("", "0", "false"):
            return await self._follow_server_logs(
                request,
                tail=int(q.get("limit", "20")),
                level=q.get("level", ""),
                component=q.get("component", ""),
            )
        return ok(
            self.s.logs.get_logs(
                level=q.get("level", ""),
                component=q.get("component", ""),
                agent_id=q.get("agent", ""),
                limit=int(q.get("limit", "100")),
            )
        )

    async def _follow_server_logs(
        self, request: web.Request, tail: int, level: str = "", component: str = ""
    ) -> web.StreamResponse:
        """Stream the control plane's structured log as JSON lines: a tail
        of recent entries, then live entries from the ``logs:stream``
        pub/sub channel until the client disconnects (the reference's
        TailLogs surface, logger.go:459-493 — round 1 published the
        channel but nothing consumed it). Filters apply to both the tail
        and the live stream. The subscription attaches AFTER the tail
        snapshot (tail -f semantics: no duplicates; an entry logged in
        that instant may be absent from the tail)."""

        def matches(entry: dict) -> bool:
            if level and entry.get("level") != level:
                return False
            if component and entry.get("component") != component:
                return False
            return True

        resp = web.StreamResponse(
            headers={"Content-Type": "application/x-ndjson; charset=utf-8"}
        )
        await resp.prepare(request)
        loop = asyncio.get_running_loop()
        queue: asyncio.Queue[str] = asyncio.Queue(maxsize=1000)

        def on_entry(_channel: str, message: str) -> None:
            # publisher thread → loop; drop on overflow (a stalled client
            # must not backpressure the logging plane)
            def put():
                if not queue.full():
                    queue.put_nowait(message)

            loop.call_soon_threadsafe(put)

        unsubscribe = None
        try:
            for entry in self.s.logs.get_logs(
                level=level, component=component, limit=tail
            ):
                await resp.write(json.dumps(entry).encode() + b"\n")
            unsubscribe = self.s.store.on_message(Keys.LOG_STREAM, on_entry)
            while True:
                line = await queue.get()
                try:
                    if not matches(json.loads(line)):
                        continue
                except ValueError:
                    pass
                await resp.write(line.encode() + b"\n")
        except (ConnectionResetError, asyncio.CancelledError):
            pass
        finally:
            if unsubscribe is not None:
                unsubscribe()
        return resp

    async def h_get_audit(self, request: web.Request) -> web.Response:
        q = request.query
        return ok(
            self.s.logs.get_audit(
                user=q.get("user", ""),
                action=q.get("action", ""),
                resource=q.get("resource", ""),
                limit=int(q.get("limit", "100")),
            )
        )

    async def h_slice(self, request: web.Request) -> web.Response:
        topo = self.s.scheduler.topology
        return ok(
            {
                "topology": {
                    "name": topo.name,
                    "total_chips": topo.total_chips,
                    "hbm_per_chip": topo.hbm_per_chip,
                },
                "placements": [p.to_dict() for p in self.s.scheduler.placements()],
                "free_hbm": self.s.scheduler.free_hbm(),
            }
        )

    # -- model artifacts (image-builder analogue, builder.go:98-218) ------
    async def h_artifact_build(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return fail("invalid JSON body", status=400)
        path = str(body.get("path", ""))
        if not path:
            return fail("'path' is required", status=400)
        doc = await asyncio.to_thread(
            self.s.artifacts.build, path, str(body.get("name", ""))
        )
        self._audit(request, "artifact-build", doc["name"], "success")
        return ok(doc, message="Artifact registered")

    async def h_artifact_list(self, request: web.Request) -> web.Response:
        return ok(await asyncio.to_thread(self.s.artifacts.list))

    async def h_artifact_remove(self, request: web.Request) -> web.Response:
        name = request.match_info["name"]
        removed = await asyncio.to_thread(self.s.artifacts.remove, name)
        if not removed:
            return fail(f"unknown artifact: {name}", status=404)
        self._audit(request, "artifact-remove", name, "success")
        return ok(message="Artifact removed")

    def _check_engine_auth(self, request: web.Request) -> str | None:
        """Validate a per-engine credential; returns the agent id or None."""
        agent_id = request.headers.get("X-Agentainer-Agent-ID", "")
        presented = request.headers.get("Authorization", "").removeprefix("Bearer ").strip()
        expected = self.s.store.get(Keys.internal_token(agent_id)) if agent_id else None
        import hmac as _hmac

        if not agent_id or expected is None or not _hmac.compare_digest(
            presented.encode(), expected
        ):
            return None
        return agent_id

    async def h_engine_ready(self, request: web.Request) -> web.Response:
        """Engine → control plane: "my model finished loading, serve me."

        Event-drives the replay drain (VERDICT r4 item 4): a respawned
        engine's queued requests replay the moment the model is servable
        instead of waiting out the 5s scan cadence — most of what stood
        between the reference's ~1s container restart and our recovery time
        once compile caching removed the recompile cost."""
        agent_id = self._check_engine_auth(request)
        if agent_id is None:
            return fail("invalid engine credentials", status=401)
        if self.s.quick_sync is not None:
            # refresh the record first so the replay pass sees RUNNING
            await asyncio.to_thread(self.s.quick_sync.sync_agent, agent_id)
        if self.s.replay is not None:
            self.s.replay.kick()
        self.s.logs.info("engine", f"agent {agent_id} reports model ready")
        return ok({"kicked": True})

    # -- fault-injection plane (docs/RESILIENCE.md §Fault injection) ------
    async def h_faults_get(self, request: web.Request) -> web.Response:
        return ok(
            {
                "active": faults.active(),
                "store_breaker": self._store_breaker.stats(),
                "journal_errors_total": self.journal_errors_total,
                "journal_skipped_total": self.journal_skipped_total,
                "abort_cancel_errors_total": self.abort_cancel_errors_total,
                "tier_parks_total": self.tier_parks_total,
                "tier_park_failures_total": self.tier_park_failures_total,
                "tier_prewarms_total": self.tier_prewarms_total,
                "tier_parked_sessions": len(self._tier_parked),
                "stream_requests_total": self.stream_requests_total,
                "stream_events_total": self.stream_events_total,
                "stream_failovers_total": self.stream_failovers_total,
                "stream_dup_suppressed_total": self.stream_dup_suppressed_total,
                "stream_client_disconnects_total": self.stream_client_disconnects_total,
                "stream_write_errors_total": self.stream_write_errors_total,
            }
        )

    async def h_faults_post(self, request: web.Request) -> web.Response:
        """Arm/disarm failpoints at runtime (admin bearer token).

        Body: ``{"arm": "<spec string>"}`` or ``{"arm": [{name, error,
        delay_ms, probability, count, seed}, ...]}``, ``{"disarm":
        ["name", ...]}``, ``{"disarm_all": true}`` — combinable; disarms
        apply first so one call can replace a schedule atomically."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return fail("invalid JSON body", status=400)
        armed: list[str] = []
        disarmed: list[str] = []
        try:
            if body.get("disarm_all"):
                disarmed = [fp["name"] for fp in faults.active()]
                faults.disarm_all()
            for name in body.get("disarm", []) or []:
                if faults.disarm(str(name)):
                    disarmed.append(str(name))
            spec = body.get("arm")
            if isinstance(spec, str) and spec:
                armed += faults.arm_spec(spec)
            elif isinstance(spec, list):
                for kw in spec:
                    if not isinstance(kw, dict) or "name" not in kw:
                        return fail("each arm entry needs a 'name'", status=400)
                    faults.arm(**{k: v for k, v in kw.items()})
                    armed.append(kw["name"])
        except (TypeError, ValueError) as e:
            return fail(f"bad failpoint spec: {e}", status=400)
        self._audit(
            request,
            "faults",
            f"arm={','.join(armed) or '-'} disarm={','.join(disarmed) or '-'}",
            "success",
        )
        return ok({"armed": armed, "disarmed": disarmed, "active": faults.active()})

    # -- internal store API for engine subprocesses -----------------------
    async def h_internal_store(self, request: web.Request) -> web.Response:
        """Store access for engine processes.

        The reference's agents talk to Redis directly over the Docker bridge
        (examples/gpt-agent/app.py:20-27); here engines reach the daemon's
        store through this endpoint. Each engine authenticates with its own
        per-engine token (minted at engine creation, never the admin token)
        and is namespaced to its agent's ``agent:{id}:*`` keys, so one agent
        can neither read another's state nor call the management API.
        """
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return fail("invalid JSON", status=400)
        agent_id = self._check_engine_auth(request)
        if agent_id is None:
            return fail("invalid engine credentials", status=401)
        store = self.s.store
        ns = f"agent:{agent_id}:"
        if body.get("op") == "pipeline":
            # one round-trip for a batch of ops — the engine's per-chat
            # conversation bookkeeping is 3-4 ops and used to cost 4 HTTP
            # round-trips against the daemon loop. The whole batch is
            # validated before anything executes so a rejected batch never
            # partially applies.
            ops = body.get("ops")
            if not isinstance(ops, list) or not all(isinstance(o, dict) for o in ops):
                return fail("pipeline ops must be a list of objects", status=400)
            for sub in ops:
                if not str(sub.get("key", "")).startswith(ns):
                    return fail("key outside agent namespace", status=403)
                if sub.get("op") not in _STORE_OPS:
                    return fail(f"unknown op {sub.get('op')!r}", status=400)
                pat = sub.get("pattern")
                if pat is not None and not str(pat).startswith(ns):
                    return fail("pattern outside agent namespace", status=403)
            try:
                return ok([self._store_op(store, ns, sub) for sub in ops])
            except (TypeError, ValueError) as e:
                return fail(str(e), status=400)
        op = body.get("op", "")
        key = body.get("key", "")
        if not key.startswith(ns):
            return fail("key outside agent namespace", status=403)
        if op == "keys" and not str(body.get("pattern", key + "*")).startswith(ns):
            return fail("pattern outside agent namespace", status=403)
        try:
            return ok(self._store_op(store, ns, body))
        except (TypeError, ValueError) as e:
            return fail(str(e), status=400)

    @staticmethod
    def _store_op(store, ns: str, body: dict):
        """Execute one namespace-checked store op; raises ValueError on bad
        input. Callers enforce key/pattern namespacing before execution."""
        op = body.get("op", "")
        key = body.get("key", "")
        if op == "get":
            raw = store.get(key)
            return None if raw is None else raw.decode("utf-8", "replace")
        if op == "set":
            store.set(key, body.get("value", ""), ttl=body.get("ttl"))
            return None
        if op == "set_b64":
            import base64 as _b64

            store.set(key, _b64.b64decode(body.get("value_b64", "")), ttl=body.get("ttl"))
            return None
        if op == "get_b64":
            import base64 as _b64

            raw = store.get(key)
            return None if raw is None else _b64.b64encode(raw).decode()
        if op == "delete":
            return store.delete(key)
        if op == "expire":
            return int(store.expire(key, float(body.get("ttl", 0))))
        if op == "rpush":
            return store.rpush(key, *[v for v in body.get("values", [])])
        if op == "lrange":
            return store.lrange_str(key, body.get("start", 0), body.get("stop", -1))
        if op == "ltrim":
            store.ltrim(key, body.get("start", 0), body.get("stop", -1))
            return None
        if op == "llen":
            return store.llen(key)
        if op == "hincrby":
            return store.hincrby(key, body.get("field", ""), body.get("amount", 1))
        if op == "hgetall":
            return {k: v.decode("utf-8", "replace") for k, v in store.hgetall(key).items()}
        if op == "keys":
            return store.keys(body.get("pattern", key + "*"))
        raise ValueError(f"unknown op {op!r}")

    # -- backups ---------------------------------------------------------
    async def h_backup_create(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            body = {}
        backup = await self._mgr(
            self.s.backups.create, body.get("name", ""), body.get("description", "")
        )
        self._audit(request, "backup-create", backup["id"], "success")
        return ok(backup, message="Backup created")

    async def h_backup_list(self, request: web.Request) -> web.Response:
        return ok(await self._mgr(self.s.backups.list))

    async def h_backup_restore(self, request: web.Request) -> web.Response:
        backup_id = request.match_info["backup_id"]
        restored = await self._mgr(self.s.backups.restore, backup_id)
        self._audit(request, "backup-restore", backup_id, "success")
        return ok(restored, message="Backup restored")

    async def h_backup_export(self, request: web.Request) -> web.StreamResponse:
        """Bundle one backup into a portable tar.gz (manager.go:397-456
        parity) and STREAM the bytes to the caller — the archive lands on
        the client's machine, and the daemon never writes a client-chosen
        server-side path."""
        backup_id = request.match_info["backup_id"]
        exported = await self._mgr(self.s.backups.export, backup_id)
        try:
            self._audit(request, "backup-export", backup_id, "success")
            # stream in chunks off the event loop and delete the one-shot
            # artifact afterwards — exports must not accumulate on disk
            # (abandoned artifacts from cancelled exports are swept by
            # BackupManager.export itself)
            resp = web.StreamResponse(
                headers={
                    "Content-Type": "application/gzip",
                    "Content-Disposition": f'attachment; filename="{backup_id}.tar.gz"',
                    "Content-Length": str(exported.stat().st_size),
                }
            )
            await resp.prepare(request)
            with exported.open("rb") as f:
                while chunk := await asyncio.to_thread(f.read, 1 << 20):
                    await resp.write(chunk)
            await resp.write_eof()
        finally:
            exported.unlink(missing_ok=True)
        return resp

    async def h_backup_delete(self, request: web.Request) -> web.Response:
        backup_id = request.match_info["backup_id"]
        await self._mgr(self.s.backups.delete, backup_id)
        self._audit(request, "backup-delete", backup_id, "success")
        return ok(message="Backup deleted")

    # -- the proxy data path (server.go:493-615) -------------------------
    async def h_proxy(self, request: web.Request) -> web.Response:
        agent_id = request.match_info["agent_id"]
        tail = request.match_info.get("tail", "")
        path = "/" + tail if not tail.startswith("/") else tail
        if request.query_string:
            path = f"{path}?{request.query_string}"
        body = await request.read()
        accepted_ns = time.time_ns()  # read, not yet journaled
        headers = {
            k: v
            for k, v in request.headers.items()
            # the accept stamp is this proxy's alone to set
            if k.lower() not in _HOP_BY_HOP and k.lower() != ACCEPTED_NS_HEADER.lower()
        }

        try:
            agent = self.s.manager.get_agent(agent_id)
        except AgentNotFound:
            return fail(f"agent not found: {agent_id}", status=404)

        # The reference trusts X-Agentainer-Replay/-Request-ID from the
        # network because its replay worker re-enters the proxy over HTTP
        # (replay_worker.go:120-163) — which also lets any caller skip
        # journaling or settle someone else's pending entry. Our replay
        # dispatches in-process, so these headers are stripped as pure
        # attack surface.
        headers.pop(REPLAY_HEADER, None)
        headers.pop(REQUEST_ID_HEADER, None)

        # SSE streaming opt-in (features.streaming AND {"stream": true} in
        # the chat body). A client RECONNECT after a dropped stream carries
        # Last-Event-ID (the highest offset it holds) plus the request id
        # it was issued: that pair re-attaches to the SAME journal entry —
        # no new journal write, no new generation (the engine memo-replays
        # the deterministic sequence; the proxy skips offsets <= the
        # floor). The echoed id is only ever used to splice a stream,
        # never to settle an entry or skip journaling of fresh work.
        stream = self._wants_stream(path, body)
        resume_rid = ""
        if stream and request.headers.get(LAST_EVENT_ID_HEADER, ""):
            resume_rid = request.headers.get(REQUEST_ID_HEADER, "").strip()

        # Per-request deadline: an explicit header always sticks; the config
        # default applies ONLY when the agent is up to serve synchronously.
        # A request accepted with 202 "queued for replay" keeps the
        # replay-forever contract unless the caller opted into a deadline —
        # a silent 30 s default would dead-letter every fire-and-forget
        # request the moment an outage outlasts it.
        dl = self.s.config.deadlines
        deadline_at = None
        if dl.enabled:
            raw = request.headers.get(DEADLINE_HEADER, "")
            ms = 0.0
            if raw:
                try:
                    ms = float(raw)
                except (TypeError, ValueError):
                    ms = 0.0
            elif agent.status == AgentStatus.RUNNING:
                ms = dl.default_ms
            if ms > 0:
                deadline_at = time.time() + ms / 1000.0

        request_id = ""
        persist = self.s.config.features.request_persistence
        if persist:
            if dl.enabled:
                # overload shedding BEFORE journaling: queueing work beyond
                # the watermark only manufactures entries that expire
                # unserved — a fast 429 + Retry-After lets a well-behaved
                # caller back off while under-watermark traffic still gets
                # its 202/200
                try:
                    reason = self._shed_reason(agent_id, dl)
                except Exception:
                    # depth accounting is store-backed: during a blip,
                    # admit rather than shed on unknowable depths
                    self._store_breaker.fail()
                    reason = ""
                if reason:
                    self.s.metrics.count_shed(agent_id)
                    return fail(
                        f"overloaded: {reason}; retry later",
                        status=429,
                        headers={
                            "Retry-After": str(
                                retry_after_jitter(dl.retry_after_s, self._retry_rng)
                            )
                        },
                    )
            # Journal behind the store circuit breaker: with the store dark
            # the proxy must not stack a timeout per request. Degradation
            # ladder: breaker open or journaling failing → a RUNNING agent
            # still serves (without durability, counted + logged); an agent
            # that is down cannot honor the 202 queue-for-replay contract,
            # so the caller gets a fast 503 + Retry-After instead of a 202
            # whose entry was never durably written.
            if not self._store_breaker.allow():
                self.journal_skipped_total += 1
            elif resume_rid:
                # stream resume: the entry is already journaled under the
                # id the client echoed back — re-journaling would fork it
                request_id = resume_rid
            else:
                try:
                    journaled = self.s.journal.store_request(
                        agent_id,
                        request.method,
                        path,
                        headers,
                        body,
                        deadline_at=deadline_at,
                    )
                    self._store_breaker.ok()
                    request_id = journaled.id
                except Exception as e:
                    self._store_breaker.fail()
                    self.journal_errors_total += 1
                    self.journal_skipped_total += 1
                    try:
                        self.s.logs.warn(
                            "proxy",
                            f"journaling failed for {agent_id} "
                            f"({type(e).__name__}: {e}); serving without durability",
                            agent_id=agent_id,
                        )
                    except Exception:
                        pass  # the log plane rides the same store

        if agent.status != AgentStatus.RUNNING:
            if persist and request_id:
                # "agent down → 202 + queue for replay" (server.go:525-541)
                return ok(
                    {"request_id": request_id, "status": "pending"},
                    message="Agent is not running. Request queued and will be "
                    "replayed when the agent is back.",
                    status=202,
                )
            if persist:
                return fail(
                    "store unavailable; request cannot be queued for replay",
                    status=503,
                    headers={
                        "Retry-After": str(
                            retry_after_jitter(
                                self._store_breaker.cooldown_s, self._retry_rng
                            )
                        )
                    },
                )
            return fail("agent is not running", status=503)

        # the engine measures its distance to this stamp: the journal
        # layer's dispatch time. The journaled headers carry none, so a
        # dispatch of the replay worker is not sampled
        headers = {**headers, ACCEPTED_NS_HEADER: str(accepted_ns)}

        if self._tier_enabled() and path.startswith("/chat"):
            # returning turn: fire the prewarm hint BEFORE the chat dispatch
            # so the engine's host→device swap-in overlaps this request's
            # own queue wait (the TTFT admission phase hides the restore)
            self._tier_on_arrival(agent_id, self._session_hint(body) or "default")

        if stream:
            return await self._proxy_stream(
                request,
                agent,
                path,
                headers,
                body,
                request_id=request_id,
                deadline_at=deadline_at,
            )

        dispatch = asyncio.ensure_future(
            self.dispatch_to_agent(
                agent_id,
                request.method,
                path,
                headers,
                body,
                request_id=request_id,
                deadline_at=deadline_at,
            )
        )
        if dl.enabled:
            # watch the CLIENT while the engine works: a caller that hangs
            # up mid-dispatch gets its abort propagated — the engine stops
            # decoding for nobody and the journal entry dead-letters
            # instead of replaying work with no waiter
            while True:
                done, _ = await asyncio.wait({dispatch}, timeout=0.25)
                if done:
                    break
                transport = request.transport
                if transport is None or transport.is_closing():
                    dispatch.cancel()
                    await self._abort_dispatch(agent_id, request_id)
                    # nobody reads this; it closes the handler cleanly
                    return web.Response(status=499, reason="Client Closed Request")
        status, resp_headers, resp_body = await dispatch
        # error envelopes for JOURNALED dispatches carry the request id too:
        # a 502/504 is not the end of the story — the entry stays in the
        # journal (pending replay, or retry-accounted), and the id lets the
        # caller poll /agents/{id}/requests/{rid} for the eventual outcome
        # (a mid-decode replica death settles the SAME id on a survivor)
        rid_headers = {REQUEST_ID_HEADER: request_id} if request_id else None
        if status == DISPATCH_ENGINE_GONE:
            # connection-level failure: the crash heuristic leaves the request
            # pending for the replay worker (server.go:597-606)
            return fail(
                "agent unreachable; request left pending for replay",
                status=502,
                headers=rid_headers,
            )
        if status == DISPATCH_FAILED:
            # non-crash failure (timeout, protocol error): retry accounting
            # ran; the entry dead-letters after MAX_RETRIES
            return fail(
                "agent request failed; retry recorded",
                status=504,
                headers=rid_headers,
            )
        if status == DISPATCH_EXPIRED:
            return fail(
                "deadline exceeded; request dead-lettered",
                status=504,
                headers=rid_headers,
            )
        if status == DISPATCH_IN_FLIGHT:
            # an in-process replay tick CAS-claimed the freshly journaled
            # entry first (it scans whenever the agent has anything
            # pending). The work IS running and settles into the journal —
            # serve the winner's archived result instead of erroring a
            # live caller on a benign race.
            archived = await self._await_archived(agent_id, request_id, deadline_at)
            if archived is not None:
                return archived
            return fail("request already being dispatched", status=409)
        out_headers = {
            k: v
            for k, v in resp_headers.items()
            if k.lower() not in _HOP_BY_HOP and k.lower() != "content-type"
        }
        if request_id:
            # span continuity: the journal id IS the trace span — the caller
            # can correlate its response with /agents/{id}/requests and the
            # engine's own logs (SURVEY §5.1 tracing requirement)
            out_headers[REQUEST_ID_HEADER] = request_id
        if self._tier_enabled() and status == 200 and path.startswith("/chat"):
            # turn settled: park after the linger window unless the session
            # speaks again first (tool-call gaps cancel the pending park)
            self._tier_schedule_park(agent_id, self._session_hint(body) or "default")
        return web.Response(
            status=status,
            body=resp_body,
            headers=out_headers,
            content_type=(resp_headers.get("Content-Type", "application/octet-stream").split(";")[0]),
        )

    def _shed_reason(self, agent_id: str, dl) -> str:
        """Why this request should be shed right now, or "" to admit.
        Three watermarks: per-agent pending depth (O(1) llen), the global
        pending ceiling, and the engine's own queue+waiting depth from its
        latest metrics sample (no per-request engine round-trip)."""
        j = self.s.journal
        if dl.shed_pending_per_agent and j.pending_depth(agent_id) >= dl.shed_pending_per_agent:
            # the O(1) llen may be counting entries whose deadline already
            # passed — a STOPPED agent gets no replay sweep, so an outage
            # queue full of corpses would shed live replay-forever traffic
            # for the whole outage. Sweep (pending() dead-letters expired
            # entries) and recount before deciding; only runs at/over the
            # watermark, so the hot path stays O(1).
            if len(j.pending(agent_id)) >= dl.shed_pending_per_agent:
                return f"agent pending depth >= {dl.shed_pending_per_agent}"
            self._global_pending_cache = (0.0, 0)  # the sweep moved depths
        if dl.shed_pending_global:
            now = time.monotonic()
            expires, total = self._global_pending_cache
            if now >= expires:
                total = j.total_pending()
                self._global_pending_cache = (now + 0.25, total)
            if total >= dl.shed_pending_global:
                return f"global pending depth >= {dl.shed_pending_global}"
        if dl.engine_queue_watermark:
            engine = (self.s.metrics.current(agent_id) or {}).get("engine") or {}
            depth = (engine.get("queue_depth") or 0) + (engine.get("waiting_depth") or 0)
            if depth >= dl.engine_queue_watermark:
                return f"engine queue depth {depth} >= {dl.engine_queue_watermark}"
        return ""

    def _journal_op(self, fn, *args, **kw):
        """Best-effort journal settlement: a store blip mid-settle must not
        turn an already-served engine response into a 500. The entry stays
        in its previous state (usually PROCESSING); the replay worker's
        staleness reclaim repairs it, and the engine's idempotency memo
        guarantees the eventual re-dispatch cannot execute twice."""
        try:
            result = fn(*args, **kw)
            self._store_breaker.ok()
            return result
        except Exception as e:
            self._store_breaker.fail()
            self.journal_errors_total += 1
            try:
                self.s.logs.warn(
                    "proxy",
                    f"journal settle {getattr(fn, '__name__', fn)!s} failed: "
                    f"{type(e).__name__}: {e}",
                )
            except Exception:
                pass  # the log plane is store-backed too
            return None

    async def _abort_dispatch(self, agent_id: str, request_id: str) -> None:
        """Client disconnected mid-dispatch: dead-letter the journal entry
        (no waiter → replaying it is waste) and tell the engine to stop
        generating for it. Best effort on both counts."""
        if request_id:
            # a failed dead-letter leaves the entry PROCESSING — replay's
            # staleness reclaim re-dispatches work nobody awaits, so route
            # it through _journal_op (breaker + journal_errors_total + a
            # store-outage-safe warn) instead of the old silent swallow
            self._journal_op(
                self.s.journal.mark_expired,
                agent_id,
                request_id,
                reason="client disconnected",
            )
        try:
            agent = self.s.manager.get_agent(agent_id)
            endpoint = self.s.manager.endpoint(agent)
            if endpoint and request_id:
                await self._cancel_on_engine(endpoint, request_id)
        except Exception as e:
            # cancel is advisory (a dead engine makes it moot) but the lane
            # keeps decoding for a vanished caller when this fails — count it
            self.abort_cancel_errors_total += 1
            try:
                self.s.logs.warn(
                    "proxy",
                    f"engine cancel failed for {agent_id}/{request_id}: "
                    f"{type(e).__name__}: {e}",
                )
            except Exception:
                pass  # the log plane is store-backed too
        self.s.logs.info(
            "proxy", f"aborted dispatch {request_id or '<unjournaled>'} for {agent_id}: client disconnected"
        )

    async def dispatch_to_agent(
        self,
        agent_id: str,
        method: str,
        path: str,
        headers: dict[str, str],
        body: bytes,
        request_id: str = "",
        deadline_at: float | None = None,
        force: bool = False,
        session_hint: str = "",
    ) -> tuple[int, dict[str, str], bytes]:
        """Forward to the engine and settle the journal entry.

        Outcome classification mirrors the reference's interceptTransport
        (server.go:583-615) with the journal entry's lifecycle made explicit:

        - before dispatch the entry's pending→processing transition is
          CLAIMED with a store compare-and-set: of two racing dispatchers
          (proxy + replay tick) exactly one wins; the loser returns
          DISPATCH_IN_FLIGHT without forwarding anything. ``force`` skips
          the claim (manual replay of already-settled entries);
        - a deadline already passed → mark_expired, DISPATCH_EXPIRED — the
          engine never sees work nobody is waiting for;
        - success → COMPLETED with the archived response;
        - connection-level failure (engine gone ↔ connection refused) →
          back to PENDING, no retry charged; returns DISPATCH_ENGINE_GONE;
        - timeout / protocol error → retry-count++ via mark_failed (dead-
          letters after MAX_RETRIES); returns DISPATCH_FAILED. The reference
          misclassifies slow responses as crashes, replaying them forever.

        Fleet (agent has >1 replica): the routing tier picks the replica
        (session affinity → health exclusion → power-of-two-choices), and
        a connection-level failure retries on the NEXT replica, bounded by
        ``fleet.retry_next_replica``. The retry re-forwards the SAME claim:
        nothing executed on the dead replica (connection refused/reset
        before a response), the CAS admitted exactly this dispatcher, and
        the engine memoizes by request id — so cross-replica retry cannot
        double-execute. Single-replica agents never enter the router.
        """
        agent = self.s.manager.get_agent(agent_id)
        multi = len(agent.all_engine_ids()) > 1
        if multi:
            if not session_hint:
                # session-affinity hint: chat-style bodies name their
                # session. Parsed HERE (not in h_proxy) so every dispatcher
                # — live proxy, replay worker re-dispatch, manual replay —
                # pins the session to the replica that actually serves it;
                # a failed-over session's next turn then follows the
                # survivor instead of racing the dead replica's respawn.
                # Single-replica agents never pay this parse.
                session_hint = self._session_hint(body)
            choice = self.router.pick(agent, session=session_hint)
        else:
            endpoint = self.s.manager.endpoint(agent)
            choice = (
                None
                if endpoint is None
                else ReplicaChoice(agent.engine_id, endpoint)
            )
        if choice is None:
            return DISPATCH_ENGINE_GONE, {}, b""
        if deadline_at is not None and time.time() > deadline_at:
            if request_id:
                self._journal_op(
                    self.s.journal.mark_expired,
                    agent_id,
                    request_id,
                    reason="deadline exceeded",
                )
            return DISPATCH_EXPIRED, {}, b""
        if request_id:
            if force:
                self._journal_op(self.s.journal.mark_processing, agent_id, request_id)
            else:
                try:
                    claimed = self.s.journal.acquire_processing(
                        agent_id, request_id, replica_id=choice.engine_id
                    )
                except Exception:
                    # can't verify the claim with the store dark — another
                    # dispatcher may own the entry, so do NOT forward: the
                    # entry replays when the store returns (durability over
                    # latency; no double execution)
                    self._store_breaker.fail()
                    self.journal_errors_total += 1
                    claimed = False
                if not claimed:
                    return DISPATCH_IN_FLIGHT, {}, b""

        tried: set[str] = set()
        attempts = 0
        # bound by ATTEMPTS, not distinct replicas: a stale routing table
        # (router.pick failpoint) can hand the same dead replica back
        # twice, and that must consume the retry budget, not loop forever
        max_attempts = 1 + (self.router.retry_next_replica if multi else 0)
        while True:
            attempts += 1
            result = await self._dispatch_once(
                agent, choice, multi, method, path, headers, body,
                request_id, deadline_at,
            )
            if result is not None:
                return result
            # connection-level failure (or loading/draining): nothing ran
            # on that replica — eligible for the bounded next-replica retry
            tried.add(choice.engine_id)
            choice = None
            if multi and attempts < max_attempts:
                choice = self.router.pick(
                    agent, session=session_hint, exclude=frozenset(tried)
                )
            if choice is None:
                # every (allowed) replica refused at the connection level:
                # the crash heuristic leaves the request pending for the
                # replay worker (server.go:597-606)
                if request_id:
                    self._journal_op(
                        self.s.journal.mark_pending, agent_id, request_id
                    )
                return DISPATCH_ENGINE_GONE, {}, b""
            if request_id and not force:
                # re-attribute the claim to the replica this retry actually
                # forwards to: fleet repair reassigns by attribution, and a
                # stale one would reset work the NEW replica is executing
                # (or fail to reset work that died with it)
                self._journal_op(
                    self.s.journal.set_replica,
                    agent_id,
                    request_id,
                    choice.engine_id,
                )

    @staticmethod
    def _session_hint(body: bytes) -> str:
        if not body:
            return ""
        try:
            doc = json.loads(body)
            return str(doc.get("session", "") or "") if isinstance(doc, dict) else ""
        except (ValueError, UnicodeDecodeError):
            return ""

    # -- SSE streaming data path (features.streaming) ---------------------

    def _wants_stream(self, path: str, body: bytes) -> bool:
        """The streamed data path engages only when the feature flag is on
        AND the chat body opted in — stream=false (the default) must keep
        the buffered proxy byte-identical to the pre-streaming build."""
        if not bool(getattr(self.s.config.features, "streaming", False)):
            return False
        if not path.startswith("/chat"):
            return False
        if not body:
            return False
        try:
            doc = json.loads(body)
            return bool(doc.get("stream")) if isinstance(doc, dict) else False
        except (ValueError, UnicodeDecodeError):
            return False

    async def _proxy_stream(
        self,
        request: web.Request,
        agent,
        path: str,
        headers: dict[str, str],
        body: bytes,
        request_id: str = "",
        deadline_at: float | None = None,
    ) -> web.StreamResponse:
        """Streamed dispatch: forward the engine's SSE token stream to the
        client, journaling every offset as a streaming checkpoint BEFORE it
        goes on the wire (checkpoint-then-emit).

        The failure contract is the whole point:

        - **mid-stream upstream death** (replica SIGKILL, payload reset,
          injected ``proxy.stream_emit`` fault): nothing client-visible is
          lost — the cursor names the last acked offset, the next leg
          carries it as ``Last-Event-ID``, the survivor restores the
          session, memo/deterministically re-emits, and the serve layer
          skips every offset <= cursor. The client sees ONE gapless,
          duplicate-free sequence on ONE connection;
        - **duplicate emission** (replay-after-crash racing a live leg):
          ``journal.advance_stream`` CAS-rejects the second advance and the
          local cursor drops the event before the write;
        - **offset gap**: :class:`StreamGapError` — a hard error that
          truncates the stream; a silent skip would corrupt the splice;
        - **client disconnect**: the entry settles EXPIRED at the last
          acked offset and the engine's lane is cancelled (the streamed
          extension of the buffered abort path);
        - **non-stream upstream outcomes** (loading/draining 503, poisoned
          prefill 500, 429 shed) classify exactly like the buffered path.
        """
        agent_id = agent.id
        self.stream_requests_total += 1
        multi = len(agent.all_engine_ids()) > 1
        session_hint = self._session_hint(body)
        rid_headers = {REQUEST_ID_HEADER: request_id} if request_id else None
        # the client's splice floor: highest offset it already holds (a
        # reconnect sends its Last-Event-ID; a fresh stream starts at -1)
        floor = -1
        raw_floor = request.headers.get(LAST_EVENT_ID_HEADER, "")
        if raw_floor:
            try:
                floor = int(raw_floor)
            except (TypeError, ValueError):
                floor = -1
        resume = bool(raw_floor)

        if multi:
            choice = self.router.pick(agent, session=session_hint)
        else:
            endpoint = self.s.manager.endpoint(agent)
            choice = (
                None if endpoint is None else ReplicaChoice(agent.engine_id, endpoint)
            )
        if choice is None:
            return fail(
                "agent unreachable; request left pending for replay",
                status=502,
                headers=rid_headers,
            )
        if deadline_at is not None and time.time() > deadline_at:
            if request_id:
                self._journal_op(
                    self.s.journal.mark_expired,
                    agent_id,
                    request_id,
                    reason="deadline exceeded",
                )
            return fail(
                "deadline exceeded; request dead-lettered",
                status=504,
                headers=rid_headers,
            )
        if request_id and not resume:
            # same pending→processing CAS claim as the buffered path; a
            # resume re-attaches to an entry that is already PROCESSING or
            # COMPLETED (the engine memo replays it), so it skips the claim
            try:
                claimed = self.s.journal.acquire_processing(
                    agent_id, request_id, replica_id=choice.engine_id
                )
            except Exception:
                self._store_breaker.fail()
                self.journal_errors_total += 1
                claimed = False
            if not claimed:
                archived = await self._await_archived(agent_id, request_id, deadline_at)
                if archived is not None:
                    return archived
                return fail("request already being dispatched", status=409)

        import aiohttp
        from aiohttp import ClientTimeout as _CT

        state: dict = {"resp": None, "cursor": floor}
        t0 = time.monotonic()

        async def ensure_prepared() -> web.StreamResponse:
            if state["resp"] is None:
                r = web.StreamResponse(status=200)
                r.headers["Content-Type"] = STREAM_CONTENT_TYPE
                r.headers["Cache-Control"] = "no-cache"
                r.headers["X-Accel-Buffering"] = "no"
                if request_id:
                    # the resume credential: a reconnect echoes this id +
                    # its Last-Event-ID to re-splice the same entry
                    r.headers[REQUEST_ID_HEADER] = request_id
                await r.prepare(request)
                state["resp"] = r
            return state["resp"]

        async def client_write(payload: bytes) -> None:
            r = await ensure_prepared()
            try:
                await r.write(payload)
            except (ConnectionResetError, ConnectionError) as e:
                raise _StreamClientGone() from e

        def settle_plain(
            status: int, rheaders: dict[str, str], rbody: bytes
        ) -> tuple[str, web.Response | None]:
            """Engine answered but not with a stream: classify exactly like
            the buffered path, then serve the plain outcome."""
            if status == 503 and (
                rheaders.get(LOADING_HEADER, "").lower() == "true"
                or rheaders.get(DRAINING_HEADER, "").lower() == "true"
            ):
                return "retry", None
            if rheaders.get(EXPIRED_HEADER, "").lower() == "true":
                if request_id:
                    self._journal_op(
                        self.s.journal.mark_expired,
                        agent_id,
                        request_id,
                        reason="expired on engine",
                    )
                return "plain", fail(
                    "deadline exceeded; request dead-lettered",
                    status=504,
                    headers=rid_headers,
                )
            if status >= 500 and rheaders.get(PREFILL_POISON_HEADER, "").lower() == "true":
                # deterministic input fault on a healthy engine: charge
                # poison accounting instead of archiving the 500
                if request_id:
                    self._journal_op(
                        self.s.journal.mark_failed,
                        agent_id,
                        request_id,
                        f"prefill poisoned (HTTP {status})",
                        poison=True,
                    )
            elif status == 429:
                if request_id:
                    self._journal_op(self.s.journal.mark_pending, agent_id, request_id)
            elif request_id:
                self._journal_op(
                    self.s.journal.store_response,
                    agent_id,
                    request_id,
                    status,
                    rheaders,
                    rbody,
                )
            out = {
                k: v
                for k, v in rheaders.items()
                if k.lower() not in _HOP_BY_HOP and k.lower() != "content-type"
            }
            if request_id:
                out[REQUEST_ID_HEADER] = request_id
            return "plain", web.Response(
                status=status,
                body=rbody,
                headers=out,
                content_type=rheaders.get("Content-Type", "application/octet-stream").split(";")[0],
            )

        async def forward_frame(raw: bytes) -> web.StreamResponse | None:
            """Forward one upstream SSE block; returns the finished
            response on the terminal ``done`` event, else None."""
            event, eid, data = _parse_sse_frame(raw)
            if event == "":
                # keep-alive comment frame: forwarded verbatim, NEVER
                # advances the journaled offset
                await client_write(raw + b"\n\n")
                return None
            if event == STREAM_EVENT_TOKEN:
                off = eid if eid is not None else state["cursor"] + 1
                if off <= state["cursor"]:
                    # duplicate emission (overlapping failover legs / memo
                    # re-emit racing the splice): dropped before the wire
                    self.stream_dup_suppressed_total += 1
                    return None
                if off != state["cursor"] + 1:
                    raise StreamGapError(
                        f"stream splice gap for {agent_id}/{request_id or '<unjournaled>'}: "
                        f"acked={state['cursor']}, offered={off}"
                    )
                # proxy-side per-event failpoint: firing here models a
                # dispatch failure mid-stream — the cursor is NOT advanced,
                # so the failover leg re-offers exactly this offset
                await faults.fire_async("proxy.stream_emit")
                if request_id:
                    # checkpoint-then-emit: the journaled cursor is never
                    # behind what a FUTURE leg must skip. False = the
                    # offset was already journaled (a reconnect re-serving
                    # acked events below the journal cursor): still owed to
                    # THIS client, whose own floor admitted it.
                    try:
                        self.s.journal.advance_stream(agent_id, request_id, off)
                    except StreamGapError:
                        raise
                    except Exception:
                        # a store blip must not kill a live stream; the
                        # replay-side CAS still guards double emission
                        self._store_breaker.fail()
                        self.journal_errors_total += 1
                await client_write(raw + b"\n\n")
                state["cursor"] = off
                self.stream_events_total += 1
                return None
            if event == STREAM_EVENT_DONE:
                # archive the done payload as the entry's completed
                # response — byte-identical to what the buffered path
                # would have archived, so /requests/{rid} and replay
                # semantics don't fork on the streaming flag
                if request_id:
                    self._journal_op(
                        self.s.journal.store_response,
                        agent_id,
                        request_id,
                        200,
                        {"Content-Type": "application/json"},
                        bytes(data),
                    )
                await client_write(raw + b"\n\n")
                r = state["resp"]
                await r.write_eof()
                return r
            # unknown/error event: forward verbatim (forward-compat)
            await client_write(raw + b"\n\n")
            return None

        async def one_leg() -> tuple[str, web.StreamResponse | web.Response | None]:
            url = choice.endpoint.rstrip("/") + path
            fwd = dict(headers)
            fwd.pop("Authorization", None)
            fwd.pop(DEADLINE_HEADER, None)
            if request_id:
                fwd[REQUEST_ID_HEADER] = request_id
            if state["cursor"] >= 0:
                # the splice cursor: the engine serve layer re-emits its
                # deterministic sequence and skips offsets <= this value
                fwd[LAST_EVENT_ID_HEADER] = str(state["cursor"])
            else:
                fwd.pop(LAST_EVENT_ID_HEADER, None)
            if deadline_at is not None:
                remaining = deadline_at - time.time()
                fwd[DEADLINE_HEADER] = str(max(1, int(remaining * 1000)))
            # no total timeout: a healthy stream outlives any fixed budget
            # (engine heartbeats bound sock_read instead)
            timeout = _CT(total=None, sock_connect=10.0, sock_read=90.0)
            async with self._client.request(
                request.method,
                url,
                headers=fwd,
                data=body if body else None,
                timeout=timeout,
            ) as upstream:
                ctype = upstream.headers.get("Content-Type", "")
                if upstream.status != 200 or not ctype.startswith(STREAM_CONTENT_TYPE):
                    rbody = await upstream.read()
                    return settle_plain(upstream.status, dict(upstream.headers), rbody)
                buf = b""
                async for chunk in upstream.content.iter_any():
                    buf += chunk
                    while b"\n\n" in buf:
                        raw, buf = buf.split(b"\n\n", 1)
                        finished = await forward_frame(raw)
                        if finished is not None:
                            return "done", finished
                # upstream closed without a done event: mid-stream death
                return "retry", None

        tried: set[str] = set()
        attempts = 0
        max_attempts = 1 + (self.router.retry_next_replica if multi else 2)
        try:
            while True:
                attempts += 1
                if multi:
                    self.router.begin(choice.engine_id)
                replica_ok = False
                try:
                    kind, terminal = await one_leg()
                    replica_ok = True
                except (
                    aiohttp.ClientError,
                    ConnectionError,
                    asyncio.TimeoutError,
                    OSError,
                    faults.FaultInjected,
                ):
                    kind, terminal = "retry", None
                finally:
                    if multi:
                        self.router.end(choice.engine_id, replica_ok)
                if kind == "done":
                    self.s.metrics.count_request(
                        agent_id, latency_s=time.monotonic() - t0
                    )
                    if self._tier_enabled():
                        self._tier_schedule_park(agent_id, session_hint or "default")
                    return terminal
                if kind == "plain":
                    if state["resp"] is None:
                        return terminal
                    # already streaming and a failover leg settled plain:
                    # nothing splice-able is coming — truncate with an
                    # error frame (the journal settle already happened)
                    await self._stream_error_frame(
                        state, f"upstream settled non-stream (HTTP {terminal.status})"
                    )
                    return state["resp"]
                # retryable: the leg died with the cursor intact — fail
                # over and re-splice at last_acked_offset + 1
                tried.add(choice.engine_id)
                nxt = None
                if attempts < max_attempts:
                    if multi:
                        nxt = self.router.pick(
                            agent, session=session_hint, exclude=frozenset(tried)
                        )
                        if nxt is None:
                            # every survivor already tried: re-open the full
                            # set (a respawned replica may be back)
                            nxt = self.router.pick(agent, session=session_hint)
                    else:
                        await asyncio.sleep(0.5)
                        endpoint = self.s.manager.endpoint(agent)
                        nxt = (
                            None
                            if endpoint is None
                            else ReplicaChoice(agent.engine_id, endpoint)
                        )
                if nxt is None:
                    break
                choice = nxt
                if state["resp"] is not None or state["cursor"] > floor:
                    self.stream_failovers_total += 1
                if request_id:
                    self._journal_op(
                        self.s.journal.set_replica, agent_id, request_id, choice.engine_id
                    )
        except asyncio.CancelledError:
            # aiohttp cancels the handler when the consumer vanishes
            self.stream_client_disconnects_total += 1
            await self._abort_stream(agent_id, request_id, choice)
            raise
        except _StreamClientGone:
            self.stream_client_disconnects_total += 1
            await self._abort_stream(agent_id, request_id, choice)
            if state["resp"] is not None:
                return state["resp"]
            return web.Response(status=499, reason="Client Closed Request")
        except StreamGapError as e:
            # hard invariant break — never silently skipped. The entry is
            # left un-settled (PROCESSING): the replay reclaim re-serves it
            # buffered, where the archived response is whole-or-nothing.
            try:
                self.s.logs.error("proxy", f"stream gap on {agent_id}: {e}")
            except Exception:
                pass
            if state["resp"] is None:
                raise
            await self._stream_error_frame(state, str(e))
            return state["resp"]

        # every leg exhausted: the entry goes back to pending (replay will
        # settle it buffered) and the client may reconnect with
        # Last-Event-ID + the request id to re-splice what it is owed
        if request_id:
            self._journal_op(self.s.journal.mark_pending, agent_id, request_id)
        if state["resp"] is not None:
            await self._stream_error_frame(
                state, "upstream lost mid-stream; reconnect with Last-Event-ID to resume"
            )
            return state["resp"]
        return fail(
            "agent unreachable; request left pending for replay",
            status=502,
            headers=rid_headers,
        )

    async def _stream_error_frame(self, state: dict, message: str) -> None:
        """Best-effort terminal error frame + EOF on an already-started
        stream (a truncated stream with no ``done`` IS the failure signal;
        the frame just names the reason)."""
        r = state.get("resp")
        if r is None:
            return
        try:
            payload = json.dumps({"error": message}, separators=(",", ":"))
            await r.write(
                f"event: {STREAM_EVENT_ERROR}\ndata: {payload}\n\n".encode()
            )
            await r.write_eof()
        except Exception:
            # the consumer is already gone; the frame just couldn't land
            self.stream_write_errors_total += 1

    async def _abort_stream(self, agent_id: str, request_id: str, choice) -> None:
        """Streamed client disconnect: settle the entry EXPIRED at the last
        acked offset (the stream cursor already journaled it) and cancel
        the engine lane on the replica actually serving the stream."""
        if request_id:
            self._journal_op(
                self.s.journal.mark_expired,
                agent_id,
                request_id,
                reason="client disconnected mid-stream",
            )
        try:
            if choice is not None and request_id:
                await self._cancel_on_engine(choice.endpoint, request_id)
        except Exception as e:
            self.abort_cancel_errors_total += 1
            try:
                self.s.logs.warn(
                    "proxy",
                    f"engine cancel failed for {agent_id}/{request_id}: "
                    f"{type(e).__name__}: {e}",
                )
            except Exception:
                pass

    # -- tiered-KV proxy policy (park on settle, prewarm on arrival) ------

    def _tier_enabled(self) -> bool:
        feats = getattr(self.s.config, "features", None)
        return bool(getattr(feats, "kv_tiering", False))

    def _tier_on_arrival(self, agent_id: str, session: str) -> None:
        """The conversation's next turn arrived: cancel any pending park
        (the linger did its job) and, when the session is parked, send the
        prewarm hint fire-and-forget so the engine's device swap-in runs
        concurrently with this request's own dispatch + queue wait."""
        key = (agent_id, session)
        task = self._tier_linger_tasks.pop(key, None)
        if task is not None:
            task.cancel()
        if key in self._tier_parked:
            self._tier_parked.discard(key)
            t = asyncio.ensure_future(self._tier_prewarm(agent_id, session))
            self._tier_bg.add(t)
            t.add_done_callback(self._tier_bg.discard)

    def _tier_schedule_park(self, agent_id: str, session: str) -> None:
        """Response complete: park the session after the linger window —
        agentic traffic's tool-call gap — unless it speaks again first."""
        key = (agent_id, session)
        old = self._tier_linger_tasks.pop(key, None)
        if old is not None:
            old.cancel()
        feats = getattr(self.s.config, "features", None)
        linger = float(getattr(feats, "tier_park_linger_s", 1.0) or 0.0)
        task = asyncio.ensure_future(self._tier_park_later(agent_id, session, linger))
        self._tier_linger_tasks[key] = task

        def _done(t, key=key):
            if self._tier_linger_tasks.get(key) is t:
                self._tier_linger_tasks.pop(key, None)

        task.add_done_callback(_done)

    async def _tier_park_later(self, agent_id: str, session: str, linger: float) -> None:
        try:
            if linger > 0:
                await asyncio.sleep(linger)
            status, _headers, rbody = await self.dispatch_to_agent(
                agent_id,
                "POST",
                "/park",
                {"Content-Type": "application/json"},
                json.dumps({"session": session}).encode(),
                session_hint=session,
            )
            parked = False
            if status == 200:
                try:
                    parked = bool(json.loads(rbody).get("parked"))
                except (ValueError, AttributeError, UnicodeDecodeError):
                    parked = False
            if parked:
                self._tier_parked.add((agent_id, session))
                self.tier_parks_total += 1
            else:
                self.tier_park_failures_total += 1
        except asyncio.CancelledError:
            raise  # the session spoke again; parking would be wrong now
        except Exception:
            # best-effort policy: a failed park only costs density, never
            # correctness — counted for the metrics surface
            self.tier_park_failures_total += 1

    async def _tier_prewarm(self, agent_id: str, session: str) -> None:
        try:
            await self.dispatch_to_agent(
                agent_id,
                "POST",
                "/prewarm",
                {"Content-Type": "application/json"},
                json.dumps({"session": session}).encode(),
                session_hint=session,
            )
            self.tier_prewarms_total += 1
        except Exception:
            # best-effort hint: the engine still promotes at admission
            self.tier_park_failures_total += 1

    async def _dispatch_once(
        self,
        agent,
        choice: ReplicaChoice,
        multi: bool,
        method: str,
        path: str,
        headers: dict[str, str],
        body: bytes,
        request_id: str,
        deadline_at: float | None,
    ) -> tuple[int, dict[str, str], bytes] | None:
        """One forwarding attempt against one replica. Returns the settled
        outcome tuple, or None for a connection-level failure / not-admitting
        503 (loading or draining) — the retryable class where nothing
        executed, with NO journal settle (the caller owns pending-vs-retry).
        Every other outcome settles the journal exactly as pre-fleet."""
        agent_id = agent.id
        endpoint = choice.endpoint
        if multi:
            self.router.begin(choice.engine_id)
        replica_ok = False
        try:
            if endpoint.startswith("fake://"):
                # in-process dispatch for the unit-test backend; the routed
                # engine id (not always the primary) receives the request
                handler = getattr(self.s.backend, "handle_request", None)
                if handler is None:
                    return None
                try:
                    faults.fire("proxy.dispatch")
                    status, resp_headers, resp_body = handler(
                        choice.engine_id or agent.engine_id,
                        method,
                        path,
                        headers,
                        body,
                    )
                except ConnectionError:
                    return None
                replica_ok = True
                if request_id:
                    self._journal_op(
                        self.s.journal.store_response,
                        agent_id,
                        request_id,
                        status,
                        resp_headers,
                        resp_body,
                    )
                self.s.metrics.count_request(agent_id)
                return status, resp_headers, resp_body
            result, replica_ok = await self._dispatch_http(
                agent_id, endpoint, method, path, headers, body,
                request_id, deadline_at,
            )
            return result
        finally:
            if multi:
                # per-replica breaker feed: anything that answered over the
                # socket is proof of life; connection-level failures and
                # timeouts count against THIS replica's breaker only
                self.router.end(choice.engine_id, replica_ok)

    async def _dispatch_http(
        self,
        agent_id: str,
        endpoint: str,
        method: str,
        path: str,
        headers: dict[str, str],
        body: bytes,
        request_id: str,
        deadline_at: float | None,
    ) -> tuple[tuple[int, dict[str, str], bytes] | None, bool]:
        """HTTP forwarding leg of ``_dispatch_once``; returns
        (outcome | None, replica_answered)."""
        url = endpoint.rstrip("/") + path
        fwd_headers = dict(headers)
        fwd_headers.pop("Authorization", None)
        # the journaled ORIGINAL deadline header must never leak through:
        # deadline_at is authoritative (a requeued entry has it cleared —
        # forwarding the stale client value would expire it all over again)
        fwd_headers.pop(DEADLINE_HEADER, None)
        if request_id:
            fwd_headers[REQUEST_ID_HEADER] = request_id
        # the wait follows the request, not the session's 30 s: a buffered
        # generation may take longer than that on a healthy engine
        timeout = ClientTimeout(total=DISPATCH_WAIT_S)
        if deadline_at is not None:
            # the engine sees the REMAINING budget, and the dispatch wait is
            # that budget: a shorter fixed wait abandoned the HTTP call while
            # the engine kept decoding, a longer one held a caller that was gone
            remaining = deadline_at - time.time()
            fwd_headers[DEADLINE_HEADER] = str(max(1, int(remaining * 1000)))
            timeout = ClientTimeout(total=max(0.1, remaining))
        t0 = time.monotonic()
        import aiohttp

        try:
            # failpoint: injected ConnectionError classifies as engine-gone
            # (crash heuristic), TimeoutError as retry-accounted failure,
            # delay_ms as a slow engine — the chaos soak drives all three
            await faults.fire_async("proxy.dispatch")
            async with self._client.request(
                method,
                url,
                headers=fwd_headers,
                data=body if body else None,
                timeout=timeout,
            ) as resp:
                resp_body = await resp.read()
                resp_headers = dict(resp.headers)
        except (aiohttp.ClientConnectorError, ConnectionError):
            # connection-level failure: retryable on another replica (the
            # caller owns the pending-vs-next-replica decision — nothing
            # executed here, so nothing is settled here)
            return None, False
        except (asyncio.TimeoutError, aiohttp.ClientError, OSError) as e:
            if deadline_at is not None and time.time() > deadline_at:
                # the wait ran out the caller's budget: dead-letter and tell
                # the engine to stop — a retry would also arrive too late
                if request_id:
                    self._journal_op(
                        self.s.journal.mark_expired,
                        agent_id,
                        request_id,
                        reason="deadline exceeded",
                    )
                    await self._cancel_on_engine(endpoint, request_id)
                return (DISPATCH_EXPIRED, {}, b""), False
            if request_id:
                self._journal_op(
                    self.s.journal.mark_failed,
                    agent_id,
                    request_id,
                    f"{type(e).__name__}: {e}",
                )
            return (DISPATCH_FAILED, {}, b""), False
        if resp.status == 503 and (
            resp_headers.get(LOADING_HEADER, "").lower() == "true"
            or resp_headers.get(DRAINING_HEADER, "").lower() == "true"
        ):
            # engine process is up but not admitting (model still loading,
            # or SIGTERM drain in progress): retryable like engine-gone —
            # single replica: stays pending for the replay worker; fleet:
            # another replica takes the dispatch right now
            return None, True
        if resp_headers.get(EXPIRED_HEADER, "").lower() == "true":
            # the engine dropped it by deadline policy: dead-letter, don't
            # archive a 504 as a completed response
            if request_id:
                self._journal_op(
                    self.s.journal.mark_expired,
                    agent_id,
                    request_id,
                    reason="expired on engine",
                )
            return (DISPATCH_EXPIRED, {}, b""), True
        if (
            resp.status >= 500
            and resp_headers.get(PREFILL_POISON_HEADER, "").lower() == "true"
        ):
            # the REQUEST itself breaks prefill on a healthy engine
            # (deterministic input fault, not a crash): archiving the 500
            # as COMPLETED would hide it; leaving it pending would replay
            # it forever. Poison accounting dead-letters it after
            # POISON_RETRIES strikes (~one replay tick), cutting the
            # repair MTTR from the full respawn/backoff ladder to ~1 s,
            # and the entry stays requeue-able for the operator.
            if request_id:
                self._journal_op(
                    self.s.journal.mark_failed,
                    agent_id,
                    request_id,
                    f"prefill poisoned (HTTP {resp.status})",
                    poison=True,
                )
            return (resp.status, resp_headers, resp_body), True
        if resp.status == 429:
            # engine-side shed: overload is transient — the entry goes back
            # to pending for a later replay tick (no retry charged; losing
            # journaled work to a load spike would break the durability
            # guarantee), while a live caller still sees the 429 +
            # Retry-After to back off on its own
            if request_id:
                self._journal_op(self.s.journal.mark_pending, agent_id, request_id)
            return (resp.status, resp_headers, resp_body), True
        if request_id:
            self._journal_op(
                self.s.journal.store_response,
                agent_id,
                request_id,
                resp.status,
                resp_headers,
                resp_body,
            )
        self.s.metrics.count_request(agent_id, latency_s=time.monotonic() - t0)
        return (resp.status, resp_headers, resp_body), True

    async def _await_archived(
        self, agent_id: str, request_id: str, deadline_at: float | None
    ) -> web.Response | None:
        """Wait for another dispatcher's settlement of a journal entry and
        serve its outcome: the archived response for COMPLETED, the matching
        error for FAILED/EXPIRED. None if it never settles in budget."""
        import base64 as _b64

        budget = 30.0 if deadline_at is None else max(0.5, deadline_at - time.time())
        end = time.monotonic() + min(30.0, budget)
        while time.monotonic() < end:
            try:
                req = self.s.journal.get(agent_id, request_id)
            except Exception:
                # the store died between journaling and here: answer fast
                # with the degradation contract instead of surfacing a 500
                # (the entry is durably journaled — it replays when the
                # store returns)
                self._store_breaker.fail()
                self.journal_errors_total += 1
                return fail(
                    "store unavailable; request state unknown, will replay",
                    status=503,
                    headers={
                        "Retry-After": str(
                            retry_after_jitter(
                                self._store_breaker.cooldown_s, self._retry_rng
                            )
                        )
                    },
                )
            if req is None:
                return None
            if req.status == RequestStatus.COMPLETED and req.response:
                r = req.response
                body = _b64.b64decode(r["body_b64"]) if r.get("body_b64") else b""
                stored = dict(r.get("headers", {}))
                out = {
                    k: v
                    for k, v in stored.items()
                    if k.lower() not in _HOP_BY_HOP and k.lower() != "content-type"
                }
                out[REQUEST_ID_HEADER] = request_id
                return web.Response(
                    status=r.get("status_code", 200),
                    body=body,
                    headers=out,
                    content_type=stored.get(
                        "Content-Type", "application/octet-stream"
                    ).split(";")[0],
                )
            if req.status == RequestStatus.EXPIRED:
                return fail("deadline exceeded; request dead-lettered", status=504)
            if req.status == RequestStatus.FAILED:
                return fail("agent request failed; retry recorded", status=504)
            await asyncio.sleep(0.05)
        return None

    async def _cancel_on_engine(self, endpoint: str, request_id: str) -> None:
        """Best-effort engine-side abort for a request whose waiter is gone."""
        if not endpoint.startswith("http"):
            return
        try:
            from aiohttp import ClientTimeout as _CT

            async with self._client.post(
                endpoint.rstrip("/") + "/cancel",
                json={"request_id": request_id},
                timeout=_CT(total=2.0),
            ) as resp:
                await resp.read()
        except Exception:
            # cancel is advisory (a dead engine makes it moot) but the lane
            # keeps decoding for a vanished caller when this fails — count it
            self.abort_cancel_errors_total += 1


def create_app(services: "Services") -> web.Application:
    return ControlPlaneApp(services).app
