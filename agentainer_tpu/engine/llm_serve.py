"""LLM engine subprocess — serves a JAX prefill+decode engine over the same
HTTP contract as the echo engine (and the reference's example agents,
examples/gpt-agent/app.py:32-179): /chat /health /history /clear /metrics.

The serving stack inside this process:

    aiohttp handlers → continuous-batching scheduler (engine/llm.py)
        → JAX model (models/llama.py; MoE configs via cfg.is_moe) on the
          chips assigned by the slice scheduler (AGENTAINER_CHIPS)

Conversation turns persist through the control plane's store (crash-durable);
the KV-cache can be checkpointed there too (engine/checkpoint.py) so a
restarted engine resumes mid-conversation — BASELINE.json config #3.
"""

from __future__ import annotations

import asyncio
import collections
import json
import os
import time

from aiohttp import web

from ..runtime.store_client import StoreClient
from ..utils.boot import BootTimeline
from ..utils.compile_cache import CompileCacheStats, compile_cache_dir, enable_compile_cache

MAX_TURNS = 50
# layer-steps (passes through the model × its layers) a /profile capture holds
# at most: stop_trace collects about 100 of them a second (h_profile)
PROFILE_LAYER_STEPS = 3_000
# per-session conversation lists carry a sliding TTL: session ids are
# client-supplied, so without one every ephemeral session would leave a
# permanent (ltrim-bounded) list behind — the old shared key was bounded
# in TOTAL size, the per-session split must be bounded in key count too
SESSION_CONVO_TTL_S = 7 * 24 * 3600
# proxy ↔ engine wire headers: single definition site shared with the
# control plane (core/protocol.py) — re-exported for existing importers
from ..core.protocol import (  # noqa: E402, F401  (re-export)
    ACCEPTED_NS_HEADER,
    DEADLINE_HEADER,
    DRAINING_HEADER,
    EXPIRED_HEADER,
    LAST_EVENT_ID_HEADER,
    LOADING_HEADER,
    PREFILL_POISON_HEADER,
    STREAM_CONTENT_TYPE,
    STREAM_EVENT_DONE,
    STREAM_EVENT_TOKEN,
)
from .. import faults  # noqa: E402


def _sse_frame(event: str, event_id: int | None, data: dict) -> bytes:
    """One SSE frame: optional ``id:`` (token offset — doubles as the
    client's Last-Event-ID resume cursor), ``event:``, one-line data."""
    lines = [f"event: {event}"]
    if event_id is not None:
        lines.append(f"id: {event_id}")
    lines.append(f"data: {json.dumps(data, separators=(',', ':'))}")
    return ("\n".join(lines) + "\n\n").encode()


class LLMServeApp:
    """One agent's serving surface.

    Normally one per process (env-configured). Under the multi-tenant model
    host (``AGENTAINER_MULTI_TENANT=1``) several instances share ONE process
    and ONE ``LLMEngine`` — one weight copy in HBM for N agents
    (BASELINE.json config #4; VERDICT r4 item 5: separate processes can
    neither share HBM nor even co-open a TPU chip). The host instance owns
    the engine; tenants are attached at runtime via ``/-/tenants`` and
    delegate ``engine``/readiness to the host while keeping their own
    identity: store credentials, conversation keys, KV snapshots, metrics
    counters, persona. Engine sessions are namespaced ``{agent_id}::{sess}``
    so tenants can never touch each other's KV slots.
    """

    def __init__(
        self,
        env: dict | None = None,
        host: "LLMServeApp | None" = None,
        compile_stats: CompileCacheStats | None = None,
        boot: BootTimeline | None = None,
    ) -> None:
        E = os.environ if env is None else env
        self._host = host
        self._engine = None
        self._engine_error = ""
        # persistent-compile-cache counters of this process (serve() turns
        # the cache on); None when embedded in a process that did not
        self._compile_stats = compile_stats
        # the boot's timeline, begun by engine_main for a spawned host and
        # here for an embedded one; a tenant's boot is its host's
        if boot is None and host is None:
            boot = BootTimeline()
        if boot is not None:
            boot.compile_stats = compile_stats
        self._boot = boot
        self.engine_load_s: float | None = None
        self.warmup_skipped = False
        self.agent_id = E.get("AGENTAINER_AGENT_ID", "standalone")
        self.agent_name = E.get("AGENTAINER_AGENT_NAME", self.agent_id)
        self.config_name = E.get("AGENTAINER_MODEL_CONFIG", "tiny")
        self.checkpoint = E.get("AGENTAINER_CHECKPOINT", "")
        self.system_prompt = E.get("AGENTAINER_SYSTEM_PROMPT", "")
        # "assistant" flavor: the reference's SECOND example personality
        # (examples/gemini-agent/app.py:87-113): a persona'd agent that
        # FLATTENS its recent store-backed history into one prompt string
        # per turn — stateless model calls, history-in-prompt — instead of
        # the llm flavor's KV-resident sessions
        self.flavor = E.get("AGENTAINER_ENGINE", "llm")
        self.flatten_history = self.flavor == "assistant"
        self.history_turns = 3  # gemini-agent keeps the last 3 exchanges
        try:
            self.model_options = json.loads(E.get("AGENTAINER_MODEL_OPTIONS", "") or "{}")
        except json.JSONDecodeError:
            self.model_options = {}
        # deploy-time persona knobs (usable on the llm flavor too)
        self.flatten_history = self.flatten_history or bool(
            self.model_options.get("flatten_history")
        )
        self.history_turns = int(self.model_options.get("history_turns", self.history_turns))
        if not self.system_prompt:
            self.system_prompt = str(self.model_options.get("system_prompt", ""))
        if self.flavor == "assistant" and not self.system_prompt:
            self.system_prompt = "You are a helpful, concise assistant."
        self.chips = tuple(
            int(c) for c in E.get("AGENTAINER_CHIPS", "0").split(",") if c != ""
        )
        # fleet replica ordinal (0 for single-replica agents): pure
        # observability — lets operators attribute traffic/restarts to one
        # replica in /metrics and logs
        try:
            self.replica = int(E.get("AGENTAINER_REPLICA", "0") or 0)
        except ValueError:
            self.replica = 0
        self.store = StoreClient(
            control_url=E.get("AGENTAINER_CONTROL_URL", ""),
            token=E.get("AGENTAINER_INTERNAL_TOKEN", ""),
            agent_id=E.get("AGENTAINER_AGENT_ID", ""),
            store_sock=E.get("AGENTAINER_STORE_SOCK", ""),
        )
        self.started_at = time.time()
        self.requests_total = 0
        # front door's accept stamp → this handler's entry, per proxied
        # /chat (ms; one machine, one wall clock): the journal layer's
        # dispatch time. Bounded like the engine's sample deques.
        self.journal_dispatch_ms_recent: collections.deque[float] = collections.deque(
            maxlen=256
        )
        self._ready = asyncio.Event()
        # multi-tenant host state (host instance only)
        self._tenants: dict[str, tuple["LLMServeApp", web.AppRunner, int]] = {}
        self._host_token = E.get("AGENTAINER_HOST_TOKEN", "")
        self.kv_restores = 0
        self.prefix_prewarms = 0
        # tiered KV hierarchy (kv_tiering): proxy-hinted park/prewarm ops
        self.kv_park_errors = 0
        self.kv_prewarm_errors = 0
        self.kv_snapshots = 0
        self.kv_snapshot_bytes = 0  # the stored blobs' sizes, summed
        self.kv_snapshots_deferred = 0
        self.kv_snapshot_errors = 0
        self.last_kv_snapshot_error = ""
        # debounce: at most one snapshot per session per interval, with a
        # trailing capture so the END of a burst of turns is still persisted
        # (VERDICT r4 weak #2: per-turn snapshots taxed the device queue the
        # pipelined decode was saturating — 2s TTFT on a healthy decode)
        try:
            self.kv_snapshot_interval_s = float(
                self.model_options.get("kv_snapshot_interval_s", 10.0)
            )
        except (TypeError, ValueError):
            self.kv_snapshot_interval_s = 10.0
        self._kv_last_snap: dict[str, float] = {}
        self._kv_deferred: set[str] = set()
        self.unhandled_errors = 0
        self.last_unhandled_error = ""
        self._bg_tasks: set[asyncio.Task] = set()  # keep snapshot tasks alive
        # graceful-drain state (SIGTERM path): drain budget, outcome, and
        # how many sessions got a final durability snapshot
        try:
            self.drain_budget_s = float(
                self.model_options.get(
                    "drain_budget_s", E.get("AGENTAINER_DRAIN_BUDGET_S", 10.0)
                )
            )
        except (TypeError, ValueError):
            self.drain_budget_s = 10.0
        self.draining = False
        self.drained_clean: bool | None = None
        self.drain_snapshots = 0
        # SSE streaming surface (stream=true on /chat, engine streaming
        # option): keep-alive cadence is configurable per deployment, with
        # the daemon's environment as the default
        try:
            self.stream_heartbeat_s = float(
                self.model_options.get(
                    "stream_heartbeat_s", E.get("ATPU_STREAM_HEARTBEAT_S", 15.0)
                )
            )
        except (TypeError, ValueError):
            self.stream_heartbeat_s = 15.0
        self.streams_started = 0
        self.stream_tokens_emitted = 0
        self.stream_heartbeats = 0
        self.stream_client_disconnects = 0

    # engine + load state delegate to the host when this app is a tenant:
    # one LLMEngine (one weight copy) serves every attached agent
    @property
    def engine(self):
        return self._host.engine if self._host is not None else self._engine

    @engine.setter
    def engine(self, value) -> None:
        self._engine = value

    @property
    def engine_error(self) -> str:
        return self._host.engine_error if self._host is not None else self._engine_error

    @engine_error.setter
    def engine_error(self, value: str) -> None:
        self._engine_error = value

    @property
    def ready_event(self) -> asyncio.Event:
        return self._host.ready_event if self._host is not None else self._ready

    def _sess(self, session: str) -> str:
        """Engine-side session namespace: tenants sharing one engine must
        never collide on KV slots (or LRU-evict each other's session by
        name)."""
        return f"{self.agent_id}::{session}"

    @property
    def convo_key(self) -> str:
        """Legacy shared conversation list (every session interleaved).
        Still read for backward compatibility; new turns land on the
        per-session keys below."""
        return f"agent:{self.agent_id}:conversations"

    def _convo_session_key(self, session: str) -> str:
        """Per-session conversation list: the flattened-history prompt
        builder reads O(history window) per turn instead of JSON-parsing
        the whole shared list and filtering in Python."""
        return f"{self.convo_key}:{session}"

    def _kv_key(self, session: str) -> str:
        return f"agent:{self.agent_id}:kvcache:{session}"

    def _deadline_from(self, request: web.Request) -> float | None:
        """Absolute give-up instant from the deadline header (remaining ms),
        falling back to the deploy-config default. None = no deadline."""
        raw = request.headers.get(DEADLINE_HEADER, "")
        if not raw:
            raw = self.model_options.get("default_deadline_ms", "")
        try:
            ms = float(raw)
        except (TypeError, ValueError):
            return None
        return time.time() + ms / 1000.0 if ms > 0 else None

    def _policy_response(self, e: BaseException) -> web.Response | None:
        """Map engine lifecycle-policy rejections to HTTP. Returns None for
        anything that is a real error (the json_errors middleware owns it)."""
        from .llm import EngineDraining, EngineOverloaded, RequestCancelled, RequestExpired

        if isinstance(e, EngineOverloaded):
            return web.json_response(
                {"error": str(e), "depth": e.depth, "watermark": e.watermark},
                status=429,
                headers={"Retry-After": str(max(1, int(round(e.retry_after_s))))},
            )
        if isinstance(e, EngineDraining):
            return web.json_response(
                {"error": "engine draining for restart"},
                status=503,
                headers={DRAINING_HEADER: "true", "Retry-After": "5"},
            )
        if isinstance(e, RequestExpired):
            return web.json_response(
                {"error": str(e)}, status=504, headers={EXPIRED_HEADER: "true"}
            )
        if isinstance(e, RequestCancelled):
            # same dead-letter marker as expiry: the proxy must not archive
            # a cancellation notice as the request's completed response
            return web.json_response(
                {"error": str(e)},
                status=499,
                reason="Client Closed Request",
                headers={EXPIRED_HEADER: "true"},
            )
        return None

    async def _snapshot_session(self, session: str) -> None:
        """Fire-and-forget KV snapshot after a turn settles (async host
        offload keeps TTFT out of the snapshot's way — SURVEY.md §7 hard
        part #2). Debounced per session: a burst of turns costs one
        leading snapshot plus one trailing capture, not one per turn."""
        now = time.monotonic()
        last = self._kv_last_snap.get(session)
        if last is not None and now - last < self.kv_snapshot_interval_s:
            if session not in self._kv_deferred:
                self._kv_deferred.add(session)
                try:
                    await asyncio.sleep(last + self.kv_snapshot_interval_s - now)
                finally:
                    self._kv_deferred.discard(session)
            else:
                return  # a deferred capture is already pending; it will see this turn
        await self._snapshot_now(session)

    async def _snapshot_now(self, session: str) -> None:
        from .llm import SnapshotDeferred

        try:
            blob = await self.engine.snapshot_session(self._sess(session))
            if blob:
                self._kv_last_snap[session] = time.monotonic()
                await self.store.set_bytes(self._kv_key(session), blob, ttl=24 * 3600)
                self.kv_snapshots += 1
                self.kv_snapshot_bytes += len(blob)
        except SnapshotDeferred:
            # engine busy / limiter saturated: not an error — the next turn
            # retries, and the engine's snapshot_force_s bounds how long a
            # loaded engine can keep deferring. Counted for observability.
            self.kv_snapshots_deferred += 1
        except Exception as e:
            # surfaced, not swallowed: /metrics carries the count + last error
            self.kv_snapshot_errors += 1
            self.last_kv_snapshot_error = f"{type(e).__name__}: {e}"
            print(f"[llm-serve] kv snapshot failed: {self.last_kv_snapshot_error}", flush=True)

    def _engine_options(self) -> dict:
        from .llm import fleet_default_applies

        opts = dict(self.model_options)
        # the three policies with a half in the proxy (config.features /
        # config.deadlines → the daemon's write-back → this process's env):
        # the deployment's own model.options still win, and tiering never
        # reaches a model whose cache cannot hold it (the engine reports it
        # off with the reason)
        for flag, env_name in (
            ("deadlines", "ATPU_DEADLINES"),
            ("kv_tiering", "ATPU_KV_TIERING"),
            ("streaming", "ATPU_STREAMING"),
        ):
            raw = os.environ.get(env_name)
            if raw is not None and flag not in opts and fleet_default_applies(self.config_name, flag):
                opts[flag] = raw.lower() in ("1", "true", "yes")
        if self.chips:
            # no tp injection: LLMEngine.create derives the parallelism
            # split from the chip budget itself (parallel/mesh.plan_layout:
            # dense → tp-first, MoE → ep-first), and an explicit
            # options.tp/ep only narrows it
            opts["chips"] = list(self.chips)
        # warm boot (engine RESPAWN with a populated persistent XLA cache):
        # skip the serving warmup — every compile it would trigger is a disk
        # cache load that the first real requests absorb in milliseconds,
        # and skipping it is most of the crash-recovery win (VERDICT r4 #4).
        # Gated on a marker proving THIS engine configuration completed a
        # warmup into the cache before — a dir holding only some other
        # model's entries would silently reintroduce full first-request
        # compiles on the recovery path.
        if os.environ.get("AGENTAINER_WARM_BOOT") == "1" and "skip_warmup" not in opts:
            if os.path.exists(self._warm_marker_path(opts)):
                opts["skip_warmup"] = True
        return opts

    def _warm_marker_path(self, opts: dict) -> str:
        import hashlib

        key = json.dumps(
            {
                "config": self.config_name,
                "checkpoint": self.checkpoint,
                "opts": {k: v for k, v in sorted(opts.items()) if k != "skip_warmup"},
            },
            sort_keys=True,
        )
        # beside the cache it vouches for (utils/compile_cache.py): found
        # again exactly when the compiled programs are
        return os.path.join(
            compile_cache_dir(), f"warmed-{hashlib.sha1(key.encode()).hexdigest()[:16]}"
        )

    def _load_engine(self) -> None:
        """Build the JAX engine (slow: compile + weight init). Runs in a
        thread at startup so /health can answer while loading."""
        try:
            with self._boot.span("boot.import"):
                from .llm import LLMEngine

            opts = self._engine_options()
            t0 = time.monotonic()
            self.engine = LLMEngine.create(
                config_name=self.config_name,
                checkpoint=self.checkpoint,
                agent_id=self.agent_id,
                store=self.store,
                # deploy-time knobs (quant/max_batch/…); the scheduler's
                # chip assignment always rides along (placement authority),
                # while an explicit options.tp can narrow the span
                options=opts,
                boot=self._boot,
            )
            self.engine_load_s = round(time.monotonic() - t0, 2)
            self.warmup_skipped = bool(opts.get("skip_warmup"))
            if not self.warmup_skipped:
                # record that THIS configuration's warmup populated the
                # persistent cache — the respawn fast path keys on it
                marker = self._warm_marker_path(opts)
                try:
                    os.makedirs(os.path.dirname(marker), exist_ok=True)
                    with open(marker, "w") as f:
                        f.write("ok")
                except OSError:
                    pass
        except BaseException as e:  # engine stays None; /chat reports 503
            self.engine_error = f"{type(e).__name__}: {e}"

    async def _prewarm_prefix(self) -> None:
        """Register this agent's persona header in the engine's prefix
        arena before traffic arrives: one throwaway 1-token generation of
        ``"{persona}\\n\\n"`` prefills and caches its bucket-prefixes, so
        even the FIRST session forks the persona instead of paying its
        prefill. Matches both serving shapes — the chat path prepends
        ``f"{system_prompt}\\n\\n{message}"`` and the flattened path opens
        with ``f"{system_prompt}\\n\\n{history}"``. Best effort."""
        eng = self.engine
        if eng is None or not self.system_prompt:
            return
        if not getattr(eng, "prefix_cache", False):
            return
        try:
            await eng.generate(
                prompt=f"{self.system_prompt}\n\n", max_tokens=1, temperature=0.0
            )
            self.prefix_prewarms += 1
        except Exception as e:
            print(
                f"[llm-serve] persona prefix prewarm failed for {self.agent_id}: "
                f"{type(e).__name__}: {e}",
                flush=True,
            )

    def _notify_ready(self) -> None:
        """Tell the control plane the model is servable so queued requests
        replay NOW rather than on the next scan tick (loader thread; best
        effort — the 5s replay cadence remains the safety net)."""
        url = self.store.control_url
        token = self.store.token
        if not url or not token:
            return  # standalone runs and identity-less hosts skip the ping
        try:
            import http.client
            from urllib.parse import urlparse

            u = urlparse(url)
            conn = http.client.HTTPConnection(u.hostname, u.port or 80, timeout=5.0)
            conn.request(
                "POST",
                "/internal/engines/ready",
                body=b"{}",
                headers={
                    "X-Agentainer-Agent-ID": self.agent_id,
                    "Authorization": f"Bearer {token}",
                    "Content-Type": "application/json",
                },
            )
            conn.getresponse().read()
            conn.close()
        except Exception as e:
            # best effort, but NEVER fatal: http.client raises more than
            # OSError (BadStatusLine/HTTPException on a garbled response),
            # and this runs on the model-loader thread — an escape here
            # used to kill the loader before the tenant ready fan-out
            # (ADVICE r5); the 5s replay cadence remains the safety net
            print(
                f"[llm-serve] ready callback failed for {self.agent_id}: "
                f"{type(e).__name__}: {e}",
                flush=True,
            )

    def _fan_out_ready(self) -> None:
        """Model-loaded notification for this app AND every attached tenant.
        Per-tenant isolation: one tenant's failing callback must not skip
        the rest (their control planes would all fall back to the replay
        scan cadence)."""
        self._notify_ready()
        for tenant, _, _ in list(self._tenants.values()):
            try:
                tenant._notify_ready()
            except Exception as e:
                print(
                    f"[llm-serve] tenant {tenant.agent_id} ready fan-out "
                    f"failed: {type(e).__name__}: {e}",
                    flush=True,
                )

    def app(self) -> web.Application:
        @web.middleware
        async def json_errors(request: web.Request, handler):
            """Any unhandled handler exception becomes a JSON 500 carrying
            the exception string, with the full traceback in the engine log.
            Round 4's flagship run died with a bare text/plain 500 and no
            surviving diagnostics (VERDICT r4 weak #1) — never again."""
            try:
                return await handler(request)
            except web.HTTPException:
                raise  # intentional status responses pass through
            except asyncio.CancelledError:
                raise
            except BaseException as e:
                import traceback

                self.unhandled_errors += 1
                self.last_unhandled_error = f"{type(e).__name__}: {e}"
                print(
                    f"[llm-serve] {request.method} {request.path} failed:\n"
                    f"{traceback.format_exc()}",
                    flush=True,
                )
                # a typed prefill failure is the request's own fault on a
                # healthy engine: mark the 500 so the proxy charges poison
                # accounting instead of archiving or blaming the engine
                headers = {}
                try:
                    from .llm import PrefillFailed

                    if isinstance(e, PrefillFailed):
                        headers[PREFILL_POISON_HEADER] = "true"
                except ImportError:
                    pass
                return web.json_response(
                    {
                        "error": self.last_unhandled_error,
                        "path": request.path,
                        "agent_id": self.agent_id,
                    },
                    status=500,
                    headers=headers,
                )

        app = web.Application(middlewares=[json_errors])
        app.router.add_get("/", self.h_root)
        app.router.add_get("/health", self.h_health)
        app.router.add_post("/chat", self.h_chat)
        app.router.add_post("/generate", self.h_generate)
        app.router.add_get("/history", self.h_history)
        app.router.add_post("/cancel", self.h_cancel)
        app.router.add_post("/clear", self.h_clear)
        app.router.add_get("/metrics", self.h_metrics)
        app.router.add_post("/profile", self.h_profile)
        # tiered KV hierarchy: the proxy's park/prewarm hints ride the same
        # dispatch path as /chat (journal/fleet semantics apply unchanged)
        app.router.add_post("/park", self.h_park)
        app.router.add_post("/prewarm", self.h_prewarm)
        if self._host_token:
            # multi-tenant host admin surface (localhost-only process; the
            # backend authenticates with the host token it minted at spawn)
            app.router.add_post("/-/tenants", self.h_tenant_attach)
            app.router.add_delete("/-/tenants/{agent_id}", self.h_tenant_detach)

        async def boot(app):
            # Tenants never load: the host's engine is theirs. Their control
            # plane still gets a ready callback (at attach, the host may
            # already be loaded; otherwise the host loader fans out).
            if self._host is not None:
                return
            if self.engine is not None:
                # an engine was injected before startup (embedding, tests):
                # loading again would orphan a second worker thread and
                # race the injected engine out of self.engine
                self._boot.ready()
                self._ready.set()
                self._fan_out_ready()
                return
            # DAEMON thread, not asyncio.to_thread: executor threads are
            # joined at interpreter exit, so a load blocked in the TPU
            # runtime would make SIGTERM hang until the backend escalates
            # to SIGKILL. A daemon loader lets a terminated engine die
            # cleanly mid-load and release its chips.
            import threading

            loop = asyncio.get_running_loop()

            def _run() -> None:
                try:
                    self._load_engine()
                    if self.engine is not None:
                        # persona prefixes into the arena BEFORE ready fans
                        # out: the first replayed request already forks
                        # them (tenants attached mid-load covered here;
                        # later attaches prewarm at attach time)
                        async def _prewarm_all() -> None:
                            await self._prewarm_prefix()
                            for tenant, _, _ in list(self._tenants.values()):
                                await tenant._prewarm_prefix()

                        with self._boot.span("boot.prewarm_prefix"):
                            asyncio.run(_prewarm_all())
                finally:
                    # set even on loader death: waiters unblock
                    self._boot.ready()
                    loop.call_soon_threadsafe(self._ready.set)
                    if self.engine is not None:
                        self._fan_out_ready()

            threading.Thread(target=_run, daemon=True, name="model-loader").start()

        async def cleanup(app):
            # graceful drain BEFORE detaching tenants: their resident
            # sessions get a final durability snapshot while the engine
            # still holds them — so a rolling restart resumes every
            # tenant's conversation token-identical instead of looking
            # like a crash
            if self._host is None and self.engine is not None:
                await self._graceful_drain()
            for aid in list(self._tenants):
                await self._detach_tenant(aid)
            if self._host is None and self.engine is not None:
                await asyncio.to_thread(self.engine.shutdown)
            await self.store.close()

        app.on_startup.append(boot)
        app.on_cleanup.append(cleanup)
        return app

    async def _graceful_drain(self) -> None:
        """SIGTERM half of a rolling restart: stop admitting, let in-flight
        lanes finish inside the drain budget, then snapshot every resident
        session (the host's AND still-attached tenants') so the respawned
        engine restores them token-identical. Queued journal entries replay
        on respawn — the drain makes a planned restart lossless, not
        crash-shaped."""
        eng = self.engine
        if eng is None:
            return
        self.draining = True
        self.drained_clean = await asyncio.to_thread(eng.drain, self.drain_budget_s)
        # the engine is idle now (or the budget ran out): lift the snapshot
        # limiter — its job is protecting in-flight decode from readback
        # traffic, and there is none left to protect
        eng.snapshot_min_gap_s = 0.0
        eng.snapshot_busy_gap_s = 0.0
        for app_ in [self] + [t for t, _, _ in self._tenants.values()]:
            if not app_.store.connected:
                continue
            prefix = f"{app_.agent_id}::"
            for name in [s for s in list(eng.sessions) if s.startswith(prefix)]:
                before = app_.kv_snapshots
                try:
                    await app_._snapshot_now(name[len(prefix):])
                except Exception:
                    continue  # _snapshot_now already counted/logged it
                if app_.kv_snapshots > before:
                    self.drain_snapshots += 1

    async def h_cancel(self, request: web.Request) -> web.Response:
        """Abort a request by id (the proxy calls this when the client
        disconnects mid-dispatch; operators can too). Queued work is
        rejected before prefill; an in-flight lane is parked mid-decode and
        its slot freed."""
        self.requests_total += 1
        err = await self._ensure_engine()
        if err is not None:
            return err
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        rid = str(body.get("request_id", ""))
        if not rid:
            return web.json_response({"error": "request_id required"}, status=400)
        return web.json_response({"cancelled": bool(self.engine.cancel(rid))})

    # -- multi-tenant host admin (backend-only; VERDICT r4 item 5) --------
    def _check_host_auth(self, request: web.Request) -> bool:
        import hmac as _hmac

        presented = request.headers.get("Authorization", "").removeprefix("Bearer ").strip()
        return bool(self._host_token) and _hmac.compare_digest(
            presented.encode(), self._host_token.encode()
        )

    async def h_tenant_attach(self, request: web.Request) -> web.Response:
        """Attach an agent to this host: a new serving surface on its own
        localhost port, sharing THIS process's engine (one weight copy)."""
        if not self._check_host_auth(request):
            return web.json_response({"error": "bad host token"}, status=401)
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        aid = str(body.get("agent_id", ""))
        if not aid:
            return web.json_response({"error": "agent_id required"}, status=400)
        if aid in self._tenants:  # idempotent re-attach (engine respawn race)
            return web.json_response({"port": self._tenants[aid][2], "existing": True})
        tenant_env = {
            "AGENTAINER_AGENT_ID": aid,
            "AGENTAINER_AGENT_NAME": str(body.get("name", aid)),
            "AGENTAINER_ENGINE": str(body.get("flavor", "llm")),
            "AGENTAINER_MODEL_CONFIG": self.config_name,
            "AGENTAINER_CHECKPOINT": self.checkpoint,
            "AGENTAINER_MODEL_OPTIONS": json.dumps(body.get("options", {}) or {}),
            "AGENTAINER_SYSTEM_PROMPT": str(body.get("system_prompt", "")),
            "AGENTAINER_CONTROL_URL": self.store.control_url,
            "AGENTAINER_INTERNAL_TOKEN": str(body.get("token", "")),
            "AGENTAINER_STORE_SOCK": os.environ.get("AGENTAINER_STORE_SOCK", ""),
            "AGENTAINER_CHIPS": ",".join(map(str, self.chips)),
            # the replica ordinal is part of the host's share key: every
            # tenant of this process is that replica of its agent
            "AGENTAINER_REPLICA": str(self.replica),
        }
        tenant = LLMServeApp(env=tenant_env, host=self)
        runner = web.AppRunner(tenant.app())
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        self._tenants[aid] = (tenant, runner, port)
        if self.engine is not None:
            # the tenant's persona goes into the shared engine's prefix
            # arena right away (its first session forks it, same as the
            # host's own persona at boot)
            task = asyncio.ensure_future(tenant._prewarm_prefix())
            self._bg_tasks.add(task)
            task.add_done_callback(self._bg_tasks.discard)
            # model already loaded: replay can drain now. Off-loop: the ping
            # is blocking HTTP and must not stall co-tenants' serving.
            asyncio.get_running_loop().run_in_executor(None, tenant._notify_ready)
        print(f"[llm-serve] tenant {aid} attached on :{port}", flush=True)
        return web.json_response({"port": port})

    async def _detach_tenant(self, aid: str) -> bool:
        entry = self._tenants.pop(aid, None)
        if entry is None:
            return False
        tenant, runner, _ = entry
        if self.engine is not None:
            await asyncio.to_thread(self.engine.clear_sessions, f"{aid}::")
        await runner.cleanup()  # closes the site; tenant cleanup closes its store
        print(f"[llm-serve] tenant {aid} detached", flush=True)
        return True

    async def h_tenant_detach(self, request: web.Request) -> web.Response:
        if not self._check_host_auth(request):
            return web.json_response({"error": "bad host token"}, status=401)
        aid = request.match_info["agent_id"]
        if not await self._detach_tenant(aid):
            return web.json_response({"error": f"no tenant {aid}"}, status=404)
        return web.json_response({"detached": aid, "remaining": len(self._tenants)})

    async def h_root(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "agent": self.agent_name,
                "engine": "llm",
                "model": self.config_name,
                "chips": list(self.chips),
                "status": "running" if self.engine else "loading",
            }
        )

    async def h_health(self, request: web.Request) -> web.Response:
        self.requests_total += 1
        host = self._host if self._host is not None else self
        return web.json_response(
            {
                "status": "draining" if host.draining else "healthy",
                "agent_id": self.agent_id,
                "model_loaded": self.engine is not None,
                "uptime_s": time.time() - self.started_at,
            }
        )

    async def _ensure_engine(self) -> web.Response | None:
        # While the model loads, answer fast with a "loading" marker instead
        # of stalling handlers: the proxy treats it like engine-not-ready
        # (journal entry stays pending, no retry charged, nothing executes
        # twice) and the replay worker re-dispatches once loading finishes.
        # The short bounded wait spares the round-trip when load is nearly
        # done; the Event is set by the loader even if it dies.
        if self.engine is None and not self.engine_error:
            try:
                await asyncio.wait_for(self.ready_event.wait(), timeout=2.0)
            except asyncio.TimeoutError:
                pass
        if self.engine is not None:
            return None
        if self.engine_error:
            return web.json_response(
                {"error": f"model runtime failed to load: {self.engine_error}"}, status=503
            )
        return web.json_response(
            {"error": "model loading"}, status=503, headers={LOADING_HEADER: "true"}
        )

    async def h_chat(self, request: web.Request) -> web.Response:
        self.requests_total += 1
        accepted_ns = request.headers.get(ACCEPTED_NS_HEADER, "")
        if accepted_ns.isdigit():
            self.journal_dispatch_ms_recent.append((time.time_ns() - int(accepted_ns)) / 1e6)
        boot = (self._host or self)._boot
        entered_ns = time.perf_counter_ns() if boot.first_dispatch_s is None else 0
        err = await self._ensure_engine()
        if err is not None:
            return err
        if entered_ns:
            # the first request taken after ready; a replayed dispatch
            # carries no accept stamp (core/protocol.py)
            boot.first_dispatch(entered_ns, replayed=not accepted_ns.isdigit())
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        message = str(body.get("message", ""))
        session = str(body.get("session", "default"))
        max_tokens = int(body.get("max_tokens", 64))
        request_id = request.headers.get("X-Agentainer-Request-ID", "")
        # kwarg only when a deadline is actually set: duck-typed engine
        # doubles (and the echo engine's contract) stay compatible
        dl_kw = (
            {"deadline_at": dl} if (dl := self._deadline_from(request)) is not None else {}
        )
        # fixed-length streams on request (benchmarks and the chaos soak's
        # mid-decode kill need a decode window that doesn't end at a tiny
        # model's early EOS); kwarg-only-when-set, same as deadline_at
        if body.get("ignore_eos"):
            dl_kw["ignore_eos"] = True
        # SSE streaming is opt-in per request AND flag-gated per engine
        # (options.streaming / the ATPU_STREAMING quad): with the flag off,
        # stream=true degrades to today's buffered response — the default
        # path stays byte-identical as the A/B baseline
        stream = bool(body.get("stream")) and bool(
            getattr(self.engine, "streaming", False)
        )

        if self.flatten_history:
            # gemini-agent-style turn: persona + last-N exchanges flattened
            # into ONE prompt string, generated statelessly (no KV session)
            prompt = await self._flattened_prompt(session, message)
            if stream:
                return await self._chat_streamed(
                    request,
                    session=session,
                    message=message,
                    prompt=prompt,
                    max_tokens=max_tokens,
                    request_id=request_id,
                    dl_kw=dl_kw,
                    flatten=True,
                )
            try:
                result = await self.engine.generate(
                    prompt=prompt,
                    max_tokens=max_tokens,
                    request_id=request_id,
                    **dl_kw,
                )
            except Exception as e:
                resp = self._policy_response(e)
                if resp is None:
                    raise
                return resp
            await self._record_turn(session, message, result["text"])
            return web.json_response(
                {
                    "response": result["text"],
                    "agent": self.agent_name,
                    "model": self.config_name,
                    "persona": self.system_prompt,
                    "usage": {
                        "prompt_tokens": result["prompt_tokens"],
                        "completion_tokens": result["completion_tokens"],
                    },
                    "ttft_ms": result.get("ttft_ms"),
                }
            )

        # crash-resume: an unknown session may have a KV snapshot in the
        # store from a previous engine life — restore it before generating
        # so the conversation continues from its exact context. A session
        # parked in the engine's host tier is KNOWN (it promotes at
        # admission) — store-restoring it would resurrect stale context.
        if self.store.connected and not self._engine_has_session(session):
            try:
                blob = await self.store.get_bytes(self._kv_key(session))
                if blob:
                    restored = await self.engine.restore_session(self._sess(session), blob)
                    if restored:
                        self.kv_restores += 1
            except Exception:
                pass

        # persona parity with the reference's SYSTEM_PROMPT env
        # (examples/gpt-agent/app.py): a brand-new session's context opens
        # with the system prompt; later turns inherit it through the KV
        # cache. Only the raw user message goes to /history.
        prompt = message
        if self.system_prompt and not self._engine_has_session(session):
            prompt = f"{self.system_prompt}\n\n{message}"

        if stream:
            return await self._chat_streamed(
                request,
                session=session,
                message=message,
                prompt=prompt,
                max_tokens=max_tokens,
                request_id=request_id,
                dl_kw=dl_kw,
                flatten=False,
            )
        try:
            result = await self.engine.chat(
                session=self._sess(session),
                message=prompt,
                max_tokens=max_tokens,
                request_id=request_id,
                **dl_kw,
            )
        except Exception as e:
            resp = self._policy_response(e)
            if resp is None:
                raise
            return resp
        if self.store.connected:
            task = asyncio.ensure_future(self._snapshot_session(session))
            self._bg_tasks.add(task)  # an unreferenced task can be GC'd mid-flight
            task.add_done_callback(self._bg_tasks.discard)
        await self._record_turn(session, message, result["text"])
        return web.json_response(
            {
                "response": result["text"],
                "agent": self.agent_name,
                "model": self.config_name,
                "usage": {
                    "prompt_tokens": result["prompt_tokens"],
                    "completion_tokens": result["completion_tokens"],
                },
                "ttft_ms": result.get("ttft_ms"),
                "ttft_breakdown": result.get("ttft_breakdown"),
            }
        )

    async def _chat_streamed(
        self,
        request: web.Request,
        *,
        session: str,
        message: str,
        prompt: str,
        max_tokens: int,
        request_id: str,
        dl_kw: dict,
        flatten: bool,
    ) -> web.StreamResponse:
        """SSE token stream for one /chat turn (stream=true).

        Every ``token`` event carries a monotone offset (the ``id:`` line)
        into the request's deterministic token sequence; ``done`` closes
        with the exact payload the buffered path would have returned. The
        offsets are the crash contract: a resume of the SAME journaled
        request re-emits the sequence from offset 0 and this layer skips
        everything at or below the Last-Event-ID splice cursor — so the
        proxy's mid-stream failover (or a reconnecting client) observes one
        gapless, duplicate-free sequence. Comment-frame keep-alives bridge
        long prefills and never advance offsets. A memoized replay returns
        the full result with no live emits; the catch-up loop re-emits it
        under the same offsets, which is exactly what the splice needs.
        """
        self.streams_started += 1
        # engine-side cancel needs an id; direct (proxy-less) clients may
        # not send one
        rid = request_id or f"stream-{time.monotonic_ns()}"
        try:
            last_acked = int(request.headers.get(LAST_EVENT_ID_HEADER, ""))
        except (TypeError, ValueError):
            last_acked = -1
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()

        def emit(start: int, ids: list) -> None:  # worker thread → loop
            loop.call_soon_threadsafe(q.put_nowait, (start, list(ids)))

        if flatten:
            gen = self.engine.generate(
                prompt=prompt,
                max_tokens=max_tokens,
                request_id=rid,
                emit=emit,
                **dl_kw,
            )
        else:
            gen = self.engine.chat(
                session=self._sess(session),
                message=prompt,
                max_tokens=max_tokens,
                request_id=rid,
                emit=emit,
                **dl_kw,
            )
        task = asyncio.ensure_future(gen)

        def _on_done(t: asyncio.Task) -> None:
            if not t.cancelled():
                t.exception()  # mark retrieved; the loop re-reads via result()
            q.put_nowait(("__done__", t))

        task.add_done_callback(_on_done)

        resp: web.StreamResponse | None = None
        tokens: list[int] = []  # engine emission sequence seen so far
        text = ""  # decoded prefix; per-event payload carries the delta
        result = None

        async def ensure_prepared() -> web.StreamResponse:
            nonlocal resp
            if resp is None:
                resp = web.StreamResponse(
                    status=200,
                    headers={
                        "Content-Type": STREAM_CONTENT_TYPE,
                        "Cache-Control": "no-cache",
                        "X-Accel-Buffering": "no",
                    },
                )
                await resp.prepare(request)
            return resp

        async def send_tokens(start: int, ids: list) -> None:
            nonlocal text
            for i, tid in enumerate(ids):
                off = start + i
                if off < len(tokens):
                    continue  # already seen (defensive; the worker is FIFO)
                tokens.append(int(tid))
                new_text = self.engine.tokenizer.decode(tokens)
                delta, text_new = new_text[len(text):], new_text
                text = text_new
                if off <= last_acked:
                    continue  # splice: the consumer already holds this one
                # failpoint: the per-event emission seam — an armed error
                # truncates the stream (no done frame), which is exactly
                # the upstream failure the proxy's failover splice absorbs
                await faults.fire_async("engine.stream")
                r = await ensure_prepared()
                await r.write(
                    _sse_frame(
                        STREAM_EVENT_TOKEN,
                        off,
                        {"offset": off, "token": int(tid), "text": delta},
                    )
                )
                self.stream_tokens_emitted += 1

        try:
            while True:
                try:
                    item = await asyncio.wait_for(
                        q.get(), timeout=max(0.05, self.stream_heartbeat_s)
                    )
                except asyncio.TimeoutError:
                    # keep-alive comment frame: holds idle LB/client
                    # timeouts open through long prefills and tool-call
                    # gaps; carries no id, never advances the cursor
                    r = await ensure_prepared()
                    await r.write(b": keep-alive\n\n")
                    self.stream_heartbeats += 1
                    continue
                if isinstance(item, tuple) and item[0] == "__done__":
                    t = item[1]
                    try:
                        result = t.result()
                    except Exception as e:
                        if resp is None:
                            # nothing sent yet: map to the same statuses as
                            # the buffered path (429/503/504/499, poison
                            # 500s via the middleware) so proxy
                            # classification is unchanged
                            pr = self._policy_response(e)
                            if pr is None:
                                raise
                            return pr
                        # mid-stream failure after bytes went out: close
                        # WITHOUT a done frame — the truncation is the
                        # upstream-failure signal the proxy fails over on
                        return resp
                    break
                await send_tokens(*item)
            # drain emits that landed between the final chunk and done
            while not q.empty():
                item = q.get_nowait()
                if not (isinstance(item, tuple) and item[0] == "__done__"):
                    await send_tokens(*item)
            # memoized replay (and any lost tail): catch up from the
            # result's token list under the same deterministic offsets
            await send_tokens(len(tokens), list(result.get("tokens") or [])[len(tokens):])
            if self.store.connected and not flatten:
                stask = asyncio.ensure_future(self._snapshot_session(session))
                self._bg_tasks.add(stask)
                stask.add_done_callback(self._bg_tasks.discard)
            await self._record_turn(session, message, result["text"])
            payload = {
                "response": result["text"],
                "agent": self.agent_name,
                "model": self.config_name,
                "usage": {
                    "prompt_tokens": result["prompt_tokens"],
                    "completion_tokens": result["completion_tokens"],
                },
                "ttft_ms": result.get("ttft_ms"),
                "ttft_breakdown": result.get("ttft_breakdown"),
            }
            if flatten:
                payload["persona"] = self.system_prompt
            r = await ensure_prepared()
            await r.write(
                _sse_frame(
                    STREAM_EVENT_DONE,
                    len(tokens) - 1 if tokens else None,
                    payload,
                )
            )
            await r.write_eof()
            return r
        except asyncio.CancelledError:
            # aiohttp cancels the handler when the SSE consumer drops:
            # propagate the abort into the engine so the lane frees
            # mid-decode (PR 3's disconnect path, extended to streams)
            self.stream_client_disconnects += 1
            self.engine.cancel(rid)
            raise
        except ConnectionError:
            self.stream_client_disconnects += 1
            self.engine.cancel(rid)
            if resp is not None:
                return resp
            return web.json_response(
                {"error": "client disconnected"},
                status=499,
                reason="Client Closed Request",
            )
        except Exception:
            if resp is None:
                raise  # buffered-style mapping (middleware owns the 500)
            # stream already under way: a clean error response is
            # impossible — cancel the engine side and truncate
            self.engine.cancel(rid)
            return resp

    def _engine_has_session(self, session: str) -> bool:
        """Cross-tier membership: device-resident or parked in the host
        tier. getattr-guarded so duck-typed engine doubles (echo engine,
        test fakes) that only expose ``sessions`` keep working."""
        name = self._sess(session)
        has = getattr(self.engine, "has_session", None)
        if has is not None:
            return bool(has(name))
        return name in self.engine.sessions

    async def h_park(self, request: web.Request) -> web.Response:
        """Tiering hint: demote an idle session off the device (proxy
        policy calls this after a response settles + linger). The exact
        staged blob is persisted to the store as the COLD tier — a parked
        session survives both the host tier's LRU budget and the process."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        session = str(body.get("session", "default"))
        park = getattr(self.engine, "park_session", None)
        if park is None or not getattr(self.engine, "kv_tiering", False):
            return web.json_response({"parked": False, "reason": "tiering off"})
        try:
            blob = await park(self._sess(session))
        except Exception as e:
            self.kv_park_errors += 1
            return web.json_response(
                {"parked": False, "reason": f"{type(e).__name__}: {e}"}
            )
        if blob is None:
            return web.json_response({"parked": False, "reason": "unknown or busy"})
        if self.store.connected:
            try:
                await self.store.set_bytes(self._kv_key(session), blob, ttl=24 * 3600)
                self._kv_last_snap[session] = time.monotonic()
            except Exception as e:
                # host tier still holds the session; only store durability
                # degraded — counted, not fatal
                self.kv_park_errors += 1
                print(
                    f"[llm-serve] park store write failed: {type(e).__name__}: {e}",
                    flush=True,
                )
        return web.json_response({"parked": True, "bytes": len(blob)})

    async def h_prewarm(self, request: web.Request) -> web.Response:
        """Tiering hint: promote a parked session back onto the device
        ahead of its next turn (proxy next-arrival hint). Falls back to a
        store restore when the session fell through to the cold tier."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        session = str(body.get("session", "default"))
        prewarm = getattr(self.engine, "prewarm_session", None)
        if prewarm is None or not getattr(self.engine, "kv_tiering", False):
            return web.json_response({"prewarmed": False, "reason": "tiering off"})
        ok = False
        try:
            ok = bool(await prewarm(self._sess(session)))
        except Exception:
            # best-effort hint: counted; admission still promotes later
            self.kv_prewarm_errors += 1
        if not ok and self.store.connected:
            # cold tier: the host entry was LRU-dropped (or never existed);
            # the store blob restores the exact context instead
            try:
                blob = await self.store.get_bytes(self._kv_key(session))
                if blob:
                    ok = bool(
                        await self.engine.restore_session(self._sess(session), blob)
                    )
                    if ok:
                        self.kv_restores += 1
            except Exception:
                self.kv_prewarm_errors += 1
        return web.json_response({"prewarmed": ok})

    async def _record_turn(self, session: str, message: str, reply: str) -> None:
        now = time.time()
        try:
            key = self._convo_session_key(session)
            await self.store.rpush(
                key,
                json.dumps({"role": "user", "content": message, "ts": now, "session": session}),
                json.dumps(
                    {"role": "assistant", "content": reply, "ts": now, "session": session}
                ),
            )
            await self.store.ltrim(key, -2 * MAX_TURNS, -1)
            await self.store.expire(key, SESSION_CONVO_TTL_S)
        except Exception:
            pass

    async def _session_turns(self, session: str, window: int) -> list[dict]:
        """Last ``window`` turns of one session: O(window) read of the
        per-session list, falling back to the legacy shared key (filter by
        session in Python) for conversations recorded before the split."""
        try:
            raw = await self.store.lrange(self._convo_session_key(session), -window, -1)
        except Exception:
            raw = []
        turns = []
        for item in raw:
            try:
                turns.append(json.loads(item))
            except json.JSONDecodeError:
                continue
        if len(turns) >= window:
            return turns
        # window not filled by the per-session list: older turns may still
        # live on the legacy shared key (a conversation recorded before the
        # split must not lose its pre-split context mid-conversation). The
        # legacy read fades out as soon as the per-session list fills.
        legacy_turns = []
        try:
            legacy = await self.store.lrange(self.convo_key, 0, -1)
        except Exception:
            legacy = []
        for item in legacy:
            try:
                t = json.loads(item)
            except json.JSONDecodeError:
                continue
            if t.get("session", "default") == session:
                legacy_turns.append(t)
        return (legacy_turns + turns)[-window:]

    async def _flattened_prompt(self, session: str, message: str) -> str:
        """Persona + the session's last ``history_turns`` exchanges as one
        prompt string (examples/gemini-agent/app.py:87-113 parity). The
        persona + stable history head is also what the engine's prefix
        arena keys on: turn N+1's prompt shares turn N's token prefix up to
        where the window slides, so each turn re-prefills only the tail."""
        lines: list[str] = []
        for t in await self._session_turns(session, 2 * self.history_turns):
            who = "User" if t.get("role") == "user" else "Assistant"
            lines.append(f"{who}: {t.get('content', '')}")
        lines.append(f"User: {message}")
        lines.append("Assistant:")
        history = "\n".join(lines)
        return f"{self.system_prompt}\n\n{history}" if self.system_prompt else history

    async def h_generate(self, request: web.Request) -> web.Response:
        """Raw completion endpoint (no conversation memory)."""
        self.requests_total += 1
        err = await self._ensure_engine()
        if err is not None:
            return err
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        dl_kw = (
            {"deadline_at": dl} if (dl := self._deadline_from(request)) is not None else {}
        )
        try:
            result = await self.engine.generate(
                prompt=str(body.get("prompt", "")),
                max_tokens=int(body.get("max_tokens", 64)),
                temperature=float(body.get("temperature", 0.0)),
                top_k=int(body.get("top_k", 0)),
                top_p=float(body.get("top_p", 1.0)),
                request_id=request.headers.get("X-Agentainer-Request-ID", ""),
                **dl_kw,
            )
        except Exception as e:
            resp = self._policy_response(e)
            if resp is None:
                raise
            return resp
        return web.json_response(result)

    async def h_history(self, request: web.Request) -> web.Response:
        self.requests_total += 1
        turns = []
        try:
            # per-session lists plus the legacy shared key (pre-split
            # turns); merged by timestamp so the combined view reads like
            # the old single list
            keys = [self.convo_key] + sorted(
                await self.store.keys(f"{self.convo_key}:*")
            )
        except Exception:
            keys = [self.convo_key]
        for key in keys:
            try:
                raw = await self.store.lrange(key, 0, -1)
            except Exception:
                continue
            for item in raw:
                try:
                    turns.append(json.loads(item))
                except json.JSONDecodeError:
                    continue
        turns.sort(key=lambda t: t.get("ts", 0.0))
        return web.json_response({"history": turns, "count": len(turns)})

    async def h_clear(self, request: web.Request) -> web.Response:
        self.requests_total += 1
        try:
            await self.store.delete(self.convo_key)
            for key in await self.store.keys(f"{self.convo_key}:*"):
                await self.store.delete(key)
            # KV snapshots must go too, or crash-resume would resurrect the
            # conversation the user just asked to forget
            for key in await self.store.keys(f"agent:{self.agent_id}:kvcache:*"):
                await self.store.delete(key)
        except Exception:
            pass
        if self.engine is not None:
            await asyncio.to_thread(self.engine.clear_sessions, f"{self.agent_id}::")
        return web.json_response({"status": "cleared"})

    async def h_profile(self, request: web.Request) -> web.Response:
        """Capture a jax.profiler trace of live serving (device + host
        timelines). One capture at a time; the trace directory is shared
        with the control plane so the management API can return its path.
        The host planes hold the engine's phase spans (utils/spans.py) and
        JAX's own events; ``python_tracer: true`` adds every Python frame,
        which slows the worker that is being watched. The answer, and the
        engine's ``/metrics`` as ``last_capture`` until the next capture,
        carry the launch ledger at the capture's own two edges: the launches
        that were really in it."""
        self.requests_total += 1
        err = await self._ensure_engine()
        if err is not None:
            return err
        try:
            body = await request.json()
        except json.JSONDecodeError:
            body = {}  # empty/absent body → defaults
        if not isinstance(body, dict):
            body = {}
        try:
            # a capture is capped: the trace's size, and the time stop_trace
            # takes to collect it (which every hop in front waits out), grow
            # with the window
            duration = min(float(body.get("duration_s", 2.0) or 2.0), 25.0)
        except (TypeError, ValueError):
            return web.json_response(
                {"error": 'duration_s must be a number, e.g. {"duration_s": 2.0}'},
                status=400,
            )
        if getattr(self, "_profiling", False):
            return web.json_response({"error": "profile already running"}, status=409)
        trace_dir = os.environ.get("AGENTAINER_PROFILE_DIR", "") or os.path.join(
            "/tmp", f"atpu-profile-{self.agent_id}"
        )
        os.makedirs(trace_dir, exist_ok=True)
        self._profiling = True
        try:
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = int(bool(body.get("python_tracer", False)))
            # starting, and stopping (a second of collection), block: off
            # the event loop, so /chat is answered meanwhile
            await asyncio.to_thread(
                jax.profiler.start_trace, trace_dir, profiler_options=options
            )
            # stop_trace's collection grows with the device events captured,
            # not with the seconds: about 10 ms a layer-step of a prefill-heavy
            # mix (measured on a v5e host: 66 s for the 383 passes × 16 layers
            # of a 5 s window at 6.7 long requests a second, 21 s for 126
            # passes), and the callers in front wait 60 s. So a capture ends
            # at ``duration`` or once the engine has launched
            # PROFILE_LAYER_STEPS (half that wait), whichever comes first;
            # ``duration_s`` in the answer is what was captured.
            t0 = time.monotonic()
            launches_before = self.engine.launches()
            first = self.engine.forward_passes
            # a layer-step of the hybrid block is about four of the K/V
            # block's in device events (two 0-or-1-trip loops, two kernels, a
            # conditional FFN): stop_trace took over 60 s for 3,000 of them
            # (my chip run, PR 30), so its capture ends at a quarter as many
            per_pass = self.engine.cfg.n_layers * (4 if self.engine.cfg.is_hybrid else 1)
            room = PROFILE_LAYER_STEPS / per_pass
            try:
                while (
                    time.monotonic() - t0 < duration
                    and self.engine.forward_passes - first < room
                ):
                    await asyncio.sleep(min(0.05, duration))
            finally:
                duration = time.monotonic() - t0
                self.engine.last_capture = capture = {
                    "captured_s": duration,
                    "launches_before": launches_before,
                    "launches_after": self.engine.launches(),
                }
                await asyncio.to_thread(jax.profiler.stop_trace)
        except Exception as e:
            return web.json_response(
                {"error": f"profiler failed: {type(e).__name__}: {e}"}, status=500
            )
        finally:
            self._profiling = False
        return web.json_response(
            {
                "trace_dir": trace_dir,
                "duration_s": duration,
                **capture,
                "agent_id": self.agent_id,
                "python_tracer": bool(options.python_tracer_level),
            }
        )

    async def h_metrics(self, request: web.Request) -> web.Response:
        doc = {
            "engine": "llm",
            "model": self.config_name,
            "replica": self.replica,
            # the process behind this surface and the chips it was given:
            # the slice ids the scheduler assigned, and the visibility
            # binding the backend started the process under
            "pid": os.getpid(),
            "chips": list(self.chips),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS") or None,
            "requests_total": self.requests_total,
            "journal_dispatch_ms_samples": [
                round(x, 3) for x in self.journal_dispatch_ms_recent
            ],
            "journal_dispatch_ms_p50": (
                round(jd[len(jd) // 2], 3)
                if (jd := sorted(self.journal_dispatch_ms_recent))
                else None
            ),
            "model_loaded": self.engine is not None,
            "engine_error": self.engine_error or None,
            "kv_snapshots": self.kv_snapshots,
            "kv_snapshot_bytes": self.kv_snapshot_bytes,
            "kv_snapshots_deferred": self.kv_snapshots_deferred,
            "kv_restores": self.kv_restores,
            "prefix_prewarms": self.prefix_prewarms,
            "kv_park_errors": self.kv_park_errors,
            "kv_prewarm_errors": self.kv_prewarm_errors,
            "kv_snapshot_errors": self.kv_snapshot_errors,
            "last_kv_snapshot_error": self.last_kv_snapshot_error or None,
            "unhandled_errors": self.unhandled_errors,
            "last_unhandled_error": self.last_unhandled_error or None,
            "drain_budget_s": self.drain_budget_s,
            "drained_clean": self.drained_clean,
            "drain_snapshots": self.drain_snapshots,
            "streams_started": self.streams_started,
            "stream_tokens_emitted": self.stream_tokens_emitted,
            "stream_heartbeats": self.stream_heartbeats,
            "stream_client_disconnects": self.stream_client_disconnects,
        }
        host = self._host if self._host is not None else self
        # set-up cost of the engine this surface serves from: seconds to
        # build it (weights + warm-up compiles), whether a warm boot skipped
        # the warm-up, the boot's timeline from main's entry to ready with
        # its stages (utils/boot.py), and what the process asked of the
        # compile cache, by program
        doc["engine_load_s"] = host.engine_load_s
        doc["warmup_skipped"] = host.warmup_skipped
        doc["boot"] = host._boot.as_dict()
        if host._compile_stats is not None:
            doc["compile_cache"] = host._compile_stats.as_dict()
        if self._host is not None or self._tenants:
            # HBM audit for the sharing demo: engine-level hbm byte counts
            # below are ONE physical copy serving every attached agent
            doc["weights_shared"] = True
            doc["tenants"] = len(
                (self._host._tenants if self._host is not None else self._tenants)
            )
        if self.engine is not None:
            doc.update(self.engine.metrics())
        return web.json_response(doc)


def serve(boot: BootTimeline | None = None) -> None:
    app_obj = LLMServeApp(compile_stats=enable_compile_cache(), boot=boot)
    if boot is not None:
        boot.imported()
    port = int(os.environ.get("AGENTAINER_PORT", "8000"))
    web.run_app(app_obj.app(), host="127.0.0.1", port=port, print=None)
