"""Daemon wiring — the composition root.

The analogue of the reference's ``runServer`` (cmd/agentainer/main.go:284-356):
construct infra adapters (store, backend, scheduler), services (manager,
journal, health, metrics, reconciler, backups, log plane), the API server,
and the background loops (state sync at 10s, replay at 5s, metrics at 10s,
health per-agent), then serve until stopped.
"""

from __future__ import annotations

import asyncio
import os
from dataclasses import dataclass, field
from typing import Awaitable, Callable

from aiohttp import web

from .config import Config, load_config
from .manager.agents import AgentManager
from .manager.audit import LogPlane
from .manager.backup import BackupManager
from .manager.health import HealthMonitor
from .manager.journal import RequestJournal
from .manager.metrics import MetricsPlane
from .manager.reconcile import QuickSync, StateSynchronizer
from .manager.replay import ReplayWorker
from .runtime.backend import Backend
from .runtime.scheduler import SliceScheduler, SliceTopology
from .store import Store, open_store


@dataclass
class Services:
    config: Config
    store: Store
    backend: Backend
    scheduler: SliceScheduler
    manager: AgentManager
    journal: RequestJournal
    logs: LogPlane
    metrics: MetricsPlane
    backups: BackupManager
    artifacts: "ArtifactRegistry" = None  # type: ignore[assignment]
    data_dir: str = ""
    health: HealthMonitor = None  # type: ignore[assignment]
    quick_sync: QuickSync = None  # type: ignore[assignment]
    state_sync: StateSynchronizer = None  # type: ignore[assignment]
    replay: ReplayWorker = None  # type: ignore[assignment]
    # fleet plane (multi-replica agents): the proxy's routing tier, the
    # lease-driven replica monitor, and the dead-replica repair path
    router: object = None
    replica_monitor: object = None
    fleet_repair: object = None
    dispatch: Callable[..., Awaitable[tuple[int, dict, bytes]]] = None  # type: ignore[assignment]
    dataplane: object = None  # NativeDataPlane when the C++ listener is up
    public_port: int = 0  # actual bound public port once run_daemon is up
    _background_started: bool = field(default=False, repr=False)


def build_services(
    config: Config | None = None,
    store: Store | None = None,
    backend: Backend | None = None,
    console_logs: bool = True,
    data_dir: str | None = None,
) -> Services:
    config = config or load_config()
    # engines inherit the daemon's environment (runtime/local.py builds
    # their env from os.environ). The three policies with a half in the
    # proxy and a half in the engine reach the engine half this way, as a
    # write-back of the resolved value: load_config already folded any
    # operator-set variable into the config, and a second build_services
    # with a different config must not inherit a stale latch. An engine
    # switch is not a policy of the daemon's: it is set in the deployment's
    # model.options and nowhere else.
    os.environ["ATPU_KV_TIERING"] = "1" if config.features.kv_tiering else "0"
    os.environ["ATPU_STREAMING"] = "1" if config.features.streaming else "0"
    os.environ["ATPU_DEADLINES"] = "1" if config.deadlines.enabled else "0"
    # Fault plane: the registry and the ATPU_FAULTS env the engines inherit
    # always reflect THIS config's schedule — same write-back-the-resolved-
    # value discipline as the policies above: an empty spec must clear a
    # previously armed registry and the stale env latch, or "faults
    # disabled" would keep firing in the daemon and every spawned engine.
    from . import faults as _faults

    _faults.disarm_all()
    if config.resilience.faults:
        _faults.arm_spec(config.resilience.faults)
    os.environ["ATPU_FAULTS"] = config.resilience.faults
    # engine store clients read their retry policy from the env they
    # inherit; load_config already folded operator env into the config, so
    # this is a write-back of the resolved values
    os.environ["ATPU_STORE_RETRIES"] = str(config.resilience.store_retries)
    os.environ["ATPU_STORE_RETRY_BASE_S"] = str(config.resilience.store_retry_base_s)
    ddir = data_dir if data_dir is not None else config.data_path
    if store is None:
        url = config.store_url
        if url == "auto":
            # native store + AOF durability when the library builds — the
            # Redis-persistence role in the reference; memory store otherwise
            from .native import available as native_available

            if native_available():
                import os as _os

                _os.makedirs(str(ddir), exist_ok=True)
                url = f"native://{ddir}/store.aof"
            else:
                url = "mem://"
        store = open_store(url)
    # the slice this machine holds, as configured for it (slice.total_chips
    # / ATPU_SLICE_CHIPS): the scheduler places onto it and the backend
    # binds each engine process to its placement's chips
    topo = SliceTopology(
        total_chips=config.slice.total_chips,
        hbm_per_chip=config.slice.hbm_per_chip,
        name=config.slice.name,
        hosts=config.slice.hosts,
    )
    if backend is None:
        from .runtime.local import LocalBackend

        backend = LocalBackend(
            store=store,
            topology=topo,
            restart_backoff_base_s=config.resilience.restart_backoff_base_s,
            restart_backoff_max_s=config.resilience.restart_backoff_max_s,
            restart_window_s=config.resilience.restart_window_s,
            restart_max_rapid=config.resilience.restart_max_rapid,
        )
    elif getattr(backend, "store", "absent") is None:
        backend.store = store  # LocalBackend built without a store: inject ours
    # multi-host note: jax.distributed is joined by the ENGINE subprocesses
    # (runtime/engine_main.py) — they run the JAX compute; the control-plane
    # daemon must never block on the cluster barrier.
    scheduler = SliceScheduler(store, topo)
    manager = AgentManager(store, backend, scheduler)
    journal = RequestJournal(store)
    logs = LogPlane(store, data_dir=ddir, console=console_logs)
    metrics = MetricsPlane(
        manager, store, interval_s=config.cadences.metrics_interval_s, logs=logs
    )
    backups = BackupManager(manager, store, ddir)
    from .manager.artifacts import ArtifactRegistry

    artifacts = ArtifactRegistry(store)

    services = Services(
        config=config,
        store=store,
        backend=backend,
        scheduler=scheduler,
        manager=manager,
        journal=journal,
        logs=logs,
        metrics=metrics,
        backups=backups,
        artifacts=artifacts,
        data_dir=str(ddir),
    )

    quick_sync = QuickSync(manager, backend)
    manager.set_quick_sync(quick_sync)
    services.quick_sync = quick_sync
    services.state_sync = StateSynchronizer(
        quick_sync, backend, interval_s=config.cadences.state_sync_s
    )

    # The app's dispatch function is the single choke point for traffic into
    # engines; replay and health reuse it (set in create_app).
    from .server.app import ControlPlaneApp

    app_obj = ControlPlaneApp(services)
    services.dispatch = app_obj.dispatch_to_agent
    services.app = app_obj.app  # type: ignore[attr-defined]

    services.health = HealthMonitor(manager, store, services.dispatch, logs=logs)
    services.replay = ReplayWorker(
        journal,
        manager,
        services.dispatch,
        interval_s=config.cadences.replay_scan_s,
        backend=backend,
    )

    # fleet plane: replica leases + fleet-wide repair. The monitor only
    # probes agents with >1 replica, so a fleet.replicas=1 deployment runs
    # zero extra traffic (the A/B baseline).
    from .manager.health import ReplicaMonitor
    from .manager.reconcile import FleetRepair

    manager.set_fleet(config.fleet.replicas, config.fleet.lease_ttl_s)
    services.router = app_obj.router
    services.fleet_repair = FleetRepair(
        manager, journal, router=app_obj.router, replay=services.replay, logs=logs
    )
    services.replica_monitor = ReplicaMonitor(
        manager,
        store,
        router=app_obj.router,
        repair=services.fleet_repair,
        lease_ttl_s=config.fleet.lease_ttl_s,
        lease_interval_s=config.fleet.lease_interval_s,
        suspect_after_s=config.fleet.suspect_after_s,
        dead_after_s=config.fleet.dead_after_s,
        logs=logs,
    )
    return services


async def start_background(services: Services) -> None:
    """Start the reconciler, replay worker, metrics collector, and health
    monitor (runServer's goroutines, main.go:325-341 + server.go:124-135)."""
    if services._background_started:
        return
    services._background_started = True
    await services.state_sync.start()
    if services.config.features.request_persistence:
        await services.replay.start()
    await services.metrics.start()
    await services.health.start()
    if services.replica_monitor is not None:
        await services.replica_monitor.start()


async def stop_background(services: Services) -> None:
    if not services._background_started:
        return
    services._background_started = False
    if services.replica_monitor is not None:
        await services.replica_monitor.stop()
    await services.replay.stop()
    await services.state_sync.stop()
    await services.metrics.stop()
    await services.health.stop()


def _try_start_dataplane(services: Services, mgmt_port: int):
    """Start the C++ front door on the public port: /agent/* and the engine
    store socket served natively, management forwarded to aiohttp on
    ``mgmt_port``. Returns the NativeDataPlane or None (pure-Python mode)."""
    cfg = services.config
    if not cfg.features.native_dataplane:
        return None
    from .store.native import NativeStore

    if not isinstance(services.store, NativeStore):
        return None
    try:
        import os as _os

        from .runtime.dataplane import NativeDataPlane

        _os.makedirs(services.data_dir, exist_ok=True)
        uds_path = str(_os.path.join(services.data_dir, "store.sock"))
        dp = NativeDataPlane(
            services.store,
            cfg.server.host,
            cfg.server.port,
            "127.0.0.1",
            mgmt_port,
            uds_path,
        )
    except Exception as e:
        services.logs.warn("daemon", f"native data plane unavailable: {e}")
        return None

    persist = cfg.features.request_persistence

    def route_hook(agent, agent_id: str) -> None:
        if agent is None:
            dp.route_del(agent_id)
        else:
            endpoint = services.manager.endpoint(agent)
            if len(agent.all_engine_ids()) > 1:
                # replica fleet: no single endpoint is correct — install a
                # python-owned route (port 0) so the C++ front door hands
                # /agent/* for this agent to the aiohttp proxy, where the
                # routing tier (affinity, health exclusion, bounded
                # cross-replica retry) owns the dispatch. Single-replica
                # agents keep the zero-Python native fast path.
                endpoint = None
            dp.route_set(
                agent_id,
                endpoint,
                agent.status.value,
                persist,
            )

    services.manager.set_route_hook(route_hook)
    services.metrics.set_native_drain(dp.counters_drain)
    if hasattr(services.backend, "set_store_sock"):
        services.backend.set_store_sock(uds_path)
    services.dataplane = dp
    return dp


async def run_daemon(services: Services) -> None:
    """Serve until cancelled (SIGINT/SIGTERM handling lives in the CLI)."""
    runner = web.AppRunner(services.app)  # type: ignore[attr-defined]
    await runner.setup()
    cfg = services.config
    # With the native data plane, aiohttp binds an internal loopback port and
    # the C++ listener owns the public one; otherwise aiohttp is the front.
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    mgmt_port = runner.addresses[0][1]
    dp = _try_start_dataplane(services, mgmt_port)
    if dp is None:
        public_site = web.TCPSite(runner, cfg.server.host, cfg.server.port)
        await public_site.start()
        public_port = cfg.server.port
        if public_port == 0:  # ephemeral: resolve what the kernel picked
            public_port = public_site._server.sockets[0].getsockname()[1]
    else:
        public_port = dp.port  # differs from config when port 0 = ephemeral
    services.public_port = public_port
    if hasattr(services.backend, "set_control"):
        services.backend.set_control(
            f"http://127.0.0.1:{public_port}", services.config.auth_token
        )
    await start_background(services)
    services.logs.info(
        "daemon",
        f"control plane listening on {cfg.server.host}:{public_port} "
        f"(slice {services.scheduler.topology.name}, "
        f"data plane {'native' if dp else 'python'})",
    )
    try:
        while True:
            await asyncio.sleep(3600)
    finally:
        # a cancellation landing inside stop_background's awaits must not
        # skip dp.stop(): the data plane references the store, which the
        # owner may free right after run_daemon returns
        try:
            await stop_background(services)
        except asyncio.CancelledError:
            pass
        if dp is not None:
            dp.stop()
        services.backend.close()
        await runner.cleanup()
