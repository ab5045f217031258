"""Layered daemon configuration.

Parity with the reference's viper config (internal/config/config.go:49-107):
YAML file searched in ``.``, ``~/.agentainer_tpu``, ``/etc/agentainer_tpu``;
environment overrides with an ``ATPU_`` prefix; defaults matching the
reference's envelope (server on :8081, static bearer token, request
persistence on). TPU additions: store URL (mem:// by default — no Redis
sidecar needed on a TPU-VM) and the slice topology the scheduler manages.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from pathlib import Path

import yaml

DEFAULT_TOKEN = "agentainer-default-token"  # config.go:66 parity


@dataclass
class ServerConfig:
    host: str = "0.0.0.0"
    port: int = 8081


@dataclass
class SliceConfig:
    total_chips: int = 8
    hbm_per_chip: int = 16 * 1024**3
    name: str = "v5e-8"
    hosts: int = 1  # multi-host slices: chips split evenly across hosts


@dataclass
class FeatureFlags:
    request_persistence: bool = True  # config.go:70
    auto_restart_default: bool = False
    # Serve /agent/* + the engine store socket from the C++ data plane when
    # the native library is available (falls back to the aiohttp proxy).
    native_dataplane: bool = True
    # Default for the tiered KV hierarchy (device → pinned host RAM →
    # store): idle sessions park off-device and promote back at their
    # next turn, with pool-pressure demotion converting 429s into
    # slower-but-served admissions. Off by default — tiering is the
    # opt-in density lever; the resident-only arena is the A/B baseline.
    kv_tiering: bool = False
    # Proxy-side park linger: seconds an idle session must stay silent
    # after its response settles before the proxy parks it off-device.
    # Sized to agentic tool-call gaps — a tool round-trip inside the
    # linger cancels the park; anything longer pays one prewarm instead.
    tier_park_linger_s: float = 1.0
    # Default for SSE token streaming (stream=true on /chat): the proxy
    # forwards the engine's event stream with every offset journaled as a
    # streaming checkpoint, so a mid-stream crash fails over gaplessly.
    # Off by default — the buffered response path is the A/B baseline and
    # stays byte-identical while this is off.
    streaming: bool = False


@dataclass
class DeadlineConfig:
    """End-to-end request deadlines + overload shedding.

    ``enabled: false`` preserves the pre-deadline behavior everywhere
    (no default deadline, no shedding, no disconnect propagation) — the
    A/B baseline. Watermarks are depth thresholds at which the proxy
    answers ``429 + Retry-After`` instead of journaling more work that
    will expire unserved."""

    enabled: bool = True
    # default per-request budget when the caller sends no
    # X-Agentainer-Deadline-Ms header; 0 = no default deadline
    default_ms: float = 30000.0
    # per-agent pending-journal depth that starts shedding (0 = off)
    shed_pending_per_agent: int = 64
    # global pending ceiling across every agent (0 = off)
    shed_pending_global: int = 512
    # engine queue+waiting depth (from the latest metrics sample) that
    # starts shedding for that agent (0 = don't consult engine depth)
    engine_queue_watermark: int = 0
    # Retry-After seconds on shed responses
    retry_after_s: float = 1.0


@dataclass
class FleetConfig:
    """Replica fleet: N engine replicas per agent behind the routing tier.

    ``replicas: 1`` (the default) is the pre-fleet behavior exactly — one
    engine per agent, no routing tier, no lease monitor traffic — and is
    the A/B baseline. With N > 1 each replica is its own failure domain
    (own process, own port, own crash-loop watcher); sessions are routed
    with KV-residency affinity, fresh sessions by power-of-two-choices on
    in-flight depth, and a dead replica's sessions fail over to a survivor
    via the store-durable KV snapshot (token-identical resume). Per-deploy
    ``replicas`` in the agent body overrides the fleet default."""

    replicas: int = 1
    # replica heartbeat lease: the monitor probes each replica every
    # lease_interval_s and refreshes a store lease with lease_ttl_s; a
    # replica whose lease is older than suspect_after_s is SUSPECT
    # (excluded from routing), older than dead_after_s is DEAD (repaired)
    lease_ttl_s: float = 6.0
    lease_interval_s: float = 1.0
    suspect_after_s: float = 3.0
    dead_after_s: float = 6.0
    # bounded cross-replica retry for connection-level dispatch failures
    # (nothing executed on the dead replica, and the journal CAS admits
    # exactly one dispatcher, so the retry cannot double-execute)
    retry_next_replica: int = 2
    # per-replica circuit breaker (one bad replica must not open a breaker
    # for the whole agent)
    breaker_failures: int = 3
    breaker_cooldown_s: float = 2.0


@dataclass
class ResilienceConfig:
    """Crash-loop backoff, store-outage degradation, and fault injection.

    The backoff knobs govern the local backend's restart watcher: a
    crashed engine respawns immediately once, then with exponential delay
    (``restart_backoff_base_s`` doubling up to ``restart_backoff_max_s``);
    an incarnation that dies within ``restart_window_s`` of its spawn
    counts as a *rapid* death, and after ``restart_max_rapid`` of those in
    a row the agent lands FAILED with a recorded reason instead of
    hot-looping forever. The breaker knobs govern the proxy's store
    circuit breaker (503 + Retry-After instead of hanging on a dead
    store); the store_retry knobs govern the engine store client's bounded
    retry. ``faults`` is a failpoint arming spec (agentainer_tpu/faults.py
    grammar) applied at daemon startup — empty (the default) means the
    fault plane is entirely disarmed and zero-overhead."""

    restart_backoff_base_s: float = 0.5
    restart_backoff_max_s: float = 30.0
    restart_window_s: float = 30.0
    restart_max_rapid: int = 5
    store_retries: int = 3
    store_retry_base_s: float = 0.05
    breaker_failures: int = 5
    breaker_cooldown_s: float = 2.0
    faults: str = ""


@dataclass
class Cadences:
    """Background-loop intervals, reference values (BASELINE.md)."""

    state_sync_s: float = 10.0  # main.go:325
    replay_scan_s: float = 5.0  # replay_worker.go:37
    health_interval_s: float = 30.0  # monitor.go:119
    metrics_interval_s: float = 10.0  # collector.go:205


@dataclass
class Config:
    server: ServerConfig = field(default_factory=ServerConfig)
    slice: SliceConfig = field(default_factory=SliceConfig)
    features: FeatureFlags = field(default_factory=FeatureFlags)
    cadences: Cadences = field(default_factory=Cadences)
    deadlines: DeadlineConfig = field(default_factory=DeadlineConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    auth_token: str = DEFAULT_TOKEN
    # "auto": native C++ store with AOF durability when the library builds,
    # in-memory store otherwise. Explicit: mem:// | native://[aof-path]
    store_url: str = "auto"
    data_dir: str = "~/.agentainer_tpu"

    @property
    def data_path(self) -> Path:
        return Path(os.path.expanduser(self.data_dir))


_SEARCH_PATHS = [".", "~/.agentainer_tpu", "/etc/agentainer_tpu"]

# Engine switches that older config files carried under ``features:`` as
# fleet defaults. They are keys of a deployment's ``model.options`` and of
# nothing else; a file that still has one is refused, not half obeyed.
ENGINE_SWITCHES = (
    "speculative",
    "paged_kv",
    "adaptive_decode",
    "prefix_cache",
    "fused_decode",
    "inloop_spec",
    "approx_topk",
)


def load_config(path: str | None = None) -> Config:
    cfg = Config()
    doc: dict = {}
    candidates = [path] if path else [os.path.join(os.path.expanduser(p), "config.yaml") for p in _SEARCH_PATHS]
    for cand in candidates:
        if cand and os.path.isfile(cand):
            with open(cand) as f:
                doc = yaml.safe_load(f) or {}
            break

    server = doc.get("server", {})
    cfg.server.host = server.get("host", cfg.server.host)
    cfg.server.port = int(server.get("port", cfg.server.port))
    sl = doc.get("slice", {})
    cfg.slice.total_chips = int(sl.get("total_chips", cfg.slice.total_chips))
    cfg.slice.hbm_per_chip = int(sl.get("hbm_per_chip", cfg.slice.hbm_per_chip))
    cfg.slice.name = sl.get("name", cfg.slice.name)
    cfg.slice.hosts = int(sl.get("hosts", cfg.slice.hosts))
    feats = doc.get("features", {})
    moved = [k for k in ENGINE_SWITCHES if k in feats]
    if moved:
        raise ValueError(
            "config.yaml: "
            + ", ".join(f"features.{k}" for k in moved)
            + " is not read: an engine switch is set on the deployment, as "
            + ", ".join(f"model.options.{k}" for k in moved)
            + " (deploy --option KEY=VALUE, or options: in a deployment YAML)"
        )
    cfg.features.request_persistence = bool(
        feats.get("request_persistence", cfg.features.request_persistence)
    )
    dl = doc.get("deadlines", {})
    cfg.deadlines.enabled = bool(dl.get("enabled", cfg.deadlines.enabled))
    cfg.deadlines.default_ms = float(dl.get("default_ms", cfg.deadlines.default_ms))
    cfg.deadlines.shed_pending_per_agent = int(
        dl.get("shed_pending_per_agent", cfg.deadlines.shed_pending_per_agent)
    )
    cfg.deadlines.shed_pending_global = int(
        dl.get("shed_pending_global", cfg.deadlines.shed_pending_global)
    )
    cfg.deadlines.engine_queue_watermark = int(
        dl.get("engine_queue_watermark", cfg.deadlines.engine_queue_watermark)
    )
    cfg.deadlines.retry_after_s = float(
        dl.get("retry_after_s", cfg.deadlines.retry_after_s)
    )
    res = doc.get("resilience", {})
    cfg.resilience.restart_backoff_base_s = float(
        res.get("restart_backoff_base_s", cfg.resilience.restart_backoff_base_s)
    )
    cfg.resilience.restart_backoff_max_s = float(
        res.get("restart_backoff_max_s", cfg.resilience.restart_backoff_max_s)
    )
    cfg.resilience.restart_window_s = float(
        res.get("restart_window_s", cfg.resilience.restart_window_s)
    )
    cfg.resilience.restart_max_rapid = int(
        res.get("restart_max_rapid", cfg.resilience.restart_max_rapid)
    )
    cfg.resilience.store_retries = int(
        res.get("store_retries", cfg.resilience.store_retries)
    )
    cfg.resilience.store_retry_base_s = float(
        res.get("store_retry_base_s", cfg.resilience.store_retry_base_s)
    )
    cfg.resilience.breaker_failures = int(
        res.get("breaker_failures", cfg.resilience.breaker_failures)
    )
    cfg.resilience.breaker_cooldown_s = float(
        res.get("breaker_cooldown_s", cfg.resilience.breaker_cooldown_s)
    )
    cfg.resilience.faults = str(res.get("faults", cfg.resilience.faults))
    fl = doc.get("fleet", {})
    cfg.fleet.replicas = int(fl.get("replicas", cfg.fleet.replicas))
    cfg.fleet.lease_ttl_s = float(fl.get("lease_ttl_s", cfg.fleet.lease_ttl_s))
    cfg.fleet.lease_interval_s = float(
        fl.get("lease_interval_s", cfg.fleet.lease_interval_s)
    )
    cfg.fleet.suspect_after_s = float(
        fl.get("suspect_after_s", cfg.fleet.suspect_after_s)
    )
    cfg.fleet.dead_after_s = float(fl.get("dead_after_s", cfg.fleet.dead_after_s))
    cfg.fleet.retry_next_replica = int(
        fl.get("retry_next_replica", cfg.fleet.retry_next_replica)
    )
    cfg.fleet.breaker_failures = int(
        fl.get("breaker_failures", cfg.fleet.breaker_failures)
    )
    cfg.fleet.breaker_cooldown_s = float(
        fl.get("breaker_cooldown_s", cfg.fleet.breaker_cooldown_s)
    )
    sec = doc.get("security", {})
    cfg.auth_token = sec.get("auth_token", cfg.auth_token)
    cfg.store_url = doc.get("store", {}).get("url", cfg.store_url)
    cfg.data_dir = doc.get("data_dir", cfg.data_dir)

    # Env overrides, explicit binds like the reference's AGENTAINER_* set
    # (config.go:72-81).
    env = os.environ
    cfg.server.host = env.get("ATPU_SERVER_HOST", cfg.server.host)
    cfg.server.port = int(env.get("ATPU_SERVER_PORT", cfg.server.port))
    cfg.auth_token = env.get("ATPU_AUTH_TOKEN", cfg.auth_token)
    cfg.store_url = env.get("ATPU_STORE_URL", cfg.store_url)
    cfg.data_dir = env.get("ATPU_DATA_DIR", cfg.data_dir)
    if "ATPU_SLICE_CHIPS" in env:
        # the slice size is a fact about the machine the daemon runs on
        # (1 for a single-chip host, 4 for a v5e 2x2): engines are bound to
        # the chips the scheduler hands out of it
        cfg.slice.total_chips = int(env["ATPU_SLICE_CHIPS"])
        if "name" not in sl:
            cfg.slice.name = f"v5e-{cfg.slice.total_chips}"
    if "ATPU_SLICE_HOSTS" in env:
        cfg.slice.hosts = int(env["ATPU_SLICE_HOSTS"])
    if "ATPU_DEADLINES" in env:
        cfg.deadlines.enabled = env["ATPU_DEADLINES"].lower() in ("1", "true", "yes")
    if "ATPU_DEADLINE_DEFAULT_MS" in env:
        cfg.deadlines.default_ms = float(env["ATPU_DEADLINE_DEFAULT_MS"])
    if "ATPU_SHED_PER_AGENT" in env:
        cfg.deadlines.shed_pending_per_agent = int(env["ATPU_SHED_PER_AGENT"])
    if "ATPU_SHED_GLOBAL" in env:
        cfg.deadlines.shed_pending_global = int(env["ATPU_SHED_GLOBAL"])
    if "ATPU_REQUEST_PERSISTENCE" in env:
        cfg.features.request_persistence = env["ATPU_REQUEST_PERSISTENCE"].lower() in (
            "1",
            "true",
            "yes",
        )
    cfg.features.native_dataplane = bool(
        feats.get("native_dataplane", cfg.features.native_dataplane)
    )
    if "ATPU_NATIVE_DATAPLANE" in env:
        cfg.features.native_dataplane = env["ATPU_NATIVE_DATAPLANE"].lower() in (
            "1",
            "true",
            "yes",
        )
    if "ATPU_FLEET_REPLICAS" in env:
        # the env bind completes the fleet flag's operator surface
        # (config.yaml `fleet.replicas` / per-deploy `replicas` / env):
        # malformed values fall back like the other numeric binds
        try:
            cfg.fleet.replicas = int(env["ATPU_FLEET_REPLICAS"])
        except ValueError:
            pass
    if "ATPU_FAULTS" in env:
        # the env spec REPLACES a config-file spec rather than merging:
        # an operator arming from the shell must get exactly that schedule
        cfg.resilience.faults = env["ATPU_FAULTS"]

    def _env_num(name: str, cast, current):
        # malformed resilience numbers fall back to the config value
        # instead of refusing to boot (LocalBackend reads the same vars
        # with the same tolerance — behavior must not depend on which
        # reader hits them first)
        raw = env.get(name)
        if raw is None:
            return current
        try:
            return cast(raw)
        except ValueError:
            return current

    res_cfg = cfg.resilience
    res_cfg.restart_max_rapid = _env_num(
        "ATPU_RESTART_MAX_RAPID", int, res_cfg.restart_max_rapid
    )
    res_cfg.restart_backoff_base_s = _env_num(
        "ATPU_RESTART_BACKOFF_BASE_S", float, res_cfg.restart_backoff_base_s
    )
    res_cfg.restart_backoff_max_s = _env_num(
        "ATPU_RESTART_BACKOFF_MAX_S", float, res_cfg.restart_backoff_max_s
    )
    res_cfg.restart_window_s = _env_num(
        "ATPU_RESTART_WINDOW_S", float, res_cfg.restart_window_s
    )
    res_cfg.store_retries = _env_num("ATPU_STORE_RETRIES", int, res_cfg.store_retries)
    res_cfg.store_retry_base_s = _env_num(
        "ATPU_STORE_RETRY_BASE_S", float, res_cfg.store_retry_base_s
    )
    cfg.features.kv_tiering = bool(
        feats.get("kv_tiering", cfg.features.kv_tiering)
    )
    if "ATPU_KV_TIERING" in env:
        cfg.features.kv_tiering = env["ATPU_KV_TIERING"].lower() in (
            "1",
            "true",
            "yes",
        )
    cfg.features.streaming = bool(
        feats.get("streaming", cfg.features.streaming)
    )
    if "ATPU_STREAMING" in env:
        cfg.features.streaming = env["ATPU_STREAMING"].lower() in (
            "1",
            "true",
            "yes",
        )
    try:
        cfg.features.tier_park_linger_s = float(
            feats.get("tier_park_linger_s", cfg.features.tier_park_linger_s)
        )
    except (TypeError, ValueError):
        pass  # malformed linger keeps the default; tiering still works
    return cfg
