"""The system under test, started and driven through its public surface.

The pattern is ``chip_smoke.py``'s (``Daemon``/``Agent``), copied so that the
yardstick does not move when the program's smoke does: the daemon is
``python -m agentainer_tpu.cli server`` as a child of the harness, the engine
hosts are children of the daemon, and nothing here imports JAX — a parent
that had touched JAX would hold the chip its engine needs.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
TOKEN = "benchmark-token"


# What binds a process to chip 0 alone (the program's own binding for a
# one-chip engine host, ``runtime/local.chip_visibility_env``): the numerics
# child runs under it, so that it compiles the programs the engine host
# runs, and the engine finds them in the compile cache.
ONE_CHIP_ENV = {
    "TPU_VISIBLE_CHIPS": "0",
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
    "TPU_PROCESS_BOUNDS": "1,1,1",
    "TPU_MESH_CONTROLLER_ADDRESS": "localhost:8476",
    "TPU_MESH_CONTROLLER_PORT": "8476",
}


class PhaseFailure(Exception):
    """A phase did not meet its condition: the run exits non-zero and prints
    no result line."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailure(what)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def descendants(root: int) -> set[int]:
    """Every live process below ``root`` (the daemon's engine hosts and
    whatever they started), from ``/proc``."""
    parent: dict[int, int] = {}
    for name in os.listdir("/proc"):
        if name.isdigit():
            try:
                with open(f"/proc/{name}/stat") as f:
                    parent[int(name)] = int(f.read().rsplit(") ", 1)[-1].split()[1])
            except (OSError, ValueError, IndexError):
                pass
    found: set[int] = set()
    frontier = {root}
    while frontier:
        frontier = {p for p, pp in parent.items() if pp in frontier} - found
        found |= frontier
    return found


def alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(") ", 1)[-1].split()[0] != "Z"
    except OSError:
        return False


class Daemon:
    """The control plane as a child process, and the REST calls against it.
    ``config_path`` is the cell's configuration file: the start-up hook in
    ``benchmark/site`` registers its model in the daemon and, through the
    inherited environment, in the engine host."""

    def __init__(self, env: dict, chips: int, config_path: str):
        self.port = free_port()
        self.data_dir = tempfile.mkdtemp(prefix="atpu-bench-")
        self.log_path = os.path.join(self.data_dir, "daemon.log")
        env = dict(env)
        env.update(
            {
                "ATPU_SERVER_HOST": "127.0.0.1",
                "ATPU_SERVER_PORT": str(self.port),
                "ATPU_AUTH_TOKEN": TOKEN,
                "ATPU_DATA_DIR": self.data_dir,
                "ATPU_SLICE_CHIPS": str(chips),
                "ATPU_BENCH_CONFIG": config_path,
                "PYTHONPATH": os.pathsep.join(
                    p for p in (os.path.join(BENCH, "site"), REPO, env.get("PYTHONPATH", "")) if p
                ),
            }
        )
        self._log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "agentainer_tpu.cli", "server", "--port", str(self.port)],
            env=env,
            cwd=REPO,
            stdout=self._log,
            stderr=subprocess.STDOUT,
        )
        self.engine_pids: set[int] = set()
        deadline = time.monotonic() + 180  # the native front door builds itself on first use
        while True:
            check(self.proc.poll() is None, f"daemon exited at start: {self.tail_log()}")
            try:
                status, doc = self.call("GET", "/health", timeout=2)
                if status == 200:
                    self.health = doc.get("data", doc)
                    return
            except (urllib.error.URLError, OSError):
                pass
            check(time.monotonic() < deadline, "daemon did not answer /health in 180 s")
            time.sleep(0.2)

    @property
    def base(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def tail_log(self, n: int = 30) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def call(self, method: str, path: str, body: dict | None = None, timeout: float = 120):
        req = urllib.request.Request(
            self.base + path,
            method=method,
            data=None if body is None else json.dumps(body).encode(),
            headers={"Authorization": f"Bearer {TOKEN}", "Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                status, raw = resp.status, resp.read()
        except urllib.error.HTTPError as e:
            status, raw = e.code, e.read()
        try:
            doc = json.loads(raw) if raw else {}
        except json.JSONDecodeError:
            doc = {"raw": raw[:500].decode("utf-8", "replace")}
        return status, doc

    def mgmt(self, method: str, path: str, body: dict | None = None, timeout: float = 180) -> dict:
        status, doc = self.call(method, path, body, timeout)
        check(status == 200 and doc.get("success", True), f"{method} {path} -> {status}: {doc}")
        return doc.get("data", doc)

    def engine_logs(self, agent_id: str, tail: int = 60) -> list:
        status, doc = self.call("GET", f"/agents/{agent_id}/logs?tail={tail}", timeout=10)
        return (doc.get("data") or {}).get("logs", doc) if status == 200 else [str(doc)]

    def diagnosis(self) -> str:
        """Daemon and engine log tails, for stderr after a failed phase."""
        parts = ["--- daemon log ---", self.tail_log(25)]
        try:
            _, agents = self.call("GET", "/agents", timeout=10)
            for a in agents.get("data") or []:
                parts.append(f"--- engine log: {a.get('name')} ---")
                parts.extend(map(str, self.engine_logs(a["id"])))
        except (urllib.error.URLError, OSError):
            pass
        return "\n".join(parts)

    def close(self) -> None:
        # SIGINT: the CLI's handler unwinds run_daemon, whose cleanup stops
        # every engine host it spawned. Whatever is left below the daemon
        # after that (an engine whose start was cut short keeps its chip) is
        # killed: nothing this run started may outlive it.
        self.engine_pids |= descendants(self.proc.pid)
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)
        for pid in self.engine_pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        deadline = time.monotonic() + 15
        while any(alive(p) for p in self.engine_pids) and time.monotonic() < deadline:
            time.sleep(0.1)
        self._log.close()
        shutil.rmtree(self.data_dir, ignore_errors=True)


class Agent:
    """One deployed ``llm:<config>`` agent: one engine on one chip."""

    def __init__(self, d: Daemon, config: dict):
        self.d = d
        body = {
            "name": "bench",
            "model": {
                "engine": "llm",
                "config": config["name"],
                "options": dict(config.get("engine_options") or {}),
            },
            # the claim is the engine's real footprint
            "resources": {"chips": 1, "hbm_bytes": int(config["hbm_claim_bytes_per_chip"])},
        }
        self.id = d.mgmt("POST", "/agents", body)["id"]

    @property
    def chat_url(self) -> str:
        return f"{self.d.base}/agent/{self.id}/chat"

    def start(self) -> None:
        self.d.mgmt("POST", f"/agents/{self.id}/start", timeout=300)

    def engine_docs(self) -> list[dict]:
        """The engine's own live ``/metrics`` answer, as a list of one: the
        per-layer readers sum over engines, so that a fleet's cell can come
        later without a change to any of them."""
        status, m = self.d.call("GET", f"/agent/{self.id}/metrics", timeout=30)
        check(status == 200, f"/agent/{self.id}/metrics -> {status}: {m}")
        if m.get("pid"):
            self.d.engine_pids.add(int(m["pid"]))
        return [m]

    def wait_loaded(self, budget_s: float) -> list[dict]:
        deadline = time.monotonic() + budget_s
        while True:
            docs = self.engine_docs()
            for m in docs:
                if m.get("engine_error"):
                    raise PhaseFailure(f"engine failed to load: {m['engine_error']}")
            if all(m.get("model_loaded") for m in docs):
                return docs
            check(time.monotonic() < deadline, f"model not loaded in {budget_s:.0f} s")
            time.sleep(1.0)

    def journal(self) -> dict:
        # the journal's counts; ``status=failed`` keeps the listing beside them short
        return self.d.mgmt("GET", f"/agents/{self.id}/requests?status=failed", timeout=60)["stats"]

    def profile(self, duration_s: float) -> dict:
        return self.d.mgmt(
            "POST", f"/agents/{self.id}/profile", {"duration_s": duration_s}, timeout=duration_s + 60
        )

    def generate_greedy(self, prompt: str, n: int) -> list[int]:
        status, doc = self.d.call(
            "POST",
            f"/agent/{self.id}/generate",
            {"prompt": prompt, "max_tokens": n, "temperature": 0.0},
            timeout=300,
        )
        check(status == 200 and isinstance(doc.get("tokens"), list), f"generate -> {status}: {doc}")
        return [int(x) for x in doc["tokens"]]


def device_of(docs: list[dict], want_chips: int, rehearse: bool) -> dict:
    """The device as the engines report it from ``jax.devices()`` (an engine
    process sees only the chips it was bound to, so the count is the sum over
    the engines). ``memory_peak_bytes`` is the fullest chip."""
    devs = [m.get("device") or {} for m in docs]
    check(all({"platform", "kind", "count"} <= set(d) for d in devs), f"engine names no device: {devs}")
    check(len({(d["platform"], d["kind"]) for d in devs}) == 1, f"engines on different devices: {devs}")
    count = sum(int(d["count"]) for d in devs)
    if not rehearse:
        check(devs[0]["platform"] == "tpu", f"no accelerator: the engine runs on {devs[0]}")
        check(count == want_chips, f"the cell asks for {want_chips} chip(s), the engines hold {count}")
    peaks = [
        int(x.get("peak_bytes_in_use") or x.get("bytes_in_use") or 0)
        for m in docs
        for x in (m.get("engine_devices") or [])
    ]
    return {
        "platform": devs[0]["platform"],
        "kind": devs[0]["kind"],
        "count": count,
        "memory_peak_bytes": max(peaks) if peaks else 0,
    }
