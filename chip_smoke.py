#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, on one
TPU v5e: ``python -m agentainer_tpu.cli server`` → REST deploy of
``llm:llama3-8b`` at its published widths and all 32 layers (int8 weights
generated on the device from a seed) → ``/agent/{id}/chat`` through the
proxy and journal → SIGKILL of the engine host → the journaled request
replayed by a second process on the same chip. Then the non-default engine
(``paged_kv`` + ``fused_decode``) on the same chip. Before any engine
starts, the four attention kernels are checked against
``attention_reference`` on the chip at the model's widths.

This process never imports JAX: a parent that had touched JAX would hold
the chip its engine needs. Everything that runs on the device runs in a
child — the engine host (spawned by the daemon's LocalBackend) or this
file's own ``--kernel-check`` mode — and only one of them at a time.

Every line of standard output is one JSON object. The last is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
with the device as the ENGINE reports it from ``jax.devices()``; earlier
lines carry what is worth keeping (latencies there are smoke readings:
one run, no warm repetition — never quote them as performance). A failed
phase is a non-zero exit and no ``"ok"`` line.

    python chip_smoke.py                 # one chip, as the driver runs it
    python chip_smoke.py --chips 4       # only the paths that need four
    python chip_smoke.py --rehearse      # tiny model, JAX_PLATFORMS=cpu,
                                         # kernels interpreted: control flow
                                         # only, never prints "ok" for a tpu
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
TOKEN = "chip-smoke-token"
DEADLINE_S = 1150.0  # the driver allows 1200 s, compilation included
LOAD_BUDGET_S = 600.0  # one engine: weights on the device + warm-up compiles
HBM_CLAIM_PER_CHIP = 14 << 30  # llama3-8b int8: weights + arena + step temporaries
# bf16 kernels against the float32 reference at highest matmul precision
KERNEL_ATOL = KERNEL_RTOL = 2e-2
PROMPT = (
    "You are an agent on a TPU. The control plane journals every request "
    "so that a crash never loses one. Say what you would do next."
)


class SmokeFailure(Exception):
    """A phase did not meet its condition; the script exits non-zero."""


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# --kernel-check: the only code in this file that imports JAX (child mode)
# ---------------------------------------------------------------------------


def kernel_check(rehearse: bool, seed: int) -> int:
    """Each of the four attention kernels against ``attention_reference``
    on this process's device. On the chip: llama3-8b widths, compiled. In a
    rehearsal: small shapes, interpret mode."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from agentainer_tpu.ops.attention import attention_reference, cache_mask, gather_pages
    from agentainer_tpu.ops.pallas_attention import (
        flash_decode,
        flash_prefill,
        fused_paged_flash_decode,
        fused_paged_flash_prefill,
    )

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    if not rehearse and dev.platform != "tpu":
        print(json.dumps({"ok": False, "device": device, "error": "no accelerator"}))
        return 3
    if rehearse:
        b, h, kv, hd, s, t, ps = 2, 4, 2, 128, 256, 32, 64
    else:  # llama3-8b at the engine's serving shapes
        b, h, kv, hd, s, t, ps = 8, 32, 8, 128, 2048, 256, 64
    nb = s // ps
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rand(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(jnp.bfloat16)

    # ragged decode positions (first, last, page edges) and one prefill
    # chunk deep inside a lane's context
    dpos = jnp.asarray(([s - 1, 0, ps - 1, ps, s // 2, 17, s - 2, 1000 % s] * b)[:b], jnp.int32)
    start = s // 3
    ppos = jnp.arange(start, start + t, dtype=jnp.int32)[None]
    # the dense kernels read layer 1 of a two-layer stack where it lies, the
    # prefill row ``slot`` of it: what the engine's layer scan hands them
    stack_k, stack_v = rand(2, b, s, kv, hd), rand(2, b, s, kv, hd)
    arena_k, arena_v = stack_k[1], stack_v[1]
    slot = b - 1
    row_k, row_v = arena_k[slot : slot + 1], arena_v[slot : slot + 1]
    pool_k, pool_v = rand(b * nb + b, kv, ps, hd), rand(b * nb + b, kv, ps, hd)
    table = jax.random.permutation(next(keys), b * nb + b)[: b * nb].reshape(b, nb).astype(jnp.int32)
    paged_k, paged_v = gather_pages(pool_k, pool_v, table)
    qd, qp = rand(b, h, hd), rand(1, t, h, hd)
    kw = {"interpret": True} if rehearse else {}

    def reference(q, k, v, pos):
        with jax.default_matmul_precision("highest"):
            return attention_reference(q, k, v, mask=cache_mask(pos, k.shape[1]))

    cases = {
        "flash_decode": (
            lambda: flash_decode(qd, stack_k, stack_v, dpos, 1, **kw)[:, None],
            lambda: reference(qd[:, None], arena_k, arena_v, dpos[:, None]),
        ),
        "flash_prefill": (
            lambda: flash_prefill(qp, stack_k, stack_v, ppos, 1, slot, **kw),
            lambda: reference(qp, row_k, row_v, ppos),
        ),
        "fused_paged_flash_decode": (
            lambda: fused_paged_flash_decode(qd, pool_k, pool_v, table, dpos, **kw)[:, None],
            lambda: reference(qd[:, None], paged_k, paged_v, dpos[:, None]),
        ),
        "fused_paged_flash_prefill": (
            lambda: fused_paged_flash_prefill(qp, pool_k, pool_v, table[:1], ppos, **kw),
            lambda: reference(qp, paged_k[:1], paged_v[:1], ppos),
        ),
    }
    report, ok = {}, True
    for name, (kernel, ref) in cases.items():
        got = np.asarray(kernel().astype(jnp.float32))
        want = np.asarray(ref().astype(jnp.float32))
        err = float(np.max(np.abs(got - want)))
        good = bool(
            got.shape == want.shape
            and np.isfinite(got).all()
            and np.allclose(got, want, atol=KERNEL_ATOL, rtol=KERNEL_RTOL)
        )
        report[name] = {"max_abs_err": round(err, 5), "shape": list(got.shape), "ok": good}
        ok = ok and good
    print(
        json.dumps(
            {
                "ok": ok,
                "device": device,
                "widths": {"B": b, "H": h, "KV": kv, "hd": hd, "S": s, "T": t, "page": ps},
                "mode": "interpret" if rehearse else "compiled",
                "tolerance": {"atol": KERNEL_ATOL, "rtol": KERNEL_RTOL, "dtype": "bfloat16"},
                "kernels": report,
            }
        )
    )
    return 0 if ok else 4


# ---------------------------------------------------------------------------
# the parent: no JAX from here on
# ---------------------------------------------------------------------------


def build_native() -> None:
    """Rebuild the C++ store + data plane from the committed sources; the
    smoke does not accept a library it did not see compile."""
    t0 = time.monotonic()
    proc = subprocess.run(
        ["make", "-B", "-C", os.path.join(REPO, "native")],
        capture_output=True,
        text=True,
        timeout=300,
    )
    check(
        proc.returncode == 0,
        f"native build failed (make -C native): {(proc.stderr or proc.stdout)[-600:]}",
    )
    emit("build_native", seconds=round(time.monotonic() - t0, 1))


def run_kernel_child(rehearse: bool, seed: int, env: dict) -> dict:
    t0 = time.monotonic()
    cmd = [sys.executable, os.path.abspath(__file__), "--kernel-check", "--seed", str(seed)]
    if rehearse:
        cmd.append("--rehearse")
    # the child opens the chip and must be gone before an engine starts:
    # run() waits for it
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")]
    check(bool(lines), f"kernel check printed no report (rc {proc.returncode}): {proc.stderr[-800:]}")
    doc = json.loads(lines[-1])
    emit("kernels", seconds=round(time.monotonic() - t0, 1), **doc)
    check(
        proc.returncode == 0 and doc.get("ok") is True,
        f"kernel check failed (rc {proc.returncode}): {doc.get('error') or doc.get('kernels')}",
    )
    return doc["device"]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Daemon:
    """``python -m agentainer_tpu.cli server`` as a child, and the REST
    calls against it. The daemon is control plane only — it never imports
    JAX either; its LocalBackend spawns the engine hosts."""

    def __init__(self, env: dict, chips: int):
        self.port = _free_port()
        self.data_dir = tempfile.mkdtemp(prefix="atpu-smoke-")
        self.log_path = os.path.join(self.data_dir, "daemon.log")
        env = dict(env)
        env.update(
            {
                "ATPU_SERVER_HOST": "127.0.0.1",
                "ATPU_SERVER_PORT": str(self.port),
                "ATPU_AUTH_TOKEN": TOKEN,
                "ATPU_DATA_DIR": self.data_dir,
                # the slice is what THIS machine holds
                "ATPU_SLICE_CHIPS": str(chips),
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
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
        deadline = time.monotonic() + 60
        while True:
            check(self.proc.poll() is None, f"daemon exited at start: {self.tail_log()}")
            try:
                status, doc = self.call("GET", "/health", timeout=2)
                if status == 200:
                    self.health = doc.get("data", doc)
                    return
            except (urllib.error.URLError, OSError):
                pass
            check(time.monotonic() < deadline, "daemon did not answer /health in 60 s")
            time.sleep(0.2)

    def tail_log(self, n: int = 30) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def call(self, method: str, path: str, body: dict | None = None, timeout: float = 120):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}",
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
        check(status == 200 and doc.get("success", True), f"{method} {path} → {status}: {doc}")
        return doc.get("data", doc)

    def engine_logs(self, agent_id: str, tail: int = 60) -> list:
        status, doc = self.call("GET", f"/agents/{agent_id}/logs?tail={tail}", timeout=10)
        return (doc.get("data") or {}).get("logs", doc) if status == 200 else [str(doc)]

    def diagnosis(self) -> dict:
        """What a failed run leaves behind for its reader: the daemon's log
        tail and every agent's engine log tail (best effort — the failure
        being reported may be the daemon's own death)."""
        doc: dict = {"daemon_log": self.tail_log(15)}
        try:
            _, agents = self.call("GET", "/agents", timeout=10)
            for a in agents.get("data") or []:
                doc[f"engine_log:{a.get('name')}"] = self.engine_logs(a["id"])
        except (urllib.error.URLError, OSError):
            pass
        return doc

    def close(self) -> None:
        # SIGINT, not SIGTERM: the CLI's handler unwinds run_daemon, whose
        # cleanup stops every engine host it spawned
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)
        for pid in self.engine_pids:  # nothing this script started may outlive it
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        self._log.close()


class Agent:
    """One deployed agent, driven through the public surface."""

    def __init__(self, d: Daemon, name: str, config: str, options: dict, chips: int = 1, **deploy):
        self.d = d
        body = {
            "name": name,
            "model": {"engine": "llm", "config": config, "options": options},
            # the engine's real footprint, so the scheduler's HBM audit holds
            "resources": {"chips": chips, "hbm_bytes": chips * HBM_CLAIM_PER_CHIP},
            **deploy,
        }
        self.id = d.mgmt("POST", "/agents", body)["id"]
        self.name = name

    def start_and_load(self, budget_s: float) -> dict:
        """Start, then wait until every request for /metrics finds a loaded
        model (with replicas the router spreads the polls over them)."""
        t0 = time.monotonic()
        self.d.mgmt("POST", f"/agents/{self.id}/start", timeout=180)
        m = self.wait_loaded(budget_s)
        emit(
            "engine_ready",
            agent=self.name,
            setup_s=round(time.monotonic() - t0, 1),
            engine_load_s=m.get("engine_load_s"),
            warmup_skipped=m.get("warmup_skipped"),
            compile_cache=m.get("compile_cache"),
            device=m.get("device"),
            engine_devices=m.get("engine_devices"),
            attention=m.get("attention"),
            chips=m.get("chips"),
            visible_chips=m.get("visible_chips"),
            tp=m.get("tp"),
            param_hbm_bytes=m.get("param_hbm_bytes"),
            kv_arena_bytes=m.get("kv_arena_bytes"),
        )
        return m

    def metrics(self) -> dict:
        status, m = self.d.call("GET", f"/agent/{self.id}/metrics", timeout=30)
        check(status == 200, f"/agent/{self.id}/metrics → {status}: {m}")
        if m.get("pid"):
            self.d.engine_pids.add(int(m["pid"]))
        return m

    def wait_loaded(self, budget_s: float) -> dict:
        deadline = time.monotonic() + budget_s
        while True:
            m = self.metrics()
            if m.get("engine_error"):
                raise SmokeFailure(
                    f"{self.name}: engine failed to load: {m['engine_error']}\n"
                    + "\n".join(map(str, self.d.engine_logs(self.id)))
                )
            if m.get("model_loaded"):
                return m
            check(time.monotonic() < deadline, f"{self.name}: model not loaded in {budget_s:.0f} s")
            time.sleep(1.0)

    def chat(self, session: str, message: str, max_tokens: int = 16) -> tuple[int, dict, float]:
        t0 = time.monotonic()
        status, doc = self.d.call(
            "POST",
            f"/agent/{self.id}/chat",
            {"message": message, "session": session, "max_tokens": max_tokens, "ignore_eos": True},
            timeout=300,
        )
        return status, doc, time.monotonic() - t0

    def chat_ok(self, session: str, message: str, max_tokens: int = 16) -> dict:
        status, doc, wall = self.chat(session, message, max_tokens)
        usage = doc.get("usage") or {}
        check(
            status == 200
            and isinstance(doc.get("response"), str)
            and usage.get("completion_tokens") == max_tokens,
            f"{self.name} chat({session}) → {status}: {doc}",
        )
        return {
            "session": session,
            "prompt_tokens": usage.get("prompt_tokens"),
            "completion_tokens": usage.get("completion_tokens"),
            "ttft_ms_smoke": doc.get("ttft_ms"),
            "wall_ms_smoke": round(1000 * wall, 1),
        }

    def two_turns(self, sessions: tuple[str, str]) -> list[dict]:
        """A handful of chat turns on two sessions, the second turn of each
        continuing the first (its KV stays resident between turns)."""
        return [
            {"turn": turn + 1, **self.chat_ok(sess, text)}
            for turn, text in enumerate((PROMPT, "Continue from there, briefly."))
            for sess in sessions
        ]

    def healthy_metrics(self) -> dict:
        m = self.metrics()
        check(
            m.get("worker_errors") == 0 and m.get("unhandled_errors") == 0,
            f"{self.name} engine errors: worker={m.get('worker_errors')} "
            f"{m.get('last_worker_error')} unhandled={m.get('unhandled_errors')} "
            f"{m.get('last_unhandled_error')}",
        )
        return m

    def greedy_tokens(self, n: int = 32) -> list[int]:
        """Greedy continuation of the fixed prompt, as token ids — what the
        phases compare with each other."""
        status, doc = self.d.call(
            "POST",
            f"/agent/{self.id}/generate",
            {"prompt": PROMPT, "max_tokens": n, "temperature": 0.0},
            timeout=300,
        )
        toks = doc.get("tokens")
        check(
            status == 200 and isinstance(toks, list) and len(toks) >= 1,
            f"{self.name} generate → {status}: {doc}",
        )
        return [int(x) for x in toks]

    def journal(self, status: str) -> dict:
        return self.d.mgmt("GET", f"/agents/{self.id}/requests?status={status}", timeout=30)

    def retire(self) -> None:
        """Stop and remove, and wait until the engine host is gone: the
        next engine needs its chips."""
        self.d.mgmt("POST", f"/agents/{self.id}/stop", timeout=60)
        self.d.mgmt("DELETE", f"/agents/{self.id}", timeout=60)
        deadline = time.monotonic() + 30
        while any(_alive(p) for p in self.d.engine_pids) and time.monotonic() < deadline:
            time.sleep(0.2)
        check(not any(_alive(p) for p in self.d.engine_pids), "engine host still alive after remove")
        self.d.engine_pids.clear()


def _alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(") ", 1)[-1].split()[0] != "Z"
    except OSError:
        return False


def share_equal(a: list[int], b: list[int]) -> float:
    n = min(len(a), len(b))
    return round(sum(x == y for x, y in zip(a, b)) / n, 3) if n else 0.0


def require_device(m: dict, rehearse: bool, count: int, utilization: bool = True) -> dict:
    """The device the engine computes on, held to the chip contract.
    ``utilization=False`` for the per-replica extract of the management
    view, which does not carry the MFU/MBU keys."""
    from agentainer_tpu.utils.hw import chip_spec  # a peaks table; no JAX

    dev = m.get("device") or {}
    check(
        {"platform", "kind", "count"} <= set(dev), f"engine /metrics names no device: {dev}"
    )
    if rehearse:  # every process sees the same virtual devices: no binding to check
        check(dev["platform"] == "cpu", f"a rehearsal runs on the cpu, got {dev}")
        check("mfu_lifetime" not in m, "an engine on an unlisted device must report no MFU")
        return dev
    check(dev["platform"] == "tpu", f"engine platform is {dev['platform']!r}, not 'tpu'")
    # the process was bound to its placement: it sees those chips only
    check(dev["count"] == count, f"engine sees {dev['count']} device(s), expected {count}")
    check(
        chip_spec(dev["kind"]) is not None,
        f"device kind {dev['kind']!r} is not in the peaks table (utils/hw.py)",
    )
    check(
        "mfu_lifetime" in m or not utilization,
        "an engine on a listed device reports no MFU",
    )
    return dev


def require_attention(m: dict, prefill: str, decode: str) -> None:
    att = m.get("attention") or {}
    check(
        att.get("prefill") == prefill and att.get("decode") == decode,
        f"compiled steps trace {att}, expected prefill={prefill} decode={decode}",
    )


# -- one chip ---------------------------------------------------------------


def phase_default_engine(d: Daemon, args, model: str, options: dict) -> tuple[dict, list[int]]:
    """Phase 1: the default engine through daemon → proxy → journal →
    engine, then SIGKILL → 202 → resume → replay on the same chip."""
    agent = Agent(d, "smoke-default", model, options)
    m = agent.start_and_load(budget_s=LOAD_BUDGET_S)
    device = require_device(m, args.rehearse, count=1)
    if args.rehearse:  # tiny heads (hd 16) cannot take the kernels anywhere
        require_attention(m, "xla:attention_reference", "xla:attention_reference")
    else:
        require_attention(m, "pallas:flash_prefill", "pallas:flash_decode")
    first_start_cache = m.get("compile_cache") or {}

    turns = agent.two_turns(("s1", "s2"))
    tokens = agent.greedy_tokens()
    emit("chat", agent=agent.name, turns=turns, greedy_tokens=tokens)

    m = agent.healthy_metrics()
    stats = agent.journal("completed")["stats"]
    check(
        stats.get("completed", 0) >= len(turns) and not stats.get("failed") and not stats.get("pending"),
        f"journal after {len(turns)} turns: {stats}",
    )
    emit(
        "metrics",
        agent=agent.name,
        journal=stats,
        tokens_generated=m.get("tokens_generated"),
        prefills=m.get("prefills"),
        decode_steps=m.get("decode_steps"),
        kv_snapshots=m.get("kv_snapshots"),
        prefix_hits=m.get("prefix_hits"),
        ttft_ms_p50_smoke=m.get("ttft_ms_p50"),
        itl_ms_p50_smoke=m.get("itl_ms_p50"),
    )

    # the signature flow: kill the process that holds the chip
    pid = int(m["pid"])
    t_kill = time.monotonic()
    os.kill(pid, signal.SIGKILL)
    rid = None
    for attempt in range(100):
        # 200 means the kill raced a still-answering engine; 202 (agent
        # marked down) and 502 (dispatch hit the dead engine) both leave the
        # entry journaled for replay
        status, doc, _ = agent.chat("s1", f"Did you survive crash {attempt}?")
        if status == 202:
            rid = doc["data"]["request_id"]
            break
        check(status in (200, 502), f"chat during crash → {status}: {doc}")
        time.sleep(0.1)
    check(rid is not None, "no request was queued (202) after the SIGKILL")
    t_queued = time.monotonic()
    d.mgmt("POST", f"/agents/{agent.id}/resume", timeout=180)
    deadline = time.monotonic() + 600
    entry = None
    while entry is None:
        check(time.monotonic() < deadline, "the journaled request was not replayed in 600 s")
        reqs = agent.journal("completed")["requests"]
        entry = next((r for r in reqs if r["id"] == rid), None)
        if entry is None:
            failed = [r for r in agent.journal("failed")["requests"] if r["id"] == rid]
            check(not failed, f"the journaled request failed: {failed}")
            time.sleep(0.5)
    t_replayed = time.monotonic()
    resp = entry.get("response") or {}
    replayed = json.loads(base64.b64decode(resp.get("body_b64") or "") or b"{}")
    check(
        resp.get("status_code") == 200 and isinstance(replayed.get("response"), str),
        f"the replayed turn is not an answered 200: {resp}",
    )
    m2 = agent.wait_loaded(budget_s=60)
    check(int(m2["pid"]) != pid, "the engine pid did not change across the kill")
    next_turn = agent.chat_ok("s1", "And after the restart?")
    m2 = agent.metrics()
    second = m2.get("compile_cache") or {}
    emit(
        "kill_replay",
        agent=agent.name,
        killed_pid=pid,
        new_pid=m2["pid"],
        kill_to_queued_s_smoke=round(t_queued - t_kill, 2),
        kill_to_replayed_s_smoke=round(t_replayed - t_kill, 2),
        replayed_request=rid,
        next_turn=next_turn,
        kv_restores=m2.get("kv_restores"),
        second_start={
            "engine_load_s": m2.get("engine_load_s"),
            "warmup_skipped": m2.get("warmup_skipped"),
            "compile_cache": second,
            # hits: programs an earlier process had stored (the first
            # start's warm-up); writes: programs compiled afresh — zero
            # when the warm-up covered everything the replay needed, else
            # programs the warm-up never runs (the KV restore of the killed
            # session compiles one scatter per restored length)
            "compiled_nothing_new": second.get("writes") == 0,
        },
        first_start_compile_cache=first_start_cache,
    )
    check(second.get("hits", 0) > 0, f"the second start never hit the compile cache: {second}")
    agent.retire()
    return device, tokens


def phase_paged_fused_engine(d: Daemon, args, model: str, options: dict, ref_tokens: list[int]) -> None:
    """Phase 3: the same model with paged_kv and fused_decode on — it must
    load, warm up and answer, with the fused paged kernels in its steps."""
    agent = Agent(
        d, "smoke-paged-fused", model, {**options, "paged_kv": True, "fused_decode": True}
    )
    m = agent.start_and_load(budget_s=LOAD_BUDGET_S)
    require_device(m, args.rehearse, count=1)
    check(m.get("paged_kv") is True and m.get("fused_decode") is True, "paged_kv/fused_decode not on")
    if args.rehearse:
        name = "xla:gather_pages+attention_reference"
        require_attention(m, name, name)
    else:
        require_attention(
            m, "pallas:fused_paged_flash_prefill", "pallas:fused_paged_flash_decode"
        )
    turns = agent.two_turns(("p1", "p2"))
    tokens = agent.greedy_tokens()
    m = agent.healthy_metrics()
    check(m.get("fused_loops_total", 0) > 0, "the fused decode loop never ran")
    emit(
        "chat",
        agent=agent.name,
        turns=turns,
        greedy_tokens=tokens,
        # random int8 weights give near-tied logits, so this is printed,
        # not judged; numerics are judged at the kernels
        greedy_share_equal_to_default=share_equal(tokens, ref_tokens),
        fused_loops_total=m.get("fused_loops_total"),
        kv_pages_used=m.get("kv_pages_used"),
    )
    agent.retire()


# -- four chips (--chips 4) ---------------------------------------------------


def phase_tp4(d: Daemon, args, model: str, options: dict) -> dict:
    """(a) one chip, (b) the same model sharded over chips 0–3."""
    one = Agent(d, "smoke-one-chip", model, options)
    m = one.start_and_load(budget_s=LOAD_BUDGET_S)
    require_device(m, args.rehearse, count=1)
    ref_tokens = one.greedy_tokens()
    emit("chat", agent=one.name, greedy_tokens=ref_tokens)
    one.retire()

    four = Agent(d, "smoke-tp4", model, options, chips=4)
    m = four.start_and_load(budget_s=LOAD_BUDGET_S)
    device = require_device(m, args.rehearse, count=4)
    devs = m.get("engine_devices") or []
    if args.rehearse:  # tiny has 2 KV heads: the engine narrows tp to 2
        check(m.get("tp") == 2 and len(devs) == 2, f"rehearsal tp: {m.get('tp')} over {devs}")
        require_attention(
            m, "pallas-interpret:shard_map(flash_prefill)", "pallas-interpret:shard_map(flash_decode)"
        )
    else:
        check(m.get("tp") == 4 and len(devs) == 4, f"tp={m.get('tp')} over {len(devs)} devices")
        require_attention(m, "pallas:shard_map(flash_prefill)", "pallas:shard_map(flash_decode)")
        used = [x.get("bytes_in_use") for x in devs]
        check(all(isinstance(u, int) and u > 0 for u in used), f"no per-device memory stats: {devs}")
        # sharded, not replicated and not parked on the first chip: every
        # chip holds about a quarter of weights + arena
        share = (m["param_hbm_bytes"] + m["kv_arena_bytes"]) / 4
        check(
            max(used) < 2 * share and max(used) < 1.5 * min(used),
            f"weights not spread evenly: bytes_in_use {used}, expected about {share:.3g} each",
        )
    turn = four.chat_ok("t1", PROMPT)
    tokens = four.greedy_tokens()
    emit(
        "chat",
        agent=four.name,
        turn=turn,
        greedy_tokens=tokens,
        greedy_share_equal_to_one_chip=share_equal(tokens, ref_tokens),
        per_device_bytes_in_use=[x.get("bytes_in_use") for x in devs],
    )
    four.retire()
    return device


def phase_replicas(d: Daemon, args, model: str, options: dict) -> None:
    """(c) four one-chip replicas behind the router: all ready at once,
    each on its own device, traffic reaching all; one killed, three serve."""
    fleet = Agent(d, "smoke-replicas", model, options, replicas=4)
    t0 = time.monotonic()
    d.mgmt("POST", f"/agents/{fleet.id}/start", timeout=300)

    def replicas(want: int, budget_s: float) -> dict[int, dict]:
        """Every replica's own live /metrics answer, by ordinal — read from
        the management view (the proxy routes by affinity) until ``want``
        of them report a loaded model."""
        deadline = time.monotonic() + budget_s
        while True:
            view = d.mgmt("GET", f"/agents/{fleet.id}/metrics", timeout=30)
            docs = [r.get("engine") for r in view["fleet"]["replicas"].values()]
            docs = [m for m in docs if m]
            for m in docs:
                check(not m.get("engine_error"), f"replica {m.get('replica')}: {m.get('engine_error')}")
                d.engine_pids.add(int(m["pid"]))
            up = {int(m["replica"]): m for m in docs if m.get("model_loaded")}
            if len(up) >= want:
                return up
            check(time.monotonic() < deadline, f"only replicas {sorted(up)} ready in {budget_s:.0f} s")
            time.sleep(1.0)

    up = replicas(4, budget_s=120 if args.rehearse else 900)
    pids = {r: int(m["pid"]) for r, m in up.items()}
    bound = {r: (tuple(m.get("chips") or ()), m.get("visible_chips")) for r, m in up.items()}
    check(len(set(pids.values())) == 4, f"replicas share a process: {pids}")
    check(len(set(bound.values())) == 4, f"replicas share a chip binding: {bound}")
    for m in up.values():
        require_device(m, args.rehearse, count=1, utilization=False)
    emit(
        "replicas_ready",
        setup_s=round(time.monotonic() - t0, 1),
        replicas={str(r): m for r, m in sorted(up.items())},
    )
    # fresh sessions spread by power-of-two-choices: until all four served
    served: set[int] = set()
    for i in range(64):
        fleet.chat_ok(f"f{i}", PROMPT, max_tokens=8)
        if i >= 7 and i % 4 == 3:
            served = {r for r, m in replicas(4, 60).items() if m.get("tokens_generated", 0) > 0}
            if len(served) == 4:
                break
    check(len(served) == 4, f"requests reached only replicas {sorted(served)}")
    victim = 3
    os.kill(pids[victim], signal.SIGKILL)
    t_kill = time.monotonic()
    after = [fleet.chat_ok(f"k{i}", PROMPT, max_tokens=8) for i in range(12)]
    alive = {r: m for r, m in replicas(3, 60).items() if r != victim}
    check(
        len(alive) == 3 and all(pids[r] == int(m["pid"]) for r, m in alive.items()),
        f"survivors changed: {pids} → { {r: m['pid'] for r, m in alive.items()} }",
    )
    emit(
        "replica_kill",
        killed_replica=victim,
        killed_pid=pids[victim],
        answered_after_kill=len(after),
        first_answer_after_kill_s_smoke=round(after[0]["wall_ms_smoke"] / 1000, 2),
        survivors={str(r): m["pid"] for r, m in sorted(alive.items())},
        seconds=round(time.monotonic() - t_kill, 1),
    )
    fleet.retire()


def _on_deadline(signum, frame) -> None:
    raise SmokeFailure(f"not done after {DEADLINE_S:.0f} s")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--rehearse", action="store_true", help="tiny model on the CPU, kernels interpreted")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1, help="4: only the four-chip paths")
    ap.add_argument("--seed", type=int, default=0, help="seed of the kernel-check data")
    ap.add_argument("--kernel-check", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.kernel_check:
        return kernel_check(args.rehearse, args.seed)

    env = dict(os.environ)
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        # the meshed engine's shard_map kernels, interpreted (tp rehearsal)
        env["ATPU_FORCE_MESH_FLASH"] = "1"
        model, options = "tiny", {"max_batch": 4, "max_seq": 256}
    else:
        model = "llama3-8b"  # published widths, all 32 layers
        options = {"quant": "int8", "synthetic": True, "max_batch": 8, "max_seq": 2048}

    t0 = time.monotonic()
    signal.signal(signal.SIGALRM, _on_deadline)
    signal.alarm(int(DEADLINE_S))
    daemon = None
    try:
        build_native()
        if args.chips == 1:
            # the chip answers, and the kernels are right on it, before any
            # engine is asked to use them
            run_kernel_child(args.rehearse, args.seed, env)
        daemon = Daemon(env, chips=args.chips)
        emit("daemon", port=daemon.port, **{k: daemon.health.get(k) for k in ("slice", "slice_chips", "data_plane")})
        check(
            daemon.health.get("data_plane") == "native",
            f"the native data plane is not serving: {daemon.health} {daemon.tail_log(10)}",
        )
        if args.chips == 1:
            device, tokens = phase_default_engine(daemon, args, model, options)
            phase_paged_fused_engine(daemon, args, model, options, tokens)
        else:
            device = phase_tp4(daemon, args, model, options)
            phase_replicas(daemon, args, model, options)
    except SmokeFailure as e:
        emit("failed", error=str(e), **(daemon.diagnosis() if daemon else {}))
        return 1
    finally:
        signal.alarm(0)
        if daemon is not None:
            daemon.close()
    emit("done", seconds=round(time.monotonic() - t0, 1), rehearsal=args.rehearse)
    # the device is the engine's own report; a rehearsal says "cpu" here
    # and can therefore never pass for a chip run
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
