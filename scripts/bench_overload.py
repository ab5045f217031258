"""Overload benchmark: deadlines + shedding on vs off at 2-4x saturation.

A/B for the end-to-end deadline plane (ISSUE 3). The SAME engine config is
driven with open-loop Poisson-ish arrivals at a multiple of its measured
capacity, twice:

  off: ``deadlines=false``, no watermark — the historical behavior: every
       arrival queues, the backlog grows for the whole window, and most
       completions land long past the caller's patience;
  on:  ``deadlines=true`` + a submit-side shed watermark — excess arrivals
       get a fast EngineOverloaded (the proxy's 429) or expire in queue
       before prefill; admitted work completes inside its deadline.

Scored on GOODPUT — completions whose end-to-end latency fit the deadline,
per second of wall time until the system fully drains — plus p99 TTFT of
completed requests. Late completions are real work wasted on answers
nobody was waiting for; the off-mode pays for them in both metrics. A
steady-state single-lane pass guards that ``deadlines=false`` ITL is
unchanged (the deadline plane must cost nothing when disabled) and that
the enabled-but-unloaded engine matches it.

Runs on any JAX platform: the artifact under test is submit-path and
worker-loop policy, so a CPU run shows the policy's decisions and counts
(its times are CPU times; not measured on the chip yet).

Usage: JAX_PLATFORMS=cpu python scripts/bench_overload.py
       ATPU_OVERLOAD_SMOKE=1 shortens every window (make overload).
Emits one JSON line on stdout; the committed artifact is
BENCH_overload.json.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _benchlib import make_engine, percentile as _p, steady_itl_interleaved

SMOKE = os.environ.get("ATPU_OVERLOAD_SMOKE", "") not in ("", "0", "false")
MODEL = os.environ.get("ATPU_OVL_MODEL", "tiny")
MAX_BATCH = int(os.environ.get("ATPU_OVL_MAX_BATCH", "4"))
MAX_TOKENS = int(os.environ.get("ATPU_OVL_MAX_TOKENS", "24"))
CAL_S = 2.0 if SMOKE else 4.0
WINDOW_S = 4.0 if SMOKE else 10.0
MULTS = [2.0] if SMOKE else [2.0, 4.0]
DRAIN_CAP_S = 60.0 if SMOKE else 180.0
PROMPT = "overload probe: how long is the queue today? "


def _mk_engine(deadlines: bool):
    return make_engine(
        MODEL,
        max_batch=MAX_BATCH,
        max_seq=512,
        decode_chunk=8,
        prefill_chunk=32,
        deadlines=deadlines,
        # admit up to ~2 batches of backlog, then shed — the engine-level
        # twin of the proxy's pending watermark
        shed_watermark=3 * MAX_BATCH if deadlines else 0,
    )


async def _steady_itl(engines: dict) -> dict[str, float]:
    return await steady_itl_interleaved(engines, passes=5, max_tokens=200)


async def _calibrate(eng) -> tuple[float, float]:
    """Closed-loop at capacity (max_batch clients): completions/s and mean
    latency — the denominators the overload multiples are defined against."""
    done = 0
    lat_sum = 0.0
    stop_at = time.monotonic() + CAL_S

    async def client(i: int) -> None:
        nonlocal done, lat_sum
        while time.monotonic() < stop_at:
            t0 = time.monotonic()
            await eng.generate(f"{PROMPT}cal{i}", max_tokens=MAX_TOKENS, temperature=0.0)
            lat_sum += time.monotonic() - t0
            done += 1

    t0 = time.monotonic()
    await asyncio.gather(*(client(i) for i in range(MAX_BATCH)))
    elapsed = time.monotonic() - t0
    return done / elapsed, (lat_sum / max(1, done)) * 1000


async def _overload_pass(eng, deadlines: bool, rps: float, deadline_ms: float) -> dict:
    """Open-loop arrivals at ``rps`` for WINDOW_S, then drain. Every arrival
    is classified: ok (completed within deadline), late, shed (fast 429
    analogue), expired (dead-lettered pre/mid-flight), error."""
    from agentainer_tpu.engine.llm import (
        EngineOverloaded,
        RequestCancelled,
        RequestExpired,
    )

    counts = {"ok": 0, "late": 0, "shed": 0, "expired": 0, "error": 0}
    ttfts: list[float] = []
    tasks = []
    t_start = time.monotonic()

    async def one(i: int) -> None:
        t0 = time.monotonic()
        dl = time.time() + deadline_ms / 1000.0 if deadlines else None
        try:
            r = await eng.generate(
                f"{PROMPT}ovl{i}", max_tokens=MAX_TOKENS, temperature=0.0, deadline_at=dl
            )
        except EngineOverloaded:
            counts["shed"] += 1
            return
        except (RequestExpired, RequestCancelled):
            counts["expired"] += 1
            return
        except Exception:
            counts["error"] += 1
            return
        latency_ms = 1000 * (time.monotonic() - t0)
        if r.get("ttft_ms") is not None:
            ttfts.append(r["ttft_ms"])
        counts["ok" if latency_ms <= deadline_ms else "late"] += 1

    i = 0
    gap = 1.0 / rps
    next_at = time.monotonic()
    while time.monotonic() - t_start < WINDOW_S:
        tasks.append(asyncio.ensure_future(one(i)))
        i += 1
        next_at += gap
        delay = next_at - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
    try:
        await asyncio.wait_for(asyncio.gather(*tasks), DRAIN_CAP_S)
    except asyncio.TimeoutError:
        for t in tasks:
            t.cancel()
    elapsed = time.monotonic() - t_start
    ttfts.sort()
    m = eng.metrics()
    return {
        "offered": i,
        "offered_rps": round(rps, 2),
        "window_s": WINDOW_S,
        "wall_s": round(elapsed, 2),
        "deadline_ms": round(deadline_ms, 1),
        **counts,
        "goodput_rps": round(counts["ok"] / elapsed, 3),
        "ttft_ms_p50": _p(ttfts, 0.5),
        "ttft_ms_p99": _p(ttfts, 0.99),
        "engine_shed_total": m["shed_total"],
        "engine_expired_total": m["expired_total"],
        "worker_errors": m["worker_errors"],
    }


async def run() -> dict:
    t0 = time.monotonic()
    import jax

    out: dict = {
        "metric": "llm_overload_goodput_shed_on_over_off",
        "unit": "ratio",
        "platform": jax.default_backend(),
        "model": MODEL,
        "max_batch": MAX_BATCH,
        "smoke": SMOKE,
        "passes": {},
    }
    engines = {}
    try:
        engines["off"] = _mk_engine(deadlines=False)
        engines["on"] = _mk_engine(deadlines=True)
        itls = await _steady_itl(engines)
        for mode, deadlines in (("off", False), ("on", True)):
            eng = engines[mode]
            cap_rps, mean_lat_ms = await _calibrate(eng)
            # the caller's patience: a few service times — generous at
            # capacity, hopeless once the backlog passes a few batches
            deadline_ms = max(250.0, 4 * mean_lat_ms)
            out["passes"][mode] = {
                "deadlines": deadlines,
                "itl_ms_steady": itls[mode],
                "capacity_rps": round(cap_rps, 3),
                "mean_latency_ms_at_capacity": round(mean_lat_ms, 1),
                "overload": {},
            }
            for mult in MULTS:
                out["passes"][mode]["overload"][f"{mult:g}x"] = await _overload_pass(
                    eng, deadlines, mult * cap_rps, deadline_ms
                )
    finally:
        for eng in engines.values():
            eng.shutdown()
    on2 = out["passes"]["on"]["overload"]["2x"]
    off2 = out["passes"]["off"]["overload"]["2x"]
    out["value"] = (
        round(on2["goodput_rps"] / off2["goodput_rps"], 3)
        if off2["goodput_rps"]
        else None
    )
    itl_on, itl_off = (
        out["passes"]["on"]["itl_ms_steady"],
        out["passes"]["off"]["itl_ms_steady"],
    )
    out["itl_steady_regression"] = (
        round(itl_on / itl_off - 1.0, 4) if itl_off else None
    )
    out["wall_s"] = round(time.monotonic() - t0, 1)
    return out


def main() -> None:
    out = asyncio.run(run())
    print(json.dumps(out), flush=True)
    # acceptance (ISSUE 3): shedding-on goodput >= shedding-off at >=2x
    # saturation; steady ITL within noise when the plane is off/idle
    on2 = out["passes"]["on"]["overload"]["2x"]
    off2 = out["passes"]["off"]["overload"]["2x"]
    ok = on2["goodput_rps"] >= off2["goodput_rps"] and (
        out["itl_steady_regression"] is None or out["itl_steady_regression"] < 0.10
    )
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
