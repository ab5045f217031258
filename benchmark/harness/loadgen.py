"""Offers the generated sessions to ``/agent/{id}/chat`` and records what
came back. One process, one thread: an asyncio loop with one HTTP connection
pool, so the generator's own use of the machine's cores is small and steady.

The load is a closed population of ``clients`` callers: each sends its next
turn a think time after its last reply, and opens its next session when the
last one ended. Time is counted on a clock whose zero is the start of the
measured window. The callers start ``warmup_s`` before it on another seed's
sessions and go on with the measured seed's from the window's start, so the
window opens on a system already in its steady state. A request belongs to
the window iff it was *due* in ``[0, seconds)``: its latency runs from that
instant (the caller's last reply plus the think time) to the last byte of
the reply. Requests due in the window are waited for after it closes
(``drain_s``); no turn is started after it. One that is still unanswered
when that time runs out is recorded as failed, with an infinite latency.
"""

from __future__ import annotations

import asyncio
import json
import math
import time

import aiohttp

REQUEST_TIMEOUT_S = 180.0


class Recorder:
    def __init__(self, seconds: float):
        self.seconds = seconds
        self.records: list[dict] = []
        self.origin_monotonic = 0.0  # the window's start on time.monotonic()
        self.cut_by_drain = 0  # tasks still waiting when the drain time ran out

    def window(self) -> list[dict]:
        return [r for r in self.records if 0.0 <= r["due_s"] < self.seconds]


async def _turn(http, url: str, sess: dict, k: int, due: float, origin: float, rec: Recorder) -> float:
    """Send turn ``k`` of ``sess`` (due at ``due`` on the window clock) and
    return the time its reply was complete."""
    turn = sess["turns"][k]
    delay = origin + due - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    body = {
        "message": turn["message"],
        "session": sess["id"],
        "max_tokens": turn["max_tokens"],
        "ignore_eos": True,
    }
    sent = time.monotonic() - origin
    r = {
        "session": sess["id"],
        "turn": k,
        "due_s": due,
        "sent_s": sent,
        "want_prompt_tokens": turn["prompt_tokens"],
        "want_completion_tokens": turn["max_tokens"],
        "context_tokens": turn["context_tokens"],
        "status": 0,
        "ok": False,
    }
    try:
        async with http.post(url, data=json.dumps(body), headers={"Content-Type": "application/json"}) as resp:
            raw = await resp.read()
            r["status"] = resp.status
        doc = json.loads(raw)
        usage = doc.get("usage") or {}
        r["prompt_tokens"] = usage.get("prompt_tokens")
        r["completion_tokens"] = usage.get("completion_tokens")
        r["ttft_ms"] = doc.get("ttft_ms")
        r["ok"] = (
            r["status"] == 200
            and r["completion_tokens"] == turn["max_tokens"]
            and (k > 0 or r["prompt_tokens"] == turn["prompt_tokens"])
        )
        if not r["ok"]:
            r["error"] = raw[:300].decode("utf-8", "replace")
    except (aiohttp.ClientError, asyncio.TimeoutError, json.JSONDecodeError, OSError) as e:
        r["error"] = f"{type(e).__name__}: {e}"
    except asyncio.CancelledError:
        # still unanswered when the drain time ran out: it stays in the
        # window's count as a failure, and never answers in the percentiles
        r["error"] = "no reply before the drain time ran out"
        r["done_s"] = r["latency_ms"] = math.inf
        rec.records.append(r)
        raise
    done = time.monotonic() - origin
    r["done_s"] = done
    r["latency_ms"] = 1000.0 * (done - due)
    rec.records.append(r)
    return done


async def _client(http, url, stream, start, origin, rec: Recorder) -> None:
    """One caller of the population: it takes the next session when its
    last one ended, and sends each turn a think time after the last reply."""
    done = start
    while True:
        sess = next(stream)
        for k, turn in enumerate(sess["turns"]):
            due = done + turn["think_s"]
            if due >= rec.seconds:
                return
            done = await _turn(http, url, sess, k, due, origin, rec)


async def _watch(origin: float, hooks: list) -> None:
    """The timed hooks ``(at, fn)``: the counter snapshots and, in a traced
    run, the trace. They are blocking REST calls of the harness, so they run
    off the loop's thread."""
    loop = asyncio.get_running_loop()
    pending = []
    for at, fn in sorted(hooks, key=lambda x: x[0]):
        delay = origin + at - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        pending.append(loop.run_in_executor(None, fn))
    for p in pending:
        await p


async def _run(url: str, params: dict, warm_stream, main_stream, seconds: float, hooks: list) -> Recorder:
    rec = Recorder(seconds)
    warmup_s = float(params.get("warmup_s", 10))
    timeout = aiohttp.ClientTimeout(total=REQUEST_TIMEOUT_S)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as http:
        origin = time.monotonic() + warmup_s + 0.25
        rec.origin_monotonic = origin
        tasks = [asyncio.ensure_future(_watch(origin, hooks))]
        # the callers warm up on the other seed's sessions, then go on with
        # the measured seed's from the window's start
        stream = _switching(warm_stream, main_stream, origin)
        for _ in range(int(params["clients"])):
            tasks.append(asyncio.ensure_future(_client(http, url, stream, -warmup_s, origin, rec)))
        done, pending = await asyncio.wait(tasks, timeout=warmup_s + seconds + float(params.get("drain_s", 60)))
        for t in pending:
            t.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
        for t in done:
            t.result()  # a bug in the generator is an error, not a quiet run
        rec.cut_by_drain = len(pending)
    return rec


def _switching(warm, main, origin):
    while True:
        yield next(warm if time.monotonic() < origin else main)


def run(url: str, params: dict, warm_stream, main_stream, seconds: float, hooks: list | None = None) -> Recorder:
    return asyncio.run(_run(url, params, warm_stream, main_stream, seconds, hooks or []))
