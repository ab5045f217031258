"""Admission-aware decode chunking (ISSUE 1 tentpole).

The worker loop is admission-aware: decode chunks shrink to the smallest
compiled bucket while a prompt is mid-prefill, forced readback waits keep
polling the submit queue (a newcomer's first prefill chunk dispatches
immediately), and TTFT decomposes into queue-wait / prefill /
first-readback phases. Steady state must be untouched: full-size chunks,
no contention shrinks.
"""

import asyncio
import time

import pytest

from agentainer_tpu.engine.llm import EngineShutdown, GenRequest, LLMEngine


def _mk(**opts) -> LLMEngine:
    base = {
        "max_batch": 4,
        "max_seq": 256,
        "decode_chunk": 8,
        "prefill_chunk": 32,
    }
    base.update(opts)
    return LLMEngine.create("tiny", options=base)


def test_steady_state_dispatches_full_chunks():
    """No pending prompts, nobody waiting → every mid-generation dispatch
    is the full configured chunk (ITL/HBM efficiency untouched), and the
    contention-shrink counter stays at zero."""
    eng = _mk()
    try:
        r = asyncio.run(eng.generate("steady", max_tokens=40, temperature=0.0))
        assert r["completion_tokens"] == 40
        hist = {int(k): v for k, v in eng.metrics()["decode_chunk_hist"].items()}
        assert eng.decode_chunks_shrunk == 0
        assert max(hist) == eng.decode_chunk
        # the dominant dispatch size is the full chunk (the tail may trim)
        assert hist[eng.decode_chunk] >= sum(hist.values()) - 1, hist
    finally:
        eng.shutdown()


def test_mid_decode_arrival_admits_below_chunk_wall():
    """A prompt submitted while another request decodes is admitted below
    one full-chunk wall: while the newcomer prefills, the decoding lane
    advances ONE step a tick and the worker is back at the newcomer's next
    chunk after it. Either mechanism serves such a tick: the chunk's launch
    carries the lane's step (``mixed_launches``: the one-launch tick of the
    dense one-chip engine, which takes ``_pick_chunk``'s place whenever a
    chunk is pending beside a decoding lane), or the decode chunk shrinks to
    the one-step rung (``decode_chunks_shrunk``: a tick the riders do not
    take, e.g. a waiter a free slot could admit). Which of the two a tick
    takes depends on where the arrival falls in the worker's loop, so the
    counters are asserted together and no clock is read: the case used to
    compare the probes' queue wait with ``decode_chunk`` x ITL p50 and to
    demand a shrink, and failed under six workers whenever every contended
    tick was a rider (``decode_chunks_shrunk`` 0, ROADMAP Design 12)."""
    eng = _mk(max_seq=512)
    try:

        async def scenario():
            bg = asyncio.ensure_future(
                eng.generate("background generation", max_tokens=400, temperature=0.0)
            )
            await asyncio.sleep(0.05)  # decode under way
            probes = []
            for k in range(5):
                # multi-chunk prompts that share no prefix (a prefix-arena
                # hit would leave one chunk to prefill): the newcomer prefills
                # over several ticks with the background lane decoding beside it
                r = await eng.generate(f"probe {k}: " + "p " * 60, max_tokens=2, temperature=0.0)
                probes.append((r, eng.metrics()))
                await asyncio.sleep(0.01)
            return probes, await bg

        probes, bg = asyncio.run(scenario())
        m = eng.metrics()
        assert bg["completion_tokens"] == 400 and all(r["completion_tokens"] == 2 for r, _ in probes)
        assert m["prefix_hits"] == 0
        chunks = -(-probes[0][0]["prompt_tokens"] // eng.prefill_chunk)
        assert chunks >= 4  # several ticks a probe
        # the background request was still decoding when each probe came back
        # (its budget outlasts them), so every chunk of a probe met it but the
        # first, where that went out alone from inside a readback wait
        # (``_wait_admitting``), and the last (nobody waits on the worker
        # after it): a rider or a shrunk chunk each, never a full chunk's wall
        before = 0
        for k, (_, at) in enumerate(probes):
            now = at["mixed_launches"] + at["decode_chunks_shrunk"]
            assert at["requests_finished"] == k + 1  # the probes alone: the background request goes on
            assert now - before >= chunks - 2, (k, now, before, chunks)
            before = now
        # a rider is the one lane's one step; a shrunk chunk is the one-step rung
        hist = {int(k): v for k, v in m["decode_chunk_hist"].items()}
        assert m["mixed_decode_lanes"] == m["mixed_launches"]
        assert m["decode_chunks_shrunk"] <= hist.get(eng._decode_ladder[0], 0)
        # and the steady stretches between the probes still ran full chunks
        assert hist.get(eng.decode_chunk, 0) > 0, hist
    finally:
        eng.shutdown()


def test_ttft_phase_decomposition():
    """Phases are reported per request and in /metrics, and they sum to
    TTFT (up to rounding)."""
    eng = _mk()
    try:
        r = asyncio.run(eng.generate("decompose me", max_tokens=8, temperature=0.0))
        bd = r["ttft_breakdown"]
        assert bd is not None
        total = bd["queue_ms"] + bd["prefill_ms"] + bd["first_readback_ms"]
        assert abs(total - r["ttft_ms"]) < 0.1, (bd, r["ttft_ms"])
        m = eng.metrics()
        assert m["admission_ms_p50"] is not None
        assert m["ttft_prefill_ms_p50"] is not None
        assert m["ttft_first_readback_ms_p50"] is not None
        assert len(m["ttft_prefill_samples"]) == len(m["ttft_first_readback_samples"])
    finally:
        eng.shutdown()


def test_fixed_mode_keeps_legacy_cadence():
    """adaptive_decode=False is the A/B baseline: full chunks always, no
    shrinks, no multi-tick prefill — a faithful reproduction of the round-5
    scheduler."""
    eng = _mk(adaptive_decode=False)
    try:
        async def scenario():
            bg = asyncio.ensure_future(
                eng.generate("background generation", max_tokens=60, temperature=0.0)
            )
            await asyncio.sleep(0.02)
            await eng.generate("p " * 60, max_tokens=2, temperature=0.0)
            await bg

        asyncio.run(scenario())
        assert eng.adaptive_decode is False
        assert eng.decode_chunks_shrunk == 0
        hist = {int(k): v for k, v in eng.metrics()["decode_chunk_hist"].items()}
        assert set(hist) == {eng.decode_chunk}, hist
    finally:
        eng.shutdown()


def test_no_overshoot_chunks_after_budget_dispatched():
    """Once every live lane's token budget is in flight the worker stops
    dispatching (garbage chunks while waiting for readbacks): total decode
    steps dispatched stay close to the budget."""
    eng = _mk()
    try:
        asyncio.run(eng.generate("exact budget", max_tokens=17, temperature=0.0))
        hist = {int(k): v for k, v in eng.metrics()["decode_chunk_hist"].items()}
        dispatched = sum(k * v for k, v in hist.items())
        # 16 post-first tokens need 2×8; the bucket trim caps the tail —
        # anything much larger means garbage chunks were dispatched
        assert dispatched <= 24, hist
    finally:
        eng.shutdown()


def test_shutdown_fails_queued_items_instead_of_hanging():
    """ADVICE r5: the worker's sentinel used to abandon queued futures
    forever. Both the worker's exit drain and shutdown()'s post-join drain
    must fail leftovers with EngineShutdown."""
    eng = _mk(max_batch=2, max_seq=64)
    try:

        async def scenario():
            loop = asyncio.get_running_loop()
            fut = loop.create_future()
            req = GenRequest(
                id="late",
                session="",
                prompt_ids=[1, 2, 3],
                max_tokens=4,
                temperature=0.0,
                loop=loop,
                future=fut,
            )
            # sentinel first: the worker exits; the request enqueued behind
            # it must be failed by the exit drain (or by shutdown()'s
            # post-join drain if the worker died before seeing it)
            eng._queue.put(None)
            eng._queue.put(req)
            await asyncio.to_thread(eng.shutdown)
            with pytest.raises(EngineShutdown):
                await asyncio.wait_for(fut, timeout=5)

        asyncio.run(scenario())
    finally:
        eng.shutdown()  # idempotent


def test_warmup_covers_adaptive_chunk_ladder():
    """Every ladder bucket ({1,2,4,8} for decode_chunk=8) is compiled at
    warmup; contended serving must never hit a serve-time decode compile."""
    eng = _mk()
    try:
        before = eng._decode_n._cache_size()
        assert before >= len(eng._decode_ladder), (before, eng._decode_ladder)

        async def scenario():
            bg = asyncio.ensure_future(
                eng.generate("background", max_tokens=100, temperature=0.0)
            )
            await asyncio.sleep(0.03)
            await eng.generate("p " * 60, max_tokens=3, temperature=0.0)
            await bg

        asyncio.run(scenario())
        assert eng._decode_n._cache_size() == before
    finally:
        eng.shutdown()
