"""Tiered KV hierarchy: device -> pinned host RAM -> store.

The contract under test: PARKING IS INVISIBLE to the token stream. A
session demoted off-device (page-granular host copy, optionally int8
with per-page scales) and promoted back for its next turn must continue
bit-identically to a session that never left the device — dense and
paged layouts, quantized and exact host tiers. Around that core:
promotion overlaps the admission queue-wait (the TTFT phase
decomposition proves the restore was in flight before prefill started),
pool pressure demotes idle sessions instead of throwing
PagePoolExhausted, eviction/reallocation of a parked session's freed
pages cannot corrupt its host copy, and the kv_demote/kv_promote
failpoints degrade exactly as docs/RESILIENCE.md promises.
"""

import asyncio

import pytest

from agentainer_tpu import faults
from agentainer_tpu.engine.llm import (
    EngineOverloaded,
    LLMEngine,
    TierPromoteFailed,
)

OPTS_DENSE = {"max_batch": 2, "max_seq": 128, "decode_chunk": 4}
OPTS_PAGED = {
    "max_batch": 2,
    "max_seq": 128,
    "decode_chunk": 4,
    "paged_kv": True,
    "page_size": 16,
    "kv_pages": 16,
}


def run(coro):
    return asyncio.run(coro)


def _opts(paged: bool, quantized: bool) -> dict:
    base = dict(OPTS_PAGED if paged else OPTS_DENSE)
    base["kv_tiering"] = True
    base["tier_quantize"] = 1 if quantized else 0
    return base


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("quantized", [False, True], ids=["exact", "int8"])
def test_park_promote_roundtrip_is_token_identical(paged, quantized):
    """Control runs turn1+turn2 resident; the experiment parks between
    the turns (device pages freed, host tier holds the session) and the
    next chat auto-promotes at admission. Greedy streams must match."""

    async def control():
        eng = LLMEngine.create("tiny", options=_opts(paged, quantized))
        try:
            a = await eng.chat("s", "turn one", max_tokens=5)
            b = await eng.chat("s", "turn two", max_tokens=5)
            return a, b
        finally:
            eng.shutdown()

    async def parked():
        eng = LLMEngine.create("tiny", options=_opts(paged, quantized))
        try:
            a = await eng.chat("s", "turn one", max_tokens=5)
            blob = await eng.park_session("s")
            assert blob is not None  # exact cold-tier bytes, pre-quant
            assert "s" not in eng.sessions  # off the device...
            assert eng.has_session("s")  # ...but still this engine's
            if quantized:
                assert eng.tier_quantized_pages > 0
            b = await eng.chat("s", "turn two", max_tokens=5)
            assert eng.tier_demotions_total >= 1
            assert eng.tier_promotions_total >= 1
            return a, b
        finally:
            eng.shutdown()

    ref_a, ref_b = run(control())
    got_a, got_b = run(parked())
    assert got_a["tokens"] == ref_a["tokens"]
    assert got_b["tokens"] == ref_b["tokens"]  # the park was invisible


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_promotion_overlaps_admission(paged):
    """The prewarm hint starts the host->device swap-in BEFORE the turn
    is admitted; the admission stamp consumes the promote timestamp, so
    a recorded overlap proves the restore was in flight while the
    request was still queue-waiting (TTFT hides it)."""

    async def body():
        eng = LLMEngine.create("tiny", options=_opts(paged, True))
        try:
            await eng.chat("s", "turn one", max_tokens=5)
            assert await eng.park_session("s") is not None
            assert await eng.prewarm_session("s") is True
            assert eng.tier_prewarm_hits_total == 1
            await eng.chat("s", "turn two", max_tokens=5)
            assert eng.tier_promotions_total == 1
            assert len(eng.tier_promote_overlap_ms_recent) == 1 and eng.tier_promote_overlap_ms_recent[0] > 0
        finally:
            eng.shutdown()

    run(body())


def test_pool_pressure_demotes_instead_of_429():
    """A pool too small for every session to stay resident: the arrival
    that would have thrown PagePoolExhausted instead demotes the LRU
    idle session to the host tier and is served."""
    opts = dict(_opts(True, True))
    # 6-page pool (96 tokens): warmup's single max_seq lane fits, two
    # 3-page sessions fill it, and the third arrival must evict
    opts.update({"max_seq": 64, "kv_pages": 6})

    async def body():
        eng = LLMEngine.create("tiny", options=opts)
        try:
            msg = "alpha alpha alpha alpha alpha alpha"
            await eng.chat("a", msg, max_tokens=6)
            await eng.chat("b", msg.replace("alpha", "bravo"), max_tokens=6)
            # the third session NEEDS pages the pool doesn't have free —
            # without tiering this is a typed 429; with it, it serves
            r = await eng.chat("c", msg.replace("alpha", "charl"), max_tokens=6)
            assert r["tokens"]
            assert eng.tier_pressure_demotions_total >= 1
            parked = [s for s in ("a", "b") if s not in eng.sessions]
            assert parked  # somebody got demoted...
            for s in parked:
                assert eng.has_session(s)  # ...never dropped
        finally:
            eng.shutdown()

    run(body())


def test_reused_pages_cannot_corrupt_parked_copy():
    """Eviction racing promotion: the parked session's device pages go
    back through the quarantine to the free list and are REUSED by
    another session before the promote. The host copy was staged before
    the free, so the round-trip stays token-identical."""

    async def control():
        eng = LLMEngine.create("tiny", options=_opts(True, True))
        try:
            a1 = await eng.chat("a", "turn one", max_tokens=5)
            await eng.chat("b", "filler filler filler", max_tokens=5)
            a2 = await eng.chat("a", "turn two", max_tokens=5)
            return a1, a2
        finally:
            eng.shutdown()

    async def raced():
        eng = LLMEngine.create("tiny", options=_opts(True, True))
        try:
            a1 = await eng.chat("a", "turn one", max_tokens=5)
            assert await eng.park_session("a") is not None
            # b's prefill allocates from the pool a's park just refilled
            await eng.chat("b", "filler filler filler", max_tokens=5)
            a2 = await eng.chat("a", "turn two", max_tokens=5)
            return a1, a2
        finally:
            eng.shutdown()

    ref = run(control())
    got = run(raced())
    assert got[0]["tokens"] == ref[0]["tokens"]
    assert got[1]["tokens"] == ref[1]["tokens"]


def test_kv_demote_failpoint_keeps_session_resident():
    """A firing engine.kv_demote only costs density: the park no-ops,
    the session STAYS resident and serves, the failure is counted."""

    async def body():
        eng = LLMEngine.create("tiny", options=_opts(True, True))
        try:
            await eng.chat("s", "turn one", max_tokens=5)
            faults.arm("engine.kv_demote", error="RuntimeError", count=1)
            assert await eng.park_session("s") is None
            assert "s" in eng.sessions  # never left the device
            assert eng.tier_demote_failures_total == 1
            r = await eng.chat("s", "turn two", max_tokens=5)
            assert r["tokens"]
        finally:
            faults.disarm_all()
            eng.shutdown()

    run(body())


def test_kv_promote_failpoint_is_typed_429_then_recovers():
    """A firing engine.kv_promote fails the turn typed (EngineOverloaded
    -> 429 + Retry-After at the serve layer) while the host entry stays
    parked and untouched — the caller's retry promotes and the stream is
    still token-identical to the never-parked control."""

    async def control():
        eng = LLMEngine.create("tiny", options=_opts(True, True))
        try:
            await eng.chat("s", "turn one", max_tokens=5)
            return await eng.chat("s", "turn two", max_tokens=5)
        finally:
            eng.shutdown()

    async def body():
        eng = LLMEngine.create("tiny", options=_opts(True, True))
        try:
            await eng.chat("s", "turn one", max_tokens=5)
            assert await eng.park_session("s") is not None
            faults.arm("engine.kv_promote", error="RuntimeError", count=1)
            with pytest.raises(TierPromoteFailed) as ei:
                await eng.chat("s", "turn two", max_tokens=5)
            assert isinstance(ei.value, EngineOverloaded)  # typed 429 path
            assert eng.tier_promote_failures_total == 1
            assert eng.has_session("s")  # still safely parked
            assert "s" not in eng.sessions
            return await eng.chat("s", "turn two", max_tokens=5)  # retry
        finally:
            faults.disarm_all()
            eng.shutdown()

    ref = run(control())
    got = run(body())
    assert got["tokens"] == ref["tokens"]


def test_tier_metrics_surface():
    """The /metrics additions: tier gauges and counters ride the engine
    metrics dict so the manager rollup and benches can read them."""

    async def body():
        eng = LLMEngine.create("tiny", options=_opts(True, True))
        try:
            await eng.chat("s", "turn one", max_tokens=5)
            await eng.park_session("s")
            m = eng.metrics()
            assert m["kv_tiering"] is True
            assert m["tier_host_sessions"] == 1
            assert m["tier_host_bytes"] > 0
            assert m["tier_quantized_pages"] > 0
            assert m["tier_demotions_total"] == 1
            await eng.chat("s", "turn two", max_tokens=5)
            m = eng.metrics()
            assert m["tier_host_sessions"] == 0
            assert m["tier_promotions_total"] == 1
        finally:
            eng.shutdown()

    run(body())


def test_tiering_off_is_inert():
    """kv_tiering=False (the default): park/prewarm are no-ops and the
    pressure path still throws typed PagePoolExhausted — the A/B
    baseline is bit-identical to pre-tiering behavior."""

    async def body():
        eng = LLMEngine.create("tiny", options=dict(OPTS_PAGED))
        try:
            await eng.chat("s", "turn one", max_tokens=5)
            assert await eng.park_session("s") is None
            assert "s" in eng.sessions  # untouched
            assert await eng.prewarm_session("s") is False
        finally:
            eng.shutdown()

    run(body())
