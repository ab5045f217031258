"""Paged KV arena (ISSUE 6 tentpole): block-table attention, zero-copy
prefix sharing, page-tail speculative rewind.

Correctness bars pinned here, all against the dense arena as ground truth
(paged_kv=false is the A/B baseline):

- greedy decode is BIT-EXACT across the two layouts — single lane, mixed
  greedy/temperature batch, multi-turn sessions;
- a warm-prefix admission maps refcounted pages instead of forking a KV
  copy: the compiled fork-fn path is NEVER invoked in paged mode, and the
  zero-copy mapping is observable in the page metrics;
- resident sessions decouple from max_batch: a dense-equivalent pool holds
  ≥ 4× max_batch short sessions with zero evictions, and pool pressure
  evicts LRU idle residents who then re-admit correctly;
- speculative accept/reject rewind is page-tail truncation — forced
  rejections leave the greedy stream identical and return garbage pages
  to the pool;
- snapshot → restore round-trips token-identically, across paged→paged
  AND paged→dense (SNAP_VERSION 3 payload is layout-portable);
- pool exhaustion (organic or via the engine.page_alloc failpoint) is 429
  backpressure — typed EngineOverloaded, counted, never a crash.

Engine-hungry assertions share engines (same discipline as
tests/test_speculative.py): the suite's 870s budget is tight and every
engine creation pays the warmup compile ladder, so the paged/dense pair
below serves parity, zero-copy prefix, spec rewind, AND the snapshot
round-trip in one pass.
"""

import asyncio

import pytest

from agentainer_tpu import faults
from agentainer_tpu.engine.llm import EngineOverloaded, LLMEngine, PagePoolExhausted

BASE = {
    "max_batch": 4,
    # every warmup compile scales with these: 128 seq is enough for the
    # ~100-token contexts below and drops a whole pow2 level of prefill/
    # snapshot shapes; chunk 4 compiles a {1,2,4} decode ladder, not {1,2,4,8}
    "max_seq": 128,
    "decode_chunk": 4,
    "prefill_chunk": 32,
    # speculation is covered by its own phase below (on the paged engine
    # only); leaving it on everywhere would compile the 3-bucket verify
    # ladder for every engine this file creates, dominating suite wall time
    "speculative": False,
}


def _mk(paged: bool, **opts) -> LLMEngine:
    o = dict(BASE)
    if paged:
        o.update(paged_kv=True, page_size=32)
    o.update(opts)
    return LLMEngine.create("tiny", options=o)


JSON_LOOP = '{"tool": "search", "args": {"q": "w", "n": 5}}\n' * 4


@pytest.fixture(scope="module")
def pair():
    """One paged + one dense engine shared by every parity assertion in
    this file. Pool is ample (64 pages) so pool-pressure eviction can't
    (correctly) diverge the pair — eviction policy has its own engine."""
    paged = _mk(True, kv_pages=64)
    dense = _mk(False)
    yield paged, dense
    paged.shutdown()
    dense.shutdown()


def test_greedy_parity_mixed_batch_multi_turn(pair):
    """The flagship invariant: identical token streams from the paged and
    dense engines — solo, in a mixed greedy/temperature batch, and across
    session turns — while the paged engine demonstrably served from pages
    (pool gauges move, lanes detach between turns)."""
    paged, dense = pair

    async def drive(e):
        out = []
        solo = await e.generate(
            "a solo generation prompt with some words", max_tokens=24
        )
        out.append(solo["tokens"])
        g, _ = await asyncio.gather(
            e.generate("greedy lane in a mixed batch", max_tokens=16),
            e.generate("noise lane " * 3, max_tokens=16, temperature=1.0),
        )
        out.append(g["tokens"])
        for turn in ("first turn of a session", "second turn continues"):
            r = await e.chat("sess", turn, max_tokens=12)
            out.append(r["tokens"])
        return out

    tp = asyncio.run(drive(paged))
    td = asyncio.run(drive(dense))
    assert tp == td, (tp, td)
    m = paged.metrics()
    assert m["paged_kv"] is True and dense.metrics()["paged_kv"] is False
    assert m["kv_pages_used"] > 0
    # between turns the session holds pages but NO lane
    sess = paged.paged_sessions["sess"]
    assert sess.lane is None and sess.pages and sess.position > 0
    assert paged.worker_errors == 0 and dense.worker_errors == 0


def test_prefix_hit_admission_is_zero_copy(pair):
    """Second session with a shared prefix: paged admission maps the cached
    pages (refcount bump) instead of forking a copy. Pinned by making the
    dense fork path explosive — it must never be reached — and by parity
    with the dense engine's forked result."""
    paged, dense = pair

    def _boom(bucket):  # pragma: no cover - the whole point is it never runs
        raise AssertionError("dense fork-fn invoked in paged mode")

    paged._prefix_fork_fn = _boom
    persona = "You are a careful assistant. " * 3  # ~90 tokens, fits budget

    async def drive(e):
        a = await e.chat("pa", persona + "first question", max_tokens=10)
        b = await e.chat("pb", persona + "second question", max_tokens=10)
        return a["tokens"], b["tokens"]

    tp = asyncio.run(drive(paged))
    td = asyncio.run(drive(dense))
    assert tp == td, (tp, td)
    m = paged.metrics()
    assert m["prefix_hits"] >= 1, m
    assert m["prefix_pages_shared_total"] >= 1, m
    assert m["kv_pages_prefix_pinned"] >= 1, m
    assert paged._prefix_fork_fns == {}
    # the mapped pages really are shared: refcount > 1 on the first
    # shared page of the hitting session
    sess = paged.paged_sessions["pb"]
    assert sess.shared >= 1
    assert paged._page_refs[sess.pages[0]] >= 2


def test_spec_rewind_is_page_tail_truncation_and_bit_exact(pair):
    """Forced all-reject speculation: the greedy stream stays identical to
    the never-speculating paged AND dense engines, rejected drafts' pages
    return to the pool, and a post-rejection
    snapshot restores token-identically."""
    base, dense = pair
    # gamma_max 2 compiles ONE verify bucket (the forced drafts are len 2);
    # the default ladder would compile {2,4,8} — pure suite-budget waste here
    spec = _mk(True, kv_pages=64, speculative=True, spec_gamma_max=2)
    spec._spec_draft = lambda slot, gamma: [3, 5]  # junk: ~always rejected
    try:

        async def turns(e):
            r1 = await e.chat(
                "sp", '{"t": "s", "q": 1}\n' * 3 + "turn one", max_tokens=24
            )
            blob = await e.snapshot_session("sp")
            r2 = await e.chat("sp", "turn two continues the session", max_tokens=12)
            return r1, blob, r2

        r1s, blob_s, r2s = asyncio.run(turns(spec))
        r1b, _, r2b = asyncio.run(turns(base))
        r1d, _, r2d = asyncio.run(turns(dense))
        assert r1s["tokens"] == r1b["tokens"] == r1d["tokens"]
        assert spec.spec_drafted > spec.spec_accepted, spec.metrics()  # drafts were rejected
        assert r2s["tokens"] == r2b["tokens"] == r2d["tokens"]
        assert blob_s is not None

        async def resume():
            ok = await base.restore_session("rs", blob_s)
            assert ok
            return await base.chat(
                "rs", "turn two continues the session", max_tokens=12
            )

        r2r = asyncio.run(resume())
        assert r2r["tokens"] == r2b["tokens"], (r2r["tokens"], r2b["tokens"])
    finally:
        spec.shutdown()


def test_snapshot_restore_round_trip_across_layouts(pair):
    """SNAP_VERSION 3 blobs (staged from live pages only) restore into the
    paged engine and into the DENSE engine; the continuation is
    token-identical in all six lanes. Dense blobs restore into paged too."""
    paged, dense = pair

    async def drive():
        await paged.chat("snap", "some context worth keeping around", max_tokens=12)
        await dense.chat("snap", "some context worth keeping around", max_tokens=12)
        pb = await paged.snapshot_session("snap")
        db = await dense.snapshot_session("snap")
        assert pb and db
        # cross-restore all four directions
        assert await paged.restore_session("from-paged", pb)
        assert await paged.restore_session("from-dense", db)
        assert await dense.restore_session("from-paged", pb)
        assert await dense.restore_session("from-dense", db)
        outs = []
        for e, name in (
            (paged, "snap"),
            (paged, "from-paged"),
            (paged, "from-dense"),
            (dense, "snap"),
            (dense, "from-paged"),
            (dense, "from-dense"),
        ):
            r = await e.chat(name, "continue the story", max_tokens=12)
            outs.append(r["tokens"])
        return outs

    outs = asyncio.run(drive())
    assert all(o == outs[0] for o in outs), outs
    # the paged restore entered residency without binding a lane; after the
    # continuation turn the lane detaches again
    assert paged.paged_sessions["from-paged"].lane is None


def test_residency_beyond_max_batch_and_eviction_readmission():
    """A dense-equivalent pool (same HBM as the [max_batch, max_seq] arena)
    holds ≥ 4× max_batch short sessions with zero evictions; overflowing
    the pool evicts LRU idle residents, and an evicted session re-admits
    (cold) and generates correctly."""
    # small max_batch makes the ≥4× bar cheap: default pool = 2 slots' HBM
    # (max_seq back at 256 so the 8 short residents fill half the pool and
    # the long sessions genuinely overflow it)
    paged = _mk(True, max_batch=2, max_seq=256)
    try:

        async def short_sessions(n):
            for i in range(n):
                await paged.chat(f"c{i}", "hi", max_tokens=8)

        asyncio.run(short_sessions(8))
        m = paged.metrics()
        assert m["resident_sessions"] >= 4 * paged.max_batch, m
        assert paged.session_evictions == 0
        assert "c0" in paged.sessions  # membership surface for the serve layer

        # overflow: long-context sessions force pool pressure → LRU idle
        # residents (the short sessions above) evict
        async def big_sessions(n):
            for i in range(n):
                await paged.chat(f"big{i}", "x " * 100, max_tokens=24)

        asyncio.run(big_sessions(4))
        assert paged.session_evictions > 0
        assert paged.metrics()["resident_sessions"] < 12
        # an evicted session re-admits cold and still serves
        r = asyncio.run(paged.chat("c0", "hello again", max_tokens=8))
        assert len(r["tokens"]) == 8
        assert paged.worker_errors == 0, paged.last_worker_error
    finally:
        paged.shutdown()


def test_pool_exhaustion_is_429_backpressure_not_a_crash():
    """A pool too small for the requested generation fails THAT request
    with PagePoolExhausted (an EngineOverloaded → 429 + Retry-After at the
    serve layer), counts it, and keeps serving everything that fits."""
    # 2 pages = 64 tokens of KV for ONE session; the budget check passes
    # (max_seq allows it) but the pool cannot back it
    eng = _mk(True, max_batch=2, max_seq=128, kv_pages=2)
    try:

        async def too_big():
            await eng.generate("grow past the pool " * 3, max_tokens=80)

        with pytest.raises(EngineOverloaded):
            asyncio.run(too_big())
        assert eng.page_exhausted_total >= 1
        assert eng.metrics()["page_exhausted_total"] >= 1

        # failpoint-driven exhaustion: deterministic injection at the
        # allocation seam surfaces as the SAME typed backpressure
        faults.arm("engine.page_alloc", error="RuntimeError", count=1)
        try:
            with pytest.raises(EngineOverloaded):
                asyncio.run(eng.generate("anything at all", max_tokens=8))
        finally:
            faults.disarm_all()

        # the engine survives both: a pool-sized request serves fine
        r = asyncio.run(eng.generate("small", max_tokens=8))
        assert len(r["tokens"]) == 8

        # a RESIDENT session that trips exhaustion on a later turn is
        # ROLLED BACK, not destroyed: exhaustion is a policy failure that
        # never corrupts the session's existing KV, so its context
        # survives for the client's Retry-After retry
        async def keep_flow():
            await eng.chat("keep", "hello", max_tokens=8)
            pos = eng.paged_sessions["keep"].position
            with pytest.raises(EngineOverloaded):
                await eng.chat("keep", "go long", max_tokens=80, ignore_eos=True)
            sess = eng.paged_sessions["keep"]
            assert sess.position == pos and sess.pages, (sess.position, pos)
            return await eng.chat("keep", "short again", max_tokens=8)

        r2 = asyncio.run(keep_flow())
        assert len(r2["tokens"]) == 8
        assert isinstance(
            PagePoolExhausted(1, 0), EngineOverloaded
        )  # the 429 mapping contract
    finally:
        eng.shutdown()
