"""Self-speculative decoding (ISSUE 4 tentpole): prompt-lookup drafting +
batched multi-token verification.

Correctness bars pinned here:

- greedy lanes are BIT-EXACT with ``speculative=false`` (single lane,
  mixed greedy/temperature batch, mid-stream eviction, crash-restore);
- the KV rewind invariant: cache writes beyond a slot's live length
  (rejected drafts, stale pokes) are position-masked — they can never
  influence a later token, and a snapshot/restore round-trip taken after
  rejections resumes token-identical to a never-speculated lane;
- the acceptance-rate EMA collapses gamma to 0 on low-match traffic (the
  plain decode ladder serves those lanes, so adversarial workloads
  degrade to baseline);
- the verify ladder is compiled at warmup — serving-time speculation must
  never pay a compile.
"""

import asyncio

from agentainer_tpu.engine.llm import SPEC_EMA_FLOOR, LLMEngine
from agentainer_tpu.models.llama import KVCache


def _mk(**opts) -> LLMEngine:
    base = {
        "max_batch": 4,
        "max_seq": 256,
        "decode_chunk": 8,
        "prefill_chunk": 32,
    }
    base.update(opts)
    return LLMEngine.create("tiny", options=base)


# tool-call-loop shaped prompt: the trailing n-gram always has an earlier
# occurrence, so the drafter proposes full buckets
JSON_LOOP = '{"tool": "search", "args": {"q": "w", "n": 5}}\n' * 4


def test_greedy_bit_exact_with_and_without_speculation():
    """The flagship invariant: with speculation on, greedy outputs are
    token-identical to the plain engine — alone and in a batch mixing a
    greedy lane with a temperature lane — while the verify path actually
    ran (rounds and accepted drafts observable in metrics). Also pins the
    warmup bar on the same engines (the suite's 870s budget is tight, so
    engine-hungry assertions share engines): every verify bucket compiles
    at warmup and serving never compiles more; the speculative=false
    engine builds no verify ladder at all."""
    spec = _mk()
    base = _mk(speculative=False)
    try:
        assert set(spec._verify_fns) == set(spec._spec_buckets) == {2, 4, 8}
        sizes = {b: spec._verify_fns[b]._cache_size() for b in spec._spec_buckets}
        assert all(v >= 1 for v in sizes.values()), sizes
        assert base._verify_fns == {}

        async def drive(e):
            solo = await e.generate(JSON_LOOP + "solo", max_tokens=60, temperature=0.0)
            g, _ = await asyncio.gather(
                e.generate(JSON_LOOP + "mixed", max_tokens=48, temperature=0.0),
                e.generate("noise lane " * 3, max_tokens=48, temperature=1.0),
            )
            return solo, g

        s1, g1 = asyncio.run(drive(spec))
        s0, g0 = asyncio.run(drive(base))
        assert s1["tokens"] == s0["tokens"], (s1["tokens"], s0["tokens"])
        assert g1["tokens"] == g0["tokens"], (g1["tokens"], g0["tokens"])
        m = spec.metrics()
        assert m["speculative"] is True
        assert m["spec_rounds"] > 0, m
        assert m["spec_drafted"] > 0 and m["spec_accepted"] > 0, m
        assert m["spec_verify_hist"], m
        assert 0 < m["spec_accepted"] <= m["spec_drafted"]
        after = {b: spec._verify_fns[b]._cache_size() for b in spec._spec_buckets}
        assert after == sizes, (sizes, after)
        bm = base.metrics()
        assert bm["speculative"] is False
        assert bm["spec_rounds"] == 0 and bm["spec_drafted"] == 0
        assert base._verify_fns == {}
        # lookup-miss backoff: temperature-1 output over the tiny model is
        # near-uniform — ~no trigram repeats, so the lane stops triggering
        # the (pipeline-draining) speculation path within a few misses
        rounds_before = spec.spec_rounds

        async def noisy():
            return await spec.generate("zq", max_tokens=100, temperature=1.0)

        r = asyncio.run(noisy())
        assert r["completion_tokens"] == 100
        assert spec.spec_rounds - rounds_before <= 4, spec.metrics()
        assert spec.worker_errors == 0 and base.worker_errors == 0
    finally:
        spec.shutdown()
        base.shutdown()


def test_stale_kv_beyond_live_length_is_masked():
    """The rewind invariant, pinned directly: garbage KV written at
    positions >= a slot's live length (exactly what rejected drafts leave
    behind) must not change a single future token — the position mask
    hides those rows until the stream overwrites them."""
    poked = _mk()
    clean = _mk()
    try:

        async def turn1(e):
            return await e.chat("s", JSON_LOOP + "first turn", max_tokens=24)

        r1p = asyncio.run(turn1(poked))
        r1c = asyncio.run(turn1(clean))
        assert r1p["tokens"] == r1c["tokens"]
        # engine idle now: blast garbage over every cache row at/above the
        # slot's live length (the stale-draft region, maximally corrupted)
        idx = poked.sessions["s"]
        pos = poked.slots[idx].position
        k = poked.cache.k.at[:, idx, pos:, :, :].set(1e3)
        v = poked.cache.v.at[:, idx, pos:, :, :].set(-1e3)
        poked.cache = KVCache(k, v)

        async def turn2(e):
            return await e.chat("s", "second turn continues", max_tokens=24)

        r2p = asyncio.run(turn2(poked))
        r2c = asyncio.run(turn2(clean))
        assert r2p["tokens"] == r2c["tokens"], (r2p["tokens"], r2c["tokens"])
    finally:
        poked.shutdown()
        clean.shutdown()


def test_rejected_drafts_then_restore_round_trip_matches_plain():
    """After a generation with real rejections, (a) the session's next turn
    and (b) a snapshot/restore round-trip both produce tokens identical to
    a never-speculated lane — the snapshot taken after rejections must
    carry no stale-draft contamination.

    Rejections are forced deterministically: the drafter is replaced with
    one proposing junk tokens, so every verify round rejects, rewinds the
    KV position, and emits the model's own correction — which must leave
    the greedy stream bit-identical to the plain engine's."""
    spec = _mk()
    spec._spec_draft = lambda slot, gamma: [3, 5]  # junk: ~always rejected
    base = _mk(speculative=False)
    try:

        async def turns(e):
            # short turn: the session must NOT hit the context-reset path
            # on turn two (a reset re-frames the prompt and legitimately
            # diverges the engines — that is admission policy, not spec)
            r1 = await e.chat("s", '{"t": "s", "q": 1}\n' * 3 + "turn one", max_tokens=40)
            blob = await e.snapshot_session("s")
            r2 = await e.chat("s", "turn two continues the session", max_tokens=24)
            return r1, blob, r2

        r1s, blob_s, r2s = asyncio.run(turns(spec))
        r1b, _, r2b = asyncio.run(turns(base))
        assert r1s["tokens"] == r1b["tokens"]
        # drafts were really scored, and not all of them accepted — the
        # rewind path (position pulled back past rejected tokens) ran
        assert spec.spec_drafted > 0
        assert spec.spec_drafted > spec.spec_accepted, spec.metrics()  # drafts were rejected
        # (a) direct continuation after rewinds is token-identical
        assert r2s["tokens"] == r2b["tokens"], (r2s["tokens"], r2b["tokens"])
        # (b) the speculated engine's snapshot restores into a
        # NEVER-speculating engine (fresh session name = fresh slot, the
        # crash-restore shape) and continues token-identical
        assert blob_s is not None

        async def resume():
            ok = await base.restore_session("r", blob_s)
            assert ok
            return await base.chat("r", "turn two continues the session", max_tokens=24)

        r2r = asyncio.run(resume())
        assert r2r["tokens"] == r2b["tokens"], (r2r["tokens"], r2b["tokens"])
    finally:
        spec.shutdown()
        base.shutdown()


def test_mid_stream_eviction_stays_bit_exact():
    """Session evicted between turns (slot LRU) then re-admitted: the
    speculating engine matches the plain engine token-for-token across the
    whole sequence — eviction resets the drafting corpus with the slot."""
    spec = _mk(max_batch=2)
    base = _mk(max_batch=2, speculative=False)
    try:

        async def drive(e):
            out = []
            out.append(await e.chat("victim", JSON_LOOP + "turn one", max_tokens=24))
            out.append(await e.chat("other-1", "unrelated words", max_tokens=8))
            out.append(await e.chat("other-2", "more unrelated", max_tokens=8))
            assert "victim" not in e.sessions  # LRU-evicted
            out.append(await e.chat("victim", JSON_LOOP + "turn two", max_tokens=24))
            return [r["tokens"] for r in out]

        toks_s = asyncio.run(drive(spec))
        toks_b = asyncio.run(drive(base))
        assert toks_s == toks_b
    finally:
        spec.shutdown()
        base.shutdown()


def test_acceptance_ema_collapses_on_rejecting_traffic():
    """A lane whose drafts keep getting rejected must stop speculating:
    the EMA collapses under the floor, gamma goes to 0, and the rest of
    the generation comes from the plain decode ladder (graceful
    degradation — an adversarial workload pays a handful of verify rounds,
    not one per token). Forced with a junk drafter so the rejections are
    deterministic."""
    eng = _mk()
    eng._spec_draft = lambda slot, gamma: [3, 5]
    try:

        async def drive():
            return await eng.generate(
                "repeat repeat repeat repeat repeat repeat",
                max_tokens=120,
                temperature=0.0,
            )

        r = asyncio.run(drive())
        assert r["completion_tokens"] == 120
        m = eng.metrics()
        assert m["spec_drafted"] > 0, m
        assert m["spec_drafted"] > m["spec_accepted"], m  # drafts were rejected
        # the lane's EMA fell below the collapse floor → gamma 0 → later
        # tokens came from the plain decode path (visible per slot)
        assert min(m["spec_slot_acceptance"]) < SPEC_EMA_FLOOR, m
        # collapse means verify rounds STOPPED: far fewer rounds than a
        # round-per-token pace would produce
        assert m["spec_rounds"] < 50, m
    finally:
        eng.shutdown()


