"""Engine worker fault isolation + admission-latency observability.

VERDICT r4 items 1b/10: a poisoned request must fail ALONE (round 4 failed
every in-flight request on any worker exception, so one bad prompt nuked
the whole batch), the engine must keep serving afterwards, and
submit→prefill-start queueing delay must be visible separately from TTFT.
"""

import asyncio

from agentainer_tpu.engine.llm import LLMEngine

OPTS = {"max_batch": 8, "max_seq": 256, "decode_chunk": 2}


def test_poisoned_prefill_fails_only_culprit():
    engine = LLMEngine.create("tiny", options=OPTS)
    orig_prefill = engine._prefill
    poison = {"armed": False}

    def tripwire(*a, **k):
        if poison["armed"]:
            poison["armed"] = False
            raise RuntimeError("synthetic prefill fault")
        return orig_prefill(*a, **k)

    engine._prefill = tripwire

    async def scenario():
        loop = asyncio.get_running_loop()
        # A: long generation in flight
        task_a = loop.create_task(engine.chat(session="a", message="steady", max_tokens=120))
        for _ in range(2000):
            await asyncio.sleep(0.005)
            idx = engine.sessions.get("a")
            if idx is not None and engine.slots[idx].request is not None and engine.slots[
                idx
            ].request.generated:
                break
        # B: the next prefill trips the fault — only B must die
        poison["armed"] = True
        try:
            await engine.chat(session="b", message="boom", max_tokens=4)
            raise AssertionError("poisoned request did not fail")
        except RuntimeError as e:
            assert "synthetic prefill fault" in str(e)
        a = await task_a
        assert a["completion_tokens"] == 120  # A survived B's fault
        # engine still serves new sessions afterwards
        c = await engine.chat(session="c", message="after the fault", max_tokens=4)
        assert c["completion_tokens"] == 4
        return a

    try:
        asyncio.run(scenario())
        m = engine.metrics()
        assert m["worker_errors"] == 1
        assert "synthetic prefill fault" in m["last_worker_error"]
        assert m["cache_resets"] == 0  # fault raised before any donation loss
    finally:
        engine.shutdown()


def test_admission_burst_fairness():
    """8 simultaneous new sessions: every one's queueing delay (submit →
    first prefill chunk) is tracked, and the LAST admitted session's wait is
    bounded — chunked prefill keeps head-of-line blocking to chunks, so the
    spread stays within a small multiple of one prefill pass."""
    engine = LLMEngine.create("tiny", options=OPTS)

    async def burst():
        return await asyncio.gather(
            *(
                engine.chat(session=f"s{i}", message=f"burst question {i}", max_tokens=4)
                for i in range(8)
            )
        )

    try:
        results = asyncio.run(burst())
        assert all(r["completion_tokens"] == 4 for r in results)
        m = engine.metrics()
        adm = m["admission_samples"]
        assert len(adm) == 8  # one per admitted prompt
        assert m["admission_ms_p50"] is not None
        # every session's TTFT includes its admission wait; the histogram
        # separating them is the point — sanity-check the ordering holds
        assert m["admission_ms_p50"] <= (m["ttft_ms_p50"] or float("inf"))
        # generous absolute bound: the whole burst is 8 tiny prefills; a
        # serialized pathological scheduler would blow far past this
        assert max(adm) < 5000
    finally:
        engine.shutdown()
