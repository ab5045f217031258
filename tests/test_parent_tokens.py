"""Greedy replies through the engine are the parent's, token for token.

``tests/data/engine_tokens_parent_pr26.json`` holds what the parent commit
(b5375af, PR 26: the layer scan fed the arena as ``xs`` and took it back as
``ys``) answered on this CPU backend with seeded weights. ISSUE 27 changed
how the arena travels through the step programs (carried, written in place,
read by layer) and nothing of what is stored or computed, so chunked
prefill, the decode ladder, speculation's verify and rewind, the prefix
fork, a session's second turn and two lanes at once must all answer the
same tokens, on the dense arena and on the page pool. (Snapshot → kill →
restore identity is tests/test_kv_resume.py's; ``forward`` against the
parent's logits is tests/test_olmoe.py's.)
"""

import asyncio
import json
import os

import pytest

from agentainer_tpu.engine.llm import LLMEngine

JSON_LOOP = '{"tool": "search", "args": {"q": "w", "n": 5}}\n' * 4
PERSONA = "You are a terse assistant. Answer in one word. " * 4
WANT = json.load(
    open(os.path.join(os.path.dirname(__file__), "data", "engine_tokens_parent_pr26.json"))
)


async def _replies(eng) -> dict:
    async def gen(prompt, n, **kw):
        return (await eng.generate(prompt, max_tokens=n, **kw))["tokens"]

    r = {"plain": await gen("hello there", 9)}
    # five prefill chunks of 32, then the decode ladder
    r["long"] = await gen("longer than one prefill chunk " * 5, 12, ignore_eos=True)
    # repetitive text: prompt-lookup drafts, verify rounds, rewinds
    r["json"] = await gen(JSON_LOOP, 24, ignore_eos=True)
    # two sessions share a persona (prefix fork), then a second turn
    r["s_a1"] = await gen(PERSONA + "What is two plus two?", 6, session="a", ignore_eos=True)
    r["s_b1"] = await gen(PERSONA + "Name a color.", 6, session="b", ignore_eos=True)
    r["s_a2"] = await gen("and another thing", 6, session="a", ignore_eos=True)
    r["conc"] = list(
        await asyncio.gather(
            gen(JSON_LOOP, 16, ignore_eos=True), gen("late lane", 6, ignore_eos=True)
        )
    )
    return r


@pytest.mark.parametrize("arena", ["dense", "paged"])
@pytest.mark.parametrize("model", ["tiny", "tiny-moe", "tiny-olmoe"])
def test_greedy_replies_are_the_parents(model, arena):
    eng = LLMEngine.create(
        model,
        options={
            "max_batch": 4, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 32,
            "skip_warmup": True, "paged_kv": arena == "paged",
        },
    )
    try:
        got = asyncio.run(_replies(eng))
    finally:
        eng.shutdown()
    assert got == WANT[f"{model}.{arena}"]
