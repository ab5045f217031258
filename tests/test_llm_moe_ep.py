"""Expert parallelism in the SERVING engine (BASELINE.json config #5).

The engine builds a tp×ep mesh for MoE models; expert weights shard over
``ep`` (each device owns and computes E/ep experts — parallel/sharding.py's
``P(None, "ep", None, "tp")`` specs) and GSPMD turns the top-k combine's
expert contraction into an ICI psum. Runs on the virtual 8-device CPU mesh
(tests/conftest.py) — the TPU-world analogue of Mixtral-8x7B across v5e-8.
"""

import asyncio

import jax
import pytest

from agentainer_tpu.engine.llm import LLMEngine

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the virtual 8-device mesh"
)


def _mk(**opts) -> LLMEngine:
    options = {"max_batch": 2, "max_seq": 128}
    options.update(opts)
    return LLMEngine.create("tiny-moe", options=options)


def _gen(engine, prompt="the quick brown fox", n=6):
    async def go():
        return await engine.generate(prompt, max_tokens=n)

    return asyncio.run(go())


def test_ep_engine_shards_expert_weights():
    engine = _mk(ep=4)
    try:
        assert engine.ep == 4 and engine.tp == 1
        wg = engine.params["layers"]["w_gate"]
        assert len(wg.sharding.device_set) == 4
        # attention weights replicate over ep (no tp axis in play)
        result = _gen(engine)
        assert result["completion_tokens"] == 6
        assert engine.metrics()["ep"] == 4
    finally:
        engine.shutdown()


def test_ep_matches_single_device():
    """Same greedy tokens dense single-chip vs ep=4 vs tp=2×ep=2 (f32 CPU):
    expert sharding only relocates compute, not the math."""
    e1 = _mk()
    e2 = _mk(ep=4)
    e3 = _mk(tp=2, ep=2)
    try:
        r1, r2, r3 = _gen(e1), _gen(e2), _gen(e3)
        assert r1["tokens"] == r2["tokens"], (r1["tokens"], r2["tokens"])
        assert r1["tokens"] == r3["tokens"], (r1["tokens"], r3["tokens"])
    finally:
        e1.shutdown()
        e2.shutdown()
        e3.shutdown()


def test_moe_placement_defaults_ep_first():
    """A MoE agent assigned a whole slice splits it EP-first: tiny-moe
    (4 experts) on 8 chips → ep=4, tp=2 — experts dominate MoE HBM."""
    engine = _mk(chips=list(range(8)))
    try:
        assert engine.ep == 4
        assert engine.tp == 2
        # the mesh spans all 8 assigned chips
        assert len(engine.params["layers"]["w_gate"].sharding.device_set) == 8
        assert _gen(engine)["completion_tokens"] == 6
    finally:
        engine.shutdown()


def test_moe_tp_ep_session_roundtrip():
    """Multi-turn chat + KV snapshot/restore on a tp×ep mesh."""
    engine = _mk(tp=2, ep=2)
    try:

        async def turn(e, msg):
            return await e.chat(session="s1", message=msg, max_tokens=4)

        async def turn_and_snap(e, msg):
            await e.chat(session="s1", message=msg, max_tokens=4)
            return await e.snapshot_session("s1")

        blob = asyncio.run(turn_and_snap(engine, "first turn"))
        assert blob
    finally:
        engine.shutdown()

    engine2 = _mk(tp=2, ep=2)
    try:

        async def restore():
            return await engine2.restore_session("s1", blob)

        assert asyncio.run(restore())
        asyncio.run(turn(engine2, "second turn"))
    finally:
        engine2.shutdown()
