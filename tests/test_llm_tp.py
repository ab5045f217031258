"""Serve-time tensor parallelism: the engine shards params + KV arena over
a tp mesh (GSPMD) and the full continuous-batching path still works.

Runs on the virtual 8-device CPU mesh (tests/conftest.py) — the TPU-world
analogue of multi-chip serving without hardware (SURVEY.md §4).
"""

import asyncio

import jax
import pytest

from agentainer_tpu.engine.llm import LLMEngine

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs the virtual multi-device mesh"
)


def _mk(tp: int) -> LLMEngine:
    return LLMEngine.create("tiny", options={"tp": tp, "max_batch": 4, "max_seq": 256})


def test_tp_engine_shards_params_and_cache():
    engine = _mk(2)
    try:
        assert engine.tp == 2
        # params actually live on 2 devices (column-parallel wq)
        wq = engine.params["layers"]["wq"]
        assert len(wq.sharding.device_set) == 2
        # KV arena split on the kv-head axis
        assert len(engine.cache.k.sharding.device_set) == 2

        async def go():
            return await engine.generate("hello world", max_tokens=8)

        result = asyncio.run(go())
        assert result["completion_tokens"] == 8
        assert engine.metrics()["tp"] == 2
    finally:
        engine.shutdown()


def test_single_chip_placement_uses_local_device_indices():
    """A tp==1 engine assigned slice chip 3 computes on ITS first device:
    the backend starts each engine host seeing only its chips
    (runtime/local.chip_visibility_env), so indices inside the process are
    local — two single-chip agents are kept apart by their processes'
    visibility, not by indexing a shared device list."""
    engine = LLMEngine.create("tiny", options={"chips": [3], "max_batch": 2, "max_seq": 128})
    try:
        assert engine.tp == 1
        assert [d.id for d in engine.params["final_norm"].devices()] == [0]
        assert [d.id for d in engine.cache.k.devices()] == [0]

        async def go():
            return await engine.generate("placed", max_tokens=4)

        assert asyncio.run(go())["completion_tokens"] == 4
    finally:
        engine.shutdown()


# The engines ROADMAP's four-chip cells and Speed 9 will deploy on a mesh:
# the default one, the page pool, and the page pool under the fused loop.
ENGINES = {
    "default": {},
    "paged": {"paged_kv": True},
    "paged+fused": {"paged_kv": True, "fused_decode": True},
}
_TURNS = ("the quick brown fox jumps over", "and then the lazy dog")


def two_turns(config: str, **options) -> list[list[int]]:
    """Greedy tokens of two turns of one session on a fresh engine."""
    engine = LLMEngine.create(
        config, options={"max_batch": 2, "max_seq": 128, **options}
    )
    try:

        async def go():
            return [
                (await engine.chat("s", turn, max_tokens=6))["tokens"] for turn in _TURNS
            ]

        return asyncio.run(go())
    finally:
        engine.shutdown()


@pytest.fixture(scope="module")
def one_chip():
    """Each family's tokens on the one-chip default engine, computed once."""
    memo: dict[str, list[list[int]]] = {}

    def tokens(config: str) -> list[list[int]]:
        if config not in memo:
            memo[config] = two_turns(config)
        return memo[config]

    return tokens


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("config", ["tiny", "tiny-moe", "tiny-olmoe"])
def test_tp_matches_single_chip_greedy(config, engine, one_chip):
    """Greedy decode must produce the same tokens sharded or not (f32 CPU;
    the collectives only change the reduction layout), in every family and
    on every engine a mesh can carry."""
    got = two_turns(config, tp=2, **ENGINES[engine])
    assert got == one_chip(config), (got, one_chip(config))


def test_tp_session_snapshot_restore_roundtrip():
    """KV crash-resume works on a sharded arena: snapshot from a tp engine,
    restore into a fresh one, context preserved."""
    engine = _mk(2)
    try:

        async def turn(e, msg):
            return await e.chat(session="s1", message=msg, max_tokens=4)

        async def turn_and_snap(e, msg):
            await e.chat(session="s1", message=msg, max_tokens=4)
            return await e.snapshot_session("s1")

        blob = asyncio.run(turn_and_snap(engine, "first turn"))
        assert blob
        pos = engine.slots[engine.sessions["s1"]].position
    finally:
        engine.shutdown()

    engine2 = _mk(2)
    try:

        async def restore():
            return await engine2.restore_session("s1", blob)

        assert asyncio.run(restore())
        assert engine2.slots[engine2.sessions["s1"]].position == pos
        asyncio.run(turn(engine2, "second turn"))
    finally:
        engine2.shutdown()


def test_dense_chips_default_to_tp_spanning_assignment():
    """A dense agent assigned N chips with no explicit tp spans them all —
    the scheduler sized the assignment; idle chips help nobody. (The
    control plane no longer injects tp; LLMEngine.create derives it.)"""
    engine = LLMEngine.create("tiny", options={"chips": [0, 1], "max_batch": 2, "max_seq": 128})
    try:
        assert engine.tp == 2
        assert {d.id for d in engine.cache.k.sharding.device_set} == {0, 1}
    finally:
        engine.shutdown()


@pytest.mark.parametrize("axis", ["sp", "pp"])
def test_create_refuses_unserved_layout(axis):
    """A deployment that asks for a sequence-sharded arena or staged layers
    fails by name; it does not come up on one chip with less than it asked."""
    with pytest.raises(ValueError, match=f"{axis}=2.*not served"):
        LLMEngine.create("tiny", options={axis: 2, "max_batch": 2, "max_seq": 128})
