"""Expert parallelism in the SERVING engine (BASELINE.json config #5).

The engine builds a tp×ep mesh for MoE models; expert weights shard over
``ep`` (each device owns and computes E/ep experts — parallel/sharding.py's
``P(None, "ep", None, "tp")`` specs) and GSPMD turns the top-k combine's
expert contraction into an ICI psum. Runs on the virtual 8-device CPU mesh
(tests/conftest.py) — the TPU-world analogue of Mixtral-8x7B across v5e-8.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.engine.llm import LLMEngine
from agentainer_tpu.models.configs import get_config
from agentainer_tpu.models.llama import _moe_mlp, init_params
from agentainer_tpu.parallel.expert import moe_expert_parallel
from agentainer_tpu.parallel.mesh import make_mesh

from .test_llm_tp import ENGINES, one_chip, two_turns  # noqa: F401  (fixture)

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


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize(
    "layout", [{"ep": 2}, {"tp": 2, "ep": 2}], ids=["ep2", "tp2xep2"]
)
@pytest.mark.parametrize("config", ["tiny-moe", "tiny-olmoe"])
def test_ep_matches_single_device(config, layout, engine, one_chip):
    """Same greedy tokens one chip vs ep=2 vs tp=2×ep=2 (f32 CPU), in both
    MoE families and on every engine a mesh can carry: expert sharding only
    relocates compute, not the math."""
    got = two_turns(config, **layout, **ENGINES[engine])
    assert got == one_chip(config), (got, one_chip(config))


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


def _layer0(cfg):
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return {k: v[0] for k, v in params["layers"].items()}  # layer 0, no L axis


def test_expert_parallel_matches_dense():
    """The all-experts shard_map (every device computes its local experts
    for every token, psum over ep) against the one-device MoE MLP."""
    cfg = get_config("tiny-moe")  # 4 experts, top-2
    lp = _layer0(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.dim), jnp.float32)
    ep_out = moe_expert_parallel(x, lp, cfg, make_mesh(ep=4), axis="ep")
    np.testing.assert_allclose(
        np.asarray(ep_out), np.asarray(_moe_mlp(x, lp, cfg)), rtol=2e-4, atol=2e-4
    )


def test_expert_parallel_rejects_bad_ep():
    cfg = get_config("tiny-moe")
    x = jnp.zeros((1, 4, cfg.dim), jnp.float32)
    with pytest.raises(ValueError):  # 8 does not divide 4 experts
        moe_expert_parallel(x, _layer0(cfg), cfg, make_mesh(ep=8), axis="ep")
