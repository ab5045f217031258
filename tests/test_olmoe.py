"""OLMoE's block in ``models/llama.py`` (``qk_norm``, ``moe_renormalize``
False) against the benchmark's plain float32 reference
(``benchmark/families/olmoe_reference.py``, which imports nothing of the
program), on the CPU at tiny sizes with seeded weights.

Weights are NOT the 0.02-std init: at a hidden size of 64 those make
attention a near-uniform average and every gate near 1/E, and a check is then
blind to the very steps this block adds (PR 25 read 0.12 % for a reference
without the rotary embedding). Here the query/key projections, the router and
the two QK-norm weights are scaled up until a per-head norm or renormalised
gates move the logits by tens of per cent.

Tolerance: both sides compute in float32, the reference at ``highest`` matmul
precision and the program at the CPU backend's default (float32 too), and
differ by the order of summation only: the rms difference over the logits'
standard deviation stays under 1e-4 (measured 7.5e-7, full forward and through
the cache alike); the two wrong references have to read over 5e-2 (measured
0.42 for the per-head norm, 0.33 for renormalised gates).
"""

import asyncio
import dataclasses
import importlib.util
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.models import llama
from agentainer_tpu.models.configs import ModelConfig, get_config, list_configs
from agentainer_tpu.models.llama import KVCache, forward, init_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4  # see the module docstring
WRONG = 5e-2


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "olmoe_reference", os.path.join(REPO, "benchmark", "families", "olmoe_reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()
CFG = get_config("tiny-olmoe")


def sharp_params(cfg, seed=3):
    """Seeded float32 weights under which the block's own steps matter."""
    p = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    lay = dict(p["layers"])
    lay["wq"], lay["wk"] = lay["wq"] * 8.0, lay["wk"] * 8.0
    lay["router"] = lay["router"] * 10.0
    if cfg.qk_norm:
        lay["q_norm"] = jax.random.uniform(k1, lay["q_norm"].shape, jnp.float32, 0.25, 4.0)
        lay["k_norm"] = jax.random.uniform(k2, lay["k_norm"].shape, jnp.float32, 0.25, 4.0)
    lay["w_down"] = lay["w_down"] * 40.0
    return {**p, "layers": lay, "lm_head": p["lm_head"] * 10.0}


def reference_weights(params, cfg):
    layers = [{k: v[i] for k, v in params["layers"].items()} for i in range(cfg.n_layers)]
    return {"embed": params["embed"], "layers": layers, "final_norm": params["final_norm"], "lm_head": params["lm_head"]}


def reference_logits(params, cfg, tokens):
    return ref.forward(
        reference_weights(params, cfg), tokens, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.median(np.sqrt(np.mean((got - want) ** 2, -1)) / np.std(want, -1)))


@pytest.fixture(scope="module")
def case():
    params = sharp_params(CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (24,), 3, CFG.vocab_size)
    return params, tokens, reference_logits(params, CFG, tokens)


def program_full(params, tokens):
    pos = jnp.arange(tokens.shape[0])[None]
    return forward(params, CFG, tokens[None], pos, use_flash=False)[0][0]


def program_cached(params, tokens, n_prefill=20):
    """Prefill, then one-token decode steps through the KV arena."""
    cache = KVCache.create(CFG, 1, 32, dtype=jnp.float32)
    pos = jnp.arange(n_prefill)[None]
    logits, cache = forward(params, CFG, tokens[None, :n_prefill], pos, cache, use_flash=False)
    rows = [logits[0]]
    for i in range(n_prefill, tokens.shape[0]):
        step, cache = forward(params, CFG, tokens[None, i : i + 1], jnp.full((1, 1), i), cache, use_flash=False)
        rows.append(step[0])
    return jnp.concatenate(rows)


@pytest.mark.parametrize("program", [program_full, program_cached], ids=["full_forward", "prefill_then_decode"])
def test_program_matches_the_plain_reference(case, program):
    params, tokens, want = case
    assert rel(program(params, tokens), want) < TOL


def per_head_norm(x, w, eps):
    t = x.shape[0]
    hd = CFG.head_dim
    return (ref.rms_norm(x.reshape(t, -1, hd), 1.0, eps).reshape(t, -1)) * w


def renormalised_gates(logits, top_k):
    g, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return g / jnp.sum(g, axis=-1, keepdims=True), chosen


@pytest.mark.parametrize("name, wrong", [("qk_norm", per_head_norm), ("gates", renormalised_gates)],
                         ids=["per_head_norm", "renormalised_gates"])
@pytest.mark.parametrize("program", [program_full, program_cached], ids=["full_forward", "prefill_then_decode"])
def test_program_fails_a_reference_with_another_rule(case, program, name, wrong):
    """The check is not blind: the same program against a reference with a
    per-head QK-norm, or with the eight gates renormalised, is far off."""
    params, tokens, _ = case
    with mock.patch.object(ref, name, wrong):
        other = reference_logits(params, CFG, tokens)
    assert rel(program(params, tokens), other) > WRONG


@pytest.mark.parametrize("name", ["tiny", "tiny-moe"])
@pytest.mark.parametrize("part", ["full", "prefill", "decode"])
def test_llama_and_mixtral_outputs_are_the_parents_bit_for_bit(name, part):
    """``tests/data/forward_parent_pr25.npz``: logits of the parent commit
    (a08f071) on this CPU backend, seeded weights and tokens. The two new
    fields default to the old block, so not one bit may differ."""
    cfg = get_config(name)
    params = init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(11), (2, 12), 3, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
    if part == "full":
        got, _ = forward(params, cfg, toks, pos, use_flash=False)
    else:
        cache = KVCache.create(cfg, 2, 32, dtype=jnp.float32)
        got, cache = forward(params, cfg, toks[:, :11], pos[:, :11], cache, use_flash=False)
        if part == "decode":
            got, _ = forward(params, cfg, toks[:, 11:], pos[:, 11:], cache, use_flash=False)
    want = np.load(os.path.join(REPO, "tests", "data", "forward_parent_pr25.npz"))[f"{name}.{part}"]
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("name", sorted(set(list_configs()) - {"llama3-8b", "mixtral-8x7b"}))
def test_param_count_is_the_pytrees_size(name):
    cfg = get_config(name)
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(tree))
    if name == "olmoe-1b-7b":
        assert 6.9e9 < cfg.param_count() < 6.95e9 and cfg.qk_norm and not cfg.moe_renormalize


def gate_reference(logits, cfg):
    logits = np.asarray(logits, np.float64)
    k = cfg.experts_per_token
    if cfg.moe_renormalize:
        chosen = np.argsort(-logits, -1)[..., :k]
        top = np.take_along_axis(logits, chosen, -1)
        e = np.exp(top - top.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True), chosen
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    chosen = np.argsort(-p, -1)[..., :k]
    return np.take_along_axis(p, chosen, -1), chosen


def moe_reference(x, lp, cfg):
    x = np.asarray(x, np.float64)
    g, chosen = gate_reference(x @ np.asarray(lp["router"], np.float64), cfg)
    out = np.zeros_like(x)
    for b, t in np.ndindex(x.shape[:2]):
        for w, e in zip(g[b, t], chosen[b, t]):
            wg, wu, wd = (np.asarray(lp[n][e], np.float64) for n in ("w_gate", "w_up", "w_down"))
            h = x[b, t] @ wg
            out[b, t] += w * ((h / (1 + np.exp(-h)) * (x[b, t] @ wu)) @ wd)
    return out


def path_dense(x, lp, cfg):
    return llama._moe_mlp(x, lp, cfg)


def path_routed(x, lp, cfg):
    return llama._moe_mlp_routed(x, lp, cfg, capacity_factor=64.0)


def path_expert_parallel(x, lp, cfg):
    from agentainer_tpu.parallel.expert import moe_expert_parallel
    from agentainer_tpu.parallel.mesh import make_mesh

    return moe_expert_parallel(x, lp, cfg, make_mesh(ep=2))


@pytest.mark.parametrize("path", [path_dense, path_routed, path_expert_parallel], ids=["all_experts", "routed", "ep_shard_map"])
@pytest.mark.parametrize("name", ["tiny-moe", "tiny-olmoe"])
def test_every_moe_path_computes_the_configs_gate_rule(path, name):
    """One gate function behind the three MoE paths: each gives Mixtral's
    rule for ``tiny-moe`` and OLMoE's for ``tiny-olmoe``, and is seen to
    call ``moe_gates``."""
    cfg = get_config(name)
    lp = {k: v[0] for k, v in sharp_params(cfg)["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.dim), jnp.float32)
    calls = []
    real = llama.moe_gates

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    from agentainer_tpu.parallel import expert

    with mock.patch.object(llama, "moe_gates", counted), mock.patch.object(expert, "moe_gates", counted):
        got = path(x, lp, cfg)
    assert calls
    np.testing.assert_allclose(np.asarray(got), moe_reference(x, lp, cfg), rtol=2e-4, atol=2e-5)
    other = dataclasses.replace(cfg, moe_renormalize=not cfg.moe_renormalize)
    assert np.abs(np.asarray(got) - moe_reference(x, lp, other)).max() > 1e-2


def test_gates_of_olmoe_sum_to_under_one():
    logits = jax.random.normal(jax.random.PRNGKey(0), (7, CFG.n_experts)) * 3
    g, chosen = llama.moe_gates(logits, CFG, jnp.float32)
    want, want_chosen = gate_reference(logits, CFG)
    assert np.array_equal(np.asarray(chosen), want_chosen)
    np.testing.assert_allclose(np.asarray(g), want, rtol=1e-5)
    assert float(jnp.max(jnp.sum(g, -1))) < 1.0


def test_qk_norm_under_tp_reduces_across_the_shards(case):
    """A norm that spans heads under tensor parallelism: the query's columns
    are split over tp, and GSPMD has to reduce the mean square across the
    shards. The sharded forward equals the unsharded one and the plain
    reference; a norm computed per shard would be the two-halves norm below,
    which is far off."""
    from agentainer_tpu.parallel.mesh import make_mesh
    from agentainer_tpu.parallel.sharding import param_shardings_for

    params, tokens, want = case
    mesh = make_mesh(tp=2)
    sharded = jax.device_put(params, param_shardings_for(params, mesh, moe=True))
    assert "tp" in str(sharded["layers"]["wq"].sharding.spec)
    pos = jnp.arange(tokens.shape[0])[None]
    got = jax.jit(lambda p: forward(p, CFG, tokens[None], pos, use_flash=False)[0][0])(sharded)
    assert rel(got, want) < TOL

    def per_shard_norm(x, w, eps):
        t = x.shape[0]
        return ref.rms_norm(x.reshape(t, 2, -1), 1.0, eps).reshape(t, -1) * w

    with mock.patch.object(ref, "qk_norm", per_shard_norm):
        assert rel(got, reference_logits(params, CFG, tokens)) > WRONG


ENGINE = {"max_batch": 2, "max_seq": 128, "decode_chunk": 4}


def test_tp_engine_of_an_olmoe_model_matches_one_chip():
    from agentainer_tpu.engine.llm import LLMEngine

    async def go(e):
        return (await e.generate("the quick brown fox", max_tokens=6))["tokens"]

    one = LLMEngine.create("tiny-olmoe", options=dict(ENGINE))
    two = LLMEngine.create("tiny-olmoe", options={**ENGINE, "tp": 2})
    try:
        assert two.tp == 2 and asyncio.run(go(one)) == asyncio.run(go(two))
    finally:
        one.shutdown()
        two.shutdown()


def test_kill_and_resume_is_token_identical_on_an_olmoe_model():
    """The cache stores the normalised, rotated key, so a snapshot restored
    into a new engine continues exactly (speculation's rewind, prefix fork
    and tiering move the same rows)."""
    from agentainer_tpu.engine.llm import LLMEngine

    async def uninterrupted():
        eng = LLMEngine.create("tiny-olmoe", options=dict(ENGINE))
        a = await eng.chat("s", "turn one", max_tokens=5)
        b = await eng.chat("s", "turn two", max_tokens=5)
        eng.shutdown()
        return a["tokens"], b["tokens"]

    async def interrupted():
        eng1 = LLMEngine.create("tiny-olmoe", options=dict(ENGINE))
        a = await eng1.chat("s", "turn one", max_tokens=5)
        blob = await eng1.snapshot_session("s")
        eng1.shutdown()  # the crash
        eng2 = LLMEngine.create("tiny-olmoe", options=dict(ENGINE))
        assert await eng2.restore_session("s", blob) is True
        b = await eng2.chat("s", "turn two", max_tokens=5)
        eng2.shutdown()
        return a["tokens"], b["tokens"]

    assert asyncio.run(uninterrupted()) == asyncio.run(interrupted())


def test_registered_olmoe_is_the_published_model():
    assert get_config("olmoe-1b-7b") == ModelConfig(
        name="olmoe-1b-7b", vocab_size=50304, dim=2048, n_layers=16, n_heads=16, n_kv_heads=16, ffn_dim=1024,
        max_seq_len=4096, rope_theta=10000.0, norm_eps=1e-5, n_experts=64, experts_per_token=8,
        moe_renormalize=False, qk_norm=True)
