"""Solar-Open2's block through ``models/hybrid.py`` (KDA linear attention with
``β = 2 · sigmoid`` beside NoPE grouped-query softmax attention with a gate as
wide as its output, every layer sigmoid-routed experts with a shared one)
against the benchmark's plain float32 reference
(``benchmark/families/solar_open2_reference.py``, which imports nothing of the
program), on the CPU with ``tiny-solar-open2`` and seeded weights; the plan
chosen a kind at a time; and the cache manager's moves on K/V rows and a
recurrent state side by side.

Weights are NOT the 0.02-std init: at a hidden size of 64 that makes every
gate near ½, every router score near ½ and attention a near-uniform average,
and a check is then blind to the very steps this block adds. Here the
projections are scaled until each step moves the logits by several per cent
at least (``test_program_fails_a_reference_that_omits``).

Tolerances, each with its reason. ``TOL`` 1e-3: both sides compute in float32
and differ by the order of summation and the chunked against the
token-by-token recurrence; under the sharpened weights float32 itself is
worth 1e-4 (the sibling block's reading, ``tests/test_kimi_linear.py``), so
the median over positions of the rms difference over the logits' standard
deviation stays under 1e-3, and every omission has to read over ``WRONG``
2e-2. The int8 weight-only case takes the SAME 1e-3: on the CPU the program
multiplies the stored int8 values by their float32 scales exactly, and the
reference is given those dequantised weights, so quantisation itself is in
both sides and only the order of summation differs. The ops' own cases
(``β`` to 2) take the bounds ``tests/test_kimi_linear.py`` holds the same
three forms to at ``β ≤ 1``: 1e-4 on outputs, 1e-5 on the state, 1e-3 / 1e-4
for the kernel in interpret mode.
"""

import asyncio
import dataclasses
import importlib.util
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.models import hybrid, llama
from agentainer_tpu.models.configs import get_config, list_configs
from agentainer_tpu.models.llama import forward, init_cache, init_params
from agentainer_tpu.ops import kda
from agentainer_tpu.ops.moe import stacked_experts
from agentainer_tpu.ops.quant import QTensor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-3  # see the module docstring
WRONG = 2e-2
CFG = get_config("tiny-solar-open2")
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "solar_open2_reference", os.path.join(REPO, "benchmark", "families", "solar_open2_reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()


def sharp_params(cfg, seed=3):
    """Seeded float32 weights under which the block's own steps matter."""
    p = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    scale = {
        "kda": {"wqkv": 20.0, "w_fa": 30.0, "w_fb": 30.0, "w_beta": 60.0, "w_ga": 40.0, "w_gb": 40.0, "wo": 10.0},
        "full": {"wq": 40.0, "wk": 40.0, "wv": 10.0, "wg": 60.0, "wo": 10.0},
        "moe": {"router": 60.0, "w_down": 40.0, "ws_down": 40.0},
    }
    out = dict(p)
    for group, factors in scale.items():
        out[group] = {k: v * factors.get(k, 1.0) for k, v in p[group].items()}
    out["kda"]["o_norm"] = jax.random.uniform(next(keys), p["kda"]["o_norm"].shape, jnp.float32, 0.25, 4.0)
    out["moe"]["router_bias"] = jax.random.normal(next(keys), p["moe"]["router_bias"].shape, jnp.float32) * 0.3
    out["lm_head"] = p["lm_head"] * 10.0
    return out


def dense(x):
    return x.q.astype(jnp.float32) * x.scale.astype(jnp.float32) if isinstance(x, QTensor) else x


def reference_weights(params, cfg):
    """The program's per-kind stacks as the reference's list of layers (the
    merged q|k|v projection and conv filters of a KDA layer split into the
    published three); int8 leaves as the float32 values they stand for."""
    layer_of = lambda group, i: {k: dense(jax.tree.map(lambda a: a[i], v)) for k, v in params[group].items()}  # noqa: E731
    layers, seen = [], {"kda": 0, "full": 0}
    for i, kind in enumerate(cfg.layer_kinds):
        lp = layer_of("layers", i)
        mixer = layer_of(kind, seen[kind])
        seen[kind] += 1
        if kind == "kda":
            for name, part in zip("qkv", jnp.split(mixer.pop("wqkv"), 3, axis=-1)):
                lp["w" + name] = part
            for name, part in zip("qkv", jnp.split(mixer.pop("conv"), 3, axis=-1)):
                lp["conv_" + name] = part
        lp.update(mixer)
        lp.update(layer_of("moe", i))
        layers.append(lp)
    return {"embed": dense(params["embed"]), "layers": layers, "final_norm": params["final_norm"],
            "lm_head": dense(params["lm_head"])}


def reference_kw(cfg):
    return dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, kda_heads=cfg.kda_heads,
        kda_head_dim=cfg.kda_head_dim, norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token,
        routed_scale=cfg.moe_scale, renormalize=cfg.moe_renormalize, neg_eigval=cfg.delta_neg_eigval,
        expert_offset=cfg.expert_offset,
    )


def reference_logits(params, cfg, tokens):
    return ref.forward(reference_weights(params, cfg), tokens, **reference_kw(cfg))


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.median(np.sqrt(np.mean((got - want) ** 2, -1)) / np.std(want, -1)))


@pytest.fixture(scope="module")
def case():
    params = sharp_params(CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (150,), 3, CFG.vocab_size)
    return params, tokens, reference_logits(params, CFG, tokens)


def program_full(params, tokens, cfg=CFG):
    pos = jnp.arange(tokens.shape[0])[None]
    return forward(params, cfg, tokens[None], pos)[0][0]


def program_cached(params, tokens, chunks=(70, 66), buckets=(70, 96), cfg=CFG):
    """Prefill in two chunks, then one-token decode steps through the cache.
    The first chunk ends at token 70: inside the second KDA chunk of 64 and
    inside a conv window of 4, so both are carried from one launch to the
    next; the second sits in a bucket with padding rows."""
    cache = init_cache(cfg, 1, 192, dtype=jnp.float32)
    rows, at = [], 0
    for n, bucket in zip(chunks, buckets):
        toks = jnp.pad(tokens[at : at + n], (0, bucket - n))[None]
        pos = (at + jnp.arange(bucket))[None]
        logits, cache = forward(params, cfg, toks, pos, cache, valid=(jnp.arange(bucket) < n)[None])
        rows.append(logits[0, :n])
        at += n
    for i in range(at, tokens.shape[0]):
        step, cache = forward(params, cfg, tokens[None, i : i + 1], jnp.full((1, 1), i), cache)
        rows.append(step[0])
    return jnp.concatenate(rows)


def program_cached_odd(params, tokens):
    """Three ragged chunks: every boundary splits a conv window and a KDA chunk."""
    return program_cached(params, tokens, chunks=(37, 61, 29), buckets=(37, 64, 32))


@pytest.mark.parametrize(
    "program", [program_full, program_cached, program_cached_odd],
    ids=["full_forward", "two_chunks_then_decode", "three_ragged_chunks_then_decode"])
def test_program_matches_the_plain_reference(case, program):
    params, tokens, want = case
    assert rel(program(params, tokens), want) < TOL


def test_beta_passes_one_in_the_model_under_test(case):
    """The case is the one the config's switch is for: in the tiny model's
    first KDA layer ``β`` passes 1 for a good share of (token, head) pairs."""
    params, tokens, _ = case
    w = reference_weights(params, CFG)
    lp = w["layers"][1]
    x = ref.rms_norm(w["embed"][tokens], lp["attn_norm"], CFG.norm_eps)  # the layer's input is not this; any stream does
    beta = np.asarray(ref.beta_of(x, lp, lambda a: a, True))
    assert beta.max() <= 2.0 and (beta > 1.0).mean() > 0.3 and (beta < 1.0).mean() > 0.1


def softmax_router(logits, bias, top_k, scale, renormalize):
    top, chosen = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(top, axis=-1), chosen


def bias_in_the_weights(logits, bias, top_k, scale, renormalize):
    s = jax.nn.sigmoid(logits) + bias
    w, chosen = jax.lax.top_k(s, top_k)
    return w / jnp.sum(w, axis=-1, keepdims=True) * scale, chosen


def rotary(q, k, positions, theta=10_000.0):
    def rope(x):  # [T, n, hd]; rotate-half
        r = x.shape[-1]
        inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
        ang = (positions.astype(jnp.float32)[:, None] * inv)[:, None, :]
        x1, x2 = x[..., : r // 2], x[..., r // 2 :]
        return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)

    return rope(q), rope(k)


def gate_a_head(x, lp, act):
    """The other published form of the gate: one sigmoid a head (its first column)."""
    g = jax.nn.sigmoid(act(x) @ lp["wg"]).reshape(x.shape[0], CFG.n_heads, CFG.head_dim)
    return jnp.broadcast_to(g[..., :1], g.shape).reshape(x.shape[0], -1)


OMISSIONS = {
    "no_conv": ("short_conv", lambda x, w: x),
    "no_decay_gate": ("log_decay", lambda x, lp, heads, dk, act: jnp.zeros((x.shape[0], heads, dk), jnp.float32)),
    "beta_at_most_one": ("beta_of", lambda x, lp, act, neg=True: jax.nn.sigmoid(act(x) @ lp["w_beta"])),
    "no_l2norm": ("l2norm", lambda x: x),
    "no_output_gate": ("output_gate", lambda x, lp, heads, dk, act: jnp.ones((x.shape[0], heads, dk), jnp.float32)),
    "no_attn_gate": ("attn_gate", lambda x, lp, act: jnp.ones((x.shape[0], lp["wg"].shape[-1]), jnp.float32)),
    "gate_a_head": ("attn_gate", gate_a_head),
    "rotary_in_gqa": ("position_embed", rotary),
    "no_shared_expert": ("shared_expert", lambda x, lp, act: jnp.zeros_like(x)),
    "bias_in_the_weights": ("gates", bias_in_the_weights),
    "softmax_router": ("gates", softmax_router),
}


@pytest.mark.parametrize("name", sorted(OMISSIONS))
def test_program_fails_a_reference_that_omits(case, name):
    """The check is not blind: against a reference with one step of the
    block left out or done another way, the same program is far off."""
    params, tokens, _ = case
    attr, wrong = OMISSIONS[name]
    with mock.patch.object(ref, attr, wrong):
        other = reference_logits(params, CFG, tokens)
    assert rel(program_full(params, tokens), other) > WRONG


def test_a_model_that_allows_no_negative_eigenvalue_keeps_beta_under_one(case):
    """``cfg.delta_neg_eigval`` is what doubles ``β`` in ``kda_mixer``: the same
    weights without it are the reference's ``neg_eigval=False``, and far from
    the model's own logits."""
    params, tokens, want = case
    plain = dataclasses.replace(CFG, delta_neg_eigval=False)
    got = program_full(params, tokens, cfg=plain)
    other = ref.forward(reference_weights(params, CFG), tokens, **{**reference_kw(CFG), "neg_eigval": False})
    assert rel(got, other) < TOL and rel(got, want) > WRONG


# -- β to 2 through the rule's three forms -------------------------------------------


def kda_inputs(seed=0, b=2, t=150, h=3, dk=16):
    """As ``tests/test_kimi_linear.kda_inputs`` with ``β`` drawn over (0.4, 2)."""
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(b, t, h, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    raw = (rng.normal(size=(b, t, h, dk)), k, rng.normal(size=(b, t, h, dk)),
           np.log(rng.uniform(0.5, 0.999, size=(b, t, h, dk))), rng.uniform(0.4, 2.0, size=(b, t, h)),
           rng.normal(size=(b, h, dk, dk)))
    return [jnp.asarray(x, jnp.float32) for x in raw]


def test_beta_past_one_through_the_recurrent_the_chunked_and_the_step_forms():
    """``β > 1`` in most (token, head) pairs: the erase term ``I − β k kᵀ``
    then flips the state's component along ``k``. The chunked form (two whole
    chunks and a ragged third), the step form token by token, and a masked
    tail all give the recurrence."""
    q, k, v, g, beta, s0 = kda_inputs()
    assert float((beta > 1.0).mean()) > 0.55 and float(beta.max()) > 1.9
    o_ref, s_ref = kda.kda_recurrent(q, k, v, g, beta, s0)
    # the oracle itself, written out: S ← diag(a) S; S ← S + β k (v − kᵀ S)ᵀ
    s = np.asarray(s0[0, 0], np.float64)
    for t in range(3):
        kt, vt = np.asarray(k[0, t, 0], np.float64), np.asarray(v[0, t, 0], np.float64)
        s = s * np.exp(np.asarray(g[0, t, 0], np.float64))[:, None]
        s = s + float(beta[0, t, 0]) * np.outer(kt, vt - kt @ s)
    first = kda.kda_recurrent(q[:, :3], k[:, :3], v[:, :3], g[:, :3], beta[:, :3], s0)[1]
    np.testing.assert_allclose(np.asarray(first[0, 0]), s, atol=1e-5)
    o, s = kda.kda_chunked(q, k, v, g, beta, s0)
    assert float(jnp.abs(o - o_ref).max()) < 1e-4 and float(jnp.abs(s - s_ref).max()) < 1e-5
    state, outs = s0, []
    for t in range(20):
        o_t, state = kda.kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], state)
        outs.append(o_t)
    assert float(jnp.abs(jnp.stack(outs, 1) - o_ref[:, :20]).max()) < 1e-4
    pad = lambda x: jnp.pad(x, [(0, 0), (0, 40)] + [(0, 0)] * (x.ndim - 2), constant_values=1.5)  # noqa: E731
    gp, bp = kda.mask_inputs(pad(g), pad(beta), jnp.broadcast_to(jnp.arange(190) < 150, (2, 190)))
    o2, s2 = kda.kda_chunked(pad(q), pad(k), pad(v), gp, bp, s0)
    assert float(jnp.abs(o2[:, :150] - o_ref).max()) < 1e-4 and float(jnp.abs(s2 - s_ref).max()) < 1e-5


def test_the_decode_kernel_takes_beta_past_one_at_64_heads():
    """Interpret mode, the model's head count (64 heads of 128: eight head
    blocks a lane): ``kda_decode`` against ``kda_step`` with ``β`` over (0.4,
    2), one lane masked, the other layer of the stack untouched."""
    from agentainer_tpu.ops.pallas_kda import kda_decode

    rng = np.random.default_rng(0)
    b, h, dk = 2, 64, 128
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, dk)), jnp.float32) for _ in range(3))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = jnp.asarray(np.log(rng.uniform(0.5, 0.999, size=(b, h, dk))), jnp.float32).at[1].set(0.0)
    beta = jnp.asarray(rng.uniform(0.4, 2.0, size=(b, h)), jnp.float32).at[1].set(0.0)
    assert float((beta[0] > 1.0).mean()) > 0.55
    stack = jnp.asarray(rng.normal(size=(2, b, h, dk, dk)), jnp.float32)
    o_want, s_want = kda.kda_step(q, k, v, g, beta, stack[1])
    o, out = kda_decode(q, k, v, g, beta, stack, 1, interpret=True)
    assert float(jnp.abs(o - o_want).max()) < 1e-3 and float(jnp.abs(out[1] - s_want).max()) < 1e-4
    assert np.array_equal(np.asarray(out[0]), np.asarray(stack[0]))  # another layer
    assert np.array_equal(np.asarray(out[1, 1]), np.asarray(stack[1, 1]))  # the masked lane


# -- the gate, the router, the share ---------------------------------------------------


def test_the_full_width_gate_and_the_gate_a_head_are_one_helper():
    """``hybrid._gated`` by the width of ``wg``: a column a head multiplies
    the head whole, a column a channel multiplies channel by channel; and
    ``param_shapes`` / ``gate_width`` size the leaf by ``cfg.attn_gate``."""
    rng = np.random.default_rng(0)
    o = jnp.asarray(rng.normal(size=(2, 5, 8, 16)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(2, 5, 64)), jnp.float32)
    w_head = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    w_full = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    got = hybrid._gated(o, h, w_head, per_head=True)
    np.testing.assert_allclose(got, (o * jax.nn.sigmoid(h @ w_head)[..., None]).reshape(2, 5, 128), rtol=1e-5, atol=1e-6)
    got = hybrid._gated(o, h, w_full, per_head=False)
    np.testing.assert_allclose(got, o.reshape(2, 5, 128) * jax.nn.sigmoid(h @ w_full), rtol=1e-5, atol=1e-6)
    assert CFG.gate_form == "full" and CFG.gate_width(8) == 128
    assert hybrid.param_shapes(CFG)["full"]["wg"] == ((3, 64, 128), True)
    laguna = get_config("tiny-laguna")
    assert laguna.gate_form == "per_head" and hybrid.param_shapes(laguna)["full"]["wg"][0][-1] == laguna.n_heads
    assert get_config("tiny-olmo-hybrid").gate_form == "none" and "wg" not in hybrid.param_shapes(get_config("tiny-olmo-hybrid"))["full"]
    with pytest.raises(ValueError, match="attn_gate"):
        dataclasses.replace(CFG, attn_gate="wide")


def test_the_sigmoid_router_rule_is_the_references():
    logits = jax.random.normal(jax.random.PRNGKey(0), (40, CFG.n_experts)) * 3.0
    bias = jax.random.normal(jax.random.PRNGKey(1), (CFG.n_experts,)) * 0.5
    g, chosen = llama.moe_gates(logits, CFG, jnp.float32, bias)
    g_ref, chosen_ref = ref.gates(logits, bias, CFG.experts_per_token, CFG.moe_scale, True)
    assert np.array_equal(np.asarray(chosen), np.asarray(chosen_ref))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g.sum(-1)), 1.0, rtol=1e-5)  # ``routed_scaling_factor`` 1


@pytest.mark.parametrize("path", ["einsum", "sorted"])
def test_the_eight_shares_add_up_to_the_uncut_layer(path):
    """Expert parallelism without the exchange, ``ep = 8`` as the benchmark's
    configuration states it: chips 0-7 hold one of the tiny model's 8 experts
    each (40 of 320 at the published size), every chip routes over all 8 and
    computes its own expert's terms. The routed parts of all eight shares plus
    the shared expert ONCE equal the uncut reference layer, through both of
    the program's paths; and one share alone is the reference's share."""
    params = sharp_params(CFG)
    lp = {k: v[0] for k, v in params["moe"].items()}
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 40, CFG.dim), jnp.float32)
    want = ref.moe(h[0], lp, CFG.experts_per_token, CFG.moe_scale, True, 0, lambda x: x)
    shared = llama._mlp(h, {"w" + k[2:]: v for k, v in lp.items() if k.startswith("ws_")})[0]
    held = CFG.n_experts // 8
    total = shared
    for chip in range(8):
        share = dataclasses.replace(CFG, experts_held=held, expert_offset=held * chip)
        mine = {k: (v[held * chip : held * (chip + 1)] if k in EXPERT_WEIGHTS else v) for k, v in lp.items()}
        if path == "einsum":
            part = llama._moe_mlp(h, mine, share)[0]
        else:
            experts = stacked_experts({k: v[None] for k, v in mine.items()})
            part = llama._moe_mlp_sorted(h, mine, share, experts, jnp.int32(0))[0]
        total = total + part
        if chip == 5:  # a share alone: the same held range, nothing standing in for the rest
            alone = ref.moe(h[0], mine, CFG.experts_per_token, CFG.moe_scale, True, held * chip, lambda x: x)
            assert rel(part + shared, alone) < TOL
    assert rel(total, want) < TOL


def test_a_held_share_through_the_whole_forward_is_the_references_share(case):
    """The model as the benchmark cuts it: chip 2 of ``ep = 4`` holds experts
    4-5 of 8 in every layer, the router keeps its 8 outputs, and the whole
    forward equals the reference given the same two experts."""
    params, tokens, want = case
    share = dataclasses.replace(CFG, experts_held=2, expert_offset=4)
    mine = dict(params, moe={k: (v[:, 4:6] if k in EXPERT_WEIGHTS else v) for k, v in params["moe"].items()})
    got = program_full(mine, tokens[:60], cfg=share)
    other = ref.forward(reference_weights(mine, share), tokens[:60], **reference_kw(share))
    assert rel(got, other) < TOL and rel(got, want[:60]) > WRONG


def test_int8_weight_only_is_the_reference_on_the_dequantised_weights(case):
    """The serving mode: every matrix int8 with a scale a column (the
    vectors, and the gate ``wg``, which ``quantize_params`` leaves, stay
    dense). 150 rows are over the MoE cut, so the prefill takes the sorted
    FFN over the int8 stack and the steps the all-experts einsum. Tolerance:
    the module docstring's (the same 1e-3)."""
    from agentainer_tpu.engine.quant import quantize_params

    params, tokens, want = case
    q = quantize_params(jax.device_get(params), jnp.float32)
    assert isinstance(q["kda"]["wqkv"], QTensor) and isinstance(q["full"]["wq"], QTensor) and isinstance(q["moe"]["w_up"], QTensor)
    assert not isinstance(q["kda"]["a_log"], QTensor) and not isinstance(q["moe"]["router_bias"], QTensor)
    other = reference_logits(q, CFG, tokens)
    assert rel(program_full(q, tokens), other) < TOL
    assert rel(program_cached(q, tokens), other) < TOL
    assert rel(other, want) > 1e-3  # quantisation is a real change of the weights: both sides made it


def test_the_synthetic_int8_generator_draws_every_leaf_and_serves():
    from agentainer_tpu.engine.quant import synthetic_quantized_params

    params = synthetic_quantized_params(CFG, jnp.float32)
    assert isinstance(params["full"]["wg"], QTensor) and params["full"]["wg"].q.shape == (3, 64, 128)
    assert isinstance(params["kda"]["w_beta"], QTensor) and params["kda"]["conv"].dtype == jnp.float32
    logits = forward(params, CFG, jnp.arange(3, 43)[None], jnp.arange(40)[None])[0]
    assert bool(jnp.isfinite(logits).all())


# -- the mixed step ----------------------------------------------------------------


def test_a_chunk_with_the_lanes_step_beside_it_is_the_two_launches(case):
    """Lanes 0 and 1 decode, lane 2 takes a chunk: one launch with ``lanes=``
    leaves the logits and every leaf (K and V rows, state, conv) that the
    chunk's launch and then the lanes' step leave. Tolerance: the two differ
    by the matmuls' row count alone (``T + B`` rows at once against ``T`` and
    ``B``), which the sharpened weights carry through nine layers: the
    logits within ``TOL``, the leaves to 1e-3 of their scale."""
    params, tokens, _ = case
    cache = init_cache(CFG, 3, 192, dtype=jnp.float32, live=False)
    at = {0: 50, 1: 90, 2: 70}
    for lane, n in at.items():
        cache = hybrid.admit_lane(cache, lane, True, 0 if lane == 2 else hybrid.NO_STOP, -1)
        _, cache = forward(params, CFG, tokens[None, :n] + lane, jnp.arange(n)[None], cache, slot=lane)
    chunk = tokens[None, 70:107]
    pos = (70 + jnp.arange(37))[None]
    lane_tok = jnp.asarray([[5], [9], [3]], jnp.int32)
    lane_pos = jnp.asarray([[50], [90], [191]], jnp.int32)  # the chunk's own lane parked at the arena's last row
    lg_c, two = forward(params, CFG, chunk, pos, cache, slot=2)
    lg_l, two = forward(params, CFG, lane_tok, lane_pos, two)
    lg_m, one = forward(params, CFG, chunk, pos, cache, slot=2, lanes=(lane_tok, lane_pos), last=jnp.int32(36))
    assert rel(lg_m[:1], lg_c[:, 36]) < TOL and rel(lg_m[1:3], lg_l[:2, 0]) < TOL
    close = lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-3, atol=1e-3 * float(jnp.abs(y).max()))  # noqa: E731
    for name in ("k", "v"):
        for lane, n in ((0, 51), (1, 91), (2, 107)):
            close(getattr(one, name)[:, lane, :n], getattr(two, name)[:, lane, :n])
    close(one.state, two.state)
    close(one.conv, two.conv)
    assert not np.array_equal(np.asarray(one.state[:, 0]), np.asarray(cache.state[:, 0]))  # the lanes did step


# -- the cache: four leaves side by side ---------------------------------------------


def test_snapshot_and_restore_round_trip_every_leaf_into_another_lane(case):
    """``snapshot_lane`` → ``restore_lane`` over ``k``, ``v``, ``state`` and
    ``conv``: lane 0's 90 tokens restored into lane 1 of a fresh cache go on
    to the reference's logits; the K/V rows ship up to the bucket with the
    model's two K/V heads, the state and conv whole."""
    params, tokens, want = case
    cache = init_cache(CFG, 2, 192, dtype=jnp.float32)
    _, cache = forward(params, CFG, tokens[None, :90], jnp.arange(90)[None], cache, slot=0)
    leaves = hybrid.snapshot_lane(cache, 0, 96, CFG.n_kv_heads)
    assert list(leaves) == ["k", "v", "state", "conv"]
    assert leaves["k"].shape == (3, 96, 2, 16) and leaves["state"].shape == (6, 4, 16, 16) and leaves["conv"].shape == (6, 3 * 192)
    other = hybrid.restore_lane(init_cache(CFG, 2, 192, dtype=jnp.float32), 1, leaves)
    assert int(other.stop[1]) == 0 and not np.asarray(other.state[:, 0]).any()
    for name in ("state", "conv"):
        assert np.array_equal(np.asarray(getattr(other, name)[:, 1]), np.asarray(getattr(cache, name)[:, 0]))
    other = hybrid.admit_lane(other, 1, False, hybrid.NO_STOP, -1)
    rows = []
    for i in range(90, 110):
        step, other = forward(params, CFG, tokens[None, i : i + 1], jnp.full((1, 1), i), other, slot=1)
        rows.append(step[0])
    assert rel(jnp.concatenate(rows), want[90:110]) < TOL


def test_a_parked_lanes_leaves_are_untouched_by_the_lane_beside_it(case):
    """Lane 0 holds 60 tokens and is closed (``stop = 0``); lane 1 then steps
    40 times, every launch carrying lane 0's row at the arena's last position.
    Lane 0's state, conv and live K/V rows are bit-identical, and opened again
    it goes on to the reference's logits."""
    params, tokens, want = case
    cache = init_cache(CFG, 2, 192, dtype=jnp.float32, live=False)
    cache = hybrid.admit_lane(cache, 0, True, hybrid.NO_STOP, -1)
    _, cache = forward(params, CFG, tokens[None, :60], jnp.arange(60)[None], cache, slot=0)
    cache = cache._replace(stop=cache.stop.at[0].set(0))
    cache = hybrid.admit_lane(cache, 1, True, hybrid.NO_STOP, -1)
    held = lambda c: [np.asarray(c.state[:, 0]), np.asarray(c.conv[:, 0]), np.asarray(c.k[:, 0, :60]), np.asarray(c.v[:, 0, :60])]  # noqa: E731
    before = held(cache)
    for i in range(40):
        tok = jnp.asarray([[7], [int(tokens[i])]], jnp.int32)
        _, cache = forward(params, CFG, tok, jnp.asarray([[191], [i]], jnp.int32), cache)
    for x, y in zip(before, held(cache)):
        assert np.array_equal(x, y)
    assert np.asarray(cache.state[:, 1]).any()
    cache = hybrid.admit_lane(cache, 0, False, hybrid.NO_STOP, -1)
    rows = []
    for i in range(60, 72):  # a dozen rows: ``rel`` is a median over positions
        step, cache = forward(params, CFG, jnp.asarray([[int(tokens[i])], [3]], jnp.int32), jnp.asarray([[i], [i - 20]], jnp.int32), cache)
        rows.append(step[0])
    assert rel(jnp.concatenate(rows), want[60:72]) < TOL


# -- configuration, plan, parameters ---------------------------------------------------

# ``plan_hybrid`` of every registered hybrid configuration as the parent commit
# (47ce36f, where the plan was chosen by pair of kinds) gave it, written out:
# (kinds -> (prefill, decode)), the reason
XLA_ATTN = ("xla:attention_reference", "xla:attention_reference")
FLASH = ("pallas:flash_prefill", "pallas:flash_decode")
PARENT_PLANS = {
    ("kimi-linear-48b", True): ({"kda": ("xla_chunked", "pallas_kda_decode"), "mla": ("pallas_mla_prefill", "pallas_mla_decode")},
                                "tpu backend; state and latent stacks read where they lie"),
    ("kimi-linear-48b", False): ({"kda": ("xla_chunked", "xla_step"), "mla": ("xla_absorbed", "xla_absorbed")}, "no tpu backend"),
    ("tiny-kimi-linear", True): ({"kda": ("xla_chunked", "xla_step"), "mla": ("xla_absorbed", "xla_absorbed")},
                                 "KDA heads not (8, 128)-aligned"),
    ("tiny-kimi-linear", False): ({"kda": ("xla_chunked", "xla_step"), "mla": ("xla_absorbed", "xla_absorbed")}, "no tpu backend"),
    ("mistral-small-4-119b", True): ({"mla": ("pallas_mla_prefill", "pallas_mla_decode")}, "tpu backend; the latent stack read where it lies"),
    ("mistral-small-4-119b", False): ({"mla": ("xla_absorbed", "xla_absorbed")}, "no tpu backend"),
    ("tiny-mistral4", True): ({"mla": ("pallas_mla_prefill", "pallas_mla_decode")}, "tpu backend; the latent stack read where it lies"),
    ("tiny-mistral4", False): ({"mla": ("xla_absorbed", "xla_absorbed")}, "no tpu backend"),
    ("olmo-hybrid-7b", True): ({"gdn": ("xla_chunked", "pallas_gdn_decode"), "full": FLASH},
                               "tpu backend; state and K/V stacks read where they lie"),
    ("olmo-hybrid-7b", False): ({"gdn": ("xla_chunked", "xla_step"), "full": XLA_ATTN}, "no tpu backend"),
    ("tiny-olmo-hybrid", True): ({"gdn": ("xla_chunked", "xla_step"), "full": XLA_ATTN},
                                 "GDN state tile [12, 6x24] is not whole (8, 128) tiles; heads [6]/8 stored x 16: not the flash kernels' shapes"),
    ("tiny-olmo-hybrid", False): ({"gdn": ("xla_chunked", "xla_step"), "full": XLA_ATTN}, "no tpu backend"),
    ("laguna-xs.2", True): ({"full": FLASH, "swa": FLASH}, "tpu backend; state and K/V stacks read where they lie"),
    ("laguna-xs.2", False): ({"full": XLA_ATTN, "swa": XLA_ATTN}, "no tpu backend"),
    ("tiny-laguna", True): ({"full": XLA_ATTN, "swa": XLA_ATTN}, "heads [6, 8]/2 stored x 16: not the flash kernels' shapes"),
    ("tiny-laguna", False): ({"full": XLA_ATTN, "swa": XLA_ATTN}, "no tpu backend"),
    ("minicpm-sala", True): ({"lightning": ("xla_chunked", "xla_step"), "sparse": ("xla:block_mask", "pallas:flash_decode+pallas:sparse_decode")},
                             "tpu backend; a lane's K/V rows read where they lie, a dense row's and a sparse row's listed blocks"),
    ("minicpm-sala", False): ({"lightning": ("xla_chunked", "xla_step"), "sparse": ("xla:block_mask", "xla:attention_reference+xla:block_gather")},
                              "no tpu backend"),
    ("tiny-minicpm-sala", True): ({"lightning": ("xla_chunked", "xla_step"), "sparse": ("xla:block_mask", "xla:attention_reference+xla:block_gather")},
                                  "heads 8/2 x 16: not the flash kernels' shapes"),
    ("tiny-minicpm-sala", False): ({"lightning": ("xla_chunked", "xla_step"), "sparse": ("xla:block_mask", "xla:attention_reference+xla:block_gather")},
                                   "no tpu backend"),
}


@pytest.mark.parametrize("name, on_tpu", sorted(PARENT_PLANS), ids=lambda x: str(x))
def test_every_standing_configurations_plan_is_the_parents(name, on_tpu):
    kinds, reason = PARENT_PLANS[name, on_tpu]
    plan = hybrid.plan_hybrid(get_config(name), use_pallas=on_tpu)
    assert plan.kinds() == kinds and plan.reason == reason
    # and nothing is named for a kind the model has no layer of
    named = {f for f, v in plan._asdict().items() if v and f not in ("reason", "mla_rotary")}
    assert named == {f"{k}_{form}" for k in kinds for form in ("prefill", "decode")}
    assert bool(plan.mla_rotary) == bool(get_config(name).mla_rotary)


def test_the_table_covers_every_registered_hybrid_configuration_but_this_familys():
    hybrids = {n for n in list_configs() if get_config(n).is_hybrid}
    assert hybrids - {n for n, _ in PARENT_PLANS} == {"solar-open2", "tiny-solar-open2"}


def test_the_plan_answers_a_kind_at_a_time():
    """KDA beside "full" (a pair no planner by pair knew): each kind has its
    kernel and its rule on a TPU, and its own reason where it has none."""
    big = hybrid.plan_hybrid(get_config("solar-open2"), use_pallas=True)
    assert big.kinds() == {"kda": ("xla_chunked", "pallas_kda_decode"), "full": FLASH}
    assert big.reason == "tpu backend; state and K/V stacks read where they lie" and big.describe()["arena"] == "stack+layer"
    assert big.describe()["prefill"] == "pallas:flash_prefill" and big.describe()["decode"] == "pallas:flash_decode"
    cpu = hybrid.plan_hybrid(get_config("solar-open2"), use_pallas=False)
    assert cpu.kinds() == {"kda": ("xla_chunked", "xla_step"), "full": XLA_ATTN} and cpu.reason == "no tpu backend"
    tiny = hybrid.plan_hybrid(CFG, use_pallas=True)
    assert tiny.kinds() == {"kda": ("xla_chunked", "xla_step"), "full": XLA_ATTN}
    assert "KDA heads" in tiny.reason and "flash kernels' shapes" in tiny.reason
    # any pair the block can hold: one kind's answer does not depend on the other's
    mixed = dataclasses.replace(get_config("olmo-hybrid-7b"), layer_kinds=("gdn", "mla"), mla_kv_rank=512, mla_nope_dim=128,
                                mla_rope_dim=64, mla_v_dim=128)
    assert hybrid.plan_hybrid(mixed, use_pallas=True).kinds() == {
        "gdn": ("xla_chunked", "pallas_gdn_decode"), "mla": ("pallas_mla_prefill", "pallas_mla_decode")}
    alone = dataclasses.replace(get_config("solar-open2"), layer_kinds=("kda",) * 4, n_layers=4)
    assert hybrid.plan_hybrid(alone, use_pallas=True).kinds() == {"kda": ("xla_chunked", "pallas_kda_decode")}


def test_param_count_is_the_pytrees_size_and_the_published_models():
    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    assert CFG.param_count() == sum(x.size for x in jax.tree.leaves(params))
    big = get_config("solar-open2")
    assert abs(big.param_count() / 250.29e9 - 1.0) < 1e-4  # the model's name
    assert abs(big.active_param_count() / 14.7e9 - 1.0) < 0.01  # "A15B"
    assert big.layer_kinds.count("kda") == 36 and [i for i, k in enumerate(big.layer_kinds) if k == "full"] == list(range(0, 48, 4))
    counts = big._hybrid_counts()
    assert round(counts["kda"] / 1e6, 1) == 137.7 and round(counts["full"] / 1e6, 1) == 109.1 and round(counts["expert"] / 1e6, 2) == 15.73
    a_head = dataclasses.replace(big, attn_gate=True)
    assert abs(a_head.param_count() / 249.9e9 - 1.0) < 1e-3  # the other form of the gate: the name does not decide
    cut = dataclasses.replace(big, n_layers=8, layer_kinds=big.layer_kinds[:8], experts_held=40)
    assert abs(cut.param_count() / 7.82e9 - 1.0) < 1e-3  # chip 0's share of two periods
    assert cut.flops_per_token(1300) > 2.0 * cut.active_param_count()
    cache = jax.eval_shape(lambda: init_cache(cut, 64, 4096, jnp.bfloat16))
    assert list(cache.leaves()) == ["k", "v", "state", "conv"]
    assert cache.state.shape == (6, 64, 64, 128, 128) and cache.state.dtype == jnp.float32  # 4.19 MB a lane and layer
    assert cache.k.shape == (2, 64, 4096, 8, 128) and cache.conv.shape == (6, 64, 3 * 24576)


def test_config_from_hf_reads_the_published_config_json(tmp_path):
    from agentainer_tpu.engine.hf_convert import config_from_hf

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
    (tmp_path / "config.json").write_text(json.dumps(row["config"]))
    cfg = config_from_hf(tmp_path)
    assert dataclasses.replace(cfg, name="solar-open2") == get_config("solar-open2")
    (tmp_path / "config.json").write_text(json.dumps({**row["config"], "use_rope": True}))
    with pytest.raises(ValueError, match="rotary"):
        config_from_hf(tmp_path)


def test_scopes_name_the_rule_and_the_gate_in_a_lowered_step(case):
    """``jax.named_scope``s a capture's ops carry: the chunked rule's two
    halves, the step, the gate, the shared expert."""
    params, tokens, _ = case
    cache = init_cache(CFG, 1, 128, dtype=jnp.float32)
    run = jax.jit(lambda toks, pos, cache: forward(params, CFG, toks, pos, cache))
    chunk = run.lower(tokens[None, :70], jnp.arange(70)[None], cache).as_text(debug_info=True)
    for scope in ("kda_prepass", "kda_scan", "attn_gate", "moe_shared_expert"):
        assert scope in chunk, scope
    step = run.lower(tokens[None, :1], jnp.zeros((1, 1), jnp.int32), cache).as_text(debug_info=True)
    assert "kda_step" in step and "attn_gate" in step and "kda_scan" not in step


# -- the engine: the same entry points, scheduler, cache manager, mixed step ---------

ENGINE = {"max_batch": 2, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 32}
TURNS = [("turn one of a session that goes on for a while", 11), ("and a second turn", 9), ("a third", 7)]


def make_engine(**over):
    from agentainer_tpu.engine.llm import LLMEngine

    return LLMEngine.create("tiny-solar-open2", options={**ENGINE, **over})


async def chat_all(eng, session="s", turns=TURNS):
    return [(await eng.chat(session, text, max_tokens=n))["tokens"] for text, n in turns]


@pytest.fixture(scope="module")
def uninterrupted():
    eng = make_engine()
    try:
        return asyncio.run(chat_all(eng)), eng.metrics()
    finally:
        eng.shutdown()


def test_engine_tokens_are_the_plain_greedy_decode(uninterrupted):
    """Three turns through the engine (bucketed chunked prefill, pipelined
    decode chunks that run past each reply's end, the last token of a reply
    held out and fed with the next prompt) are the tokens a plain loop over
    ``forward`` gives for the same fed stream."""
    eng = make_engine(skip_warmup=True)
    try:
        params, tok = eng.params, eng.tokenizer
    finally:
        eng.shutdown()
    cache = init_cache(CFG, 1, 256, dtype=jnp.float32)
    run = jax.jit(lambda toks, at, cache: forward(params, CFG, toks, at, cache))
    pos, pending, got = 0, [], []
    for text, n in TURNS:
        feed = pending + tok.encode(text)
        logits, cache = run(jnp.asarray([feed], jnp.int32), (pos + jnp.arange(len(feed)))[None], cache)
        pos += len(feed)
        out = [int(jnp.argmax(logits[0, -1]))]
        while len(out) < n:
            logits, cache = run(jnp.asarray([[out[-1]]], jnp.int32), jnp.full((1, 1), pos), cache)
            pos += 1
            out.append(int(jnp.argmax(logits[0, 0])))
        pending = [out[-1]]  # sampled, never fed: it leads the next turn's prompt
        got.append(out)
    assert got == uninterrupted[0]


def test_kill_and_resume_is_token_identical_on_four_leaves(uninterrupted):
    async def interrupted():
        out, blob = [], None
        for text, n in TURNS:
            eng = make_engine()
            try:
                if blob is not None:
                    assert await eng.restore_session("s", blob) is True
                out.append((await eng.chat("s", text, max_tokens=n))["tokens"])
                blob = await eng.snapshot_session("s")
                assert blob is not None
            finally:
                eng.shutdown()  # the crash
        return out

    assert asyncio.run(interrupted()) == uninterrupted[0]


def test_two_sessions_over_one_lane_evict_snapshot_restore_like_never_evicting(uninterrupted):
    """One lane: ``s`` is snapshotted, evicted by another session taking its
    lane (which starts from ZERO state: its tokens are a fresh engine's), then
    restored into the lane it lost."""

    async def run():
        eng = make_engine(max_batch=1)
        try:
            eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
            first = (await eng.chat("s", TURNS[0][0], max_tokens=TURNS[0][1]))["tokens"]
            blob = await eng.snapshot_session("s")
            other = (await eng.chat("other", "someone else entirely", max_tokens=10))["tokens"]
            assert not eng.has_session("s") and eng.session_evictions == 1
            assert await eng.restore_session("s", blob) is True
            rest = await chat_all(eng, turns=TURNS[1:])
            return [first] + rest, other, eng.metrics()["cache"]
        finally:
            eng.shutdown()

    async def fresh():
        eng = make_engine(max_batch=1)
        try:
            return (await eng.chat("other", "someone else entirely", max_tokens=10))["tokens"]
        finally:
            eng.shutdown()

    tokens, other, cache = asyncio.run(run())
    assert tokens == uninterrupted[0] and other == asyncio.run(fresh())
    assert cache["state_restores"] == 1 and cache["state_snapshots"] == 1 and cache["state_resets"] >= 2


def test_concurrent_sessions_ride_the_mixed_step_and_match_their_own_engines(uninterrupted):
    """Two sessions at once over two lanes: one's prompt chunks carry the
    other's decode step (``mixed_launches`` counts them), and each reply is
    the one the session gets alone."""

    async def run():
        eng = make_engine()
        try:
            long = "a second caller arrives while the first is still writing its reply, with a prompt of several chunks " * 2
            a = asyncio.ensure_future(eng.chat("s", TURNS[0][0], max_tokens=40))
            await asyncio.sleep(0.05)
            b = asyncio.ensure_future(eng.chat("t", long, max_tokens=6))
            ra, rb = await a, await b
            return ra["tokens"], rb["tokens"], eng.metrics(), long
        finally:
            eng.shutdown()

    async def alone(text, n):
        eng = make_engine()
        try:
            return (await eng.chat("x", text, max_tokens=n))["tokens"]
        finally:
            eng.shutdown()

    a, b, m, long = asyncio.run(run())
    assert a == asyncio.run(alone(TURNS[0][0], 40)) and a[:11] == uninterrupted[0][0]
    assert b == asyncio.run(alone(long, 6))
    assert m["mixed_launches"] >= 1 and m["mixed_decode_lanes"] >= 1


@pytest.mark.parametrize("option", ["speculative", "paged_kv", "kv_tiering", "fused_decode", "prefix_cache"])
def test_a_feature_the_state_cannot_hold_is_an_error_when_asked_for(option):
    """Off by default with ``_RECURRENT_OFF``'s reason reported; asked for by
    name, refused at build — never a silent fallback."""
    with pytest.raises(ValueError, match=option):
        make_engine(skip_warmup=True, **{option: True})


def test_metrics_name_the_cache_kinds_the_plan_and_what_is_off(uninterrupted):
    m = uninterrupted[1]
    cache = m["cache"]
    assert cache["kinds"] == ["k", "v", "state", "conv"]
    sizes = [cache[f"{k}_bytes"] for k in cache["kinds"]]
    assert all(s > 0 for s in sizes) and cache["bytes_per_lane"] * 2 == sum(sizes) == m["kv_arena_bytes"] - 16
    assert set(cache["off"]) == {"speculative", "prefix_cache", "paged_kv", "fused_decode", "kv_tiering", "mesh"}
    assert "recurrent state" in cache["off"]["speculative"] and m["speculative"] is False and m["prefix_cache"] is False
    assert m["model_arch"]["layer_kinds"] == {"full": 3, "kda": 6} and m["model_arch"]["dense_layers"] == 0
    a = m["attention"]
    assert a["kda_decode"] == "xla_step" and a["full_decode"] == a["decode"] == "xla:attention_reference"
    assert a["gate"] == "full" and a["kv_heads_stored"] == 2
    lin = m["linear"]
    assert (lin["kind"], lin["layers"], lin["heads"], lin["head_dim"], lin["neg_eigval"], lin["conv"]) == ("kda", 6, 4, 16, True, True)
    assert lin["state_bytes_lane"] == 6 * 4 * 16 * 16 * 4 and lin["rows_chunked"] > 0 and lin["steps"] > 0
    moe = m["moe"]
    assert (moe["held"], moe["published"], moe["offset"], moe["shared_experts"], moe["router"]) == (8, 8, 0, 1, "sigmoid")
    assert cache["state_resets"] >= 1  # the session's first turn (and the warm-up's requests)
