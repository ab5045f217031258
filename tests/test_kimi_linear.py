"""The hybrid block (``models/hybrid.py``: KDA linear attention beside NoPE
latent attention, a dense first layer, then sigmoid-routed experts with a
shared one) against the benchmark's plain float32 reference
(``benchmark/families/kimi_linear_reference.py``, which imports nothing of
the program), on the CPU with ``tiny-kimi-linear`` and seeded weights — and
the cache manager's moves on a recurrent state, which cannot be truncated,
rewound or overwritten harmlessly.

Weights are NOT the 0.02-std init: at a hidden size of 64 that makes every
gate near ½, every router score near ½ and attention a near-uniform average,
and a check is then blind to the very steps this block adds. Here the
projections are scaled until each step moves the logits by several per cent
at least (``test_program_fails_a_reference_that_omits``).

Tolerance: both sides compute in float32 and differ by the order of
summation and the chunked against the token-by-token recurrence. Under the
sharpened weights float32 itself is worth 1e-4 (the reference in float32
against itself in float64 reads 9e-5; the program against the reference
2e-4), so the rms difference over the logits' standard deviation has to stay
under 1e-3; every omission has to read over 2e-2 (a reference that overflows
without a step counts as far off).
"""

import asyncio
import dataclasses
import importlib.util
import io
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.analysis.hlo_contracts import DonationAliased, StacksRideInCarry, check
from agentainer_tpu.models import llama
from agentainer_tpu.models.configs import get_config
from agentainer_tpu.models.hybrid import plan_hybrid
from agentainer_tpu.models.llama import forward, init_cache, init_params
from agentainer_tpu.ops import kda, mla
from agentainer_tpu.ops.moe import stacked_experts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-3  # see the module docstring
WRONG = 2e-2
CFG = get_config("tiny-kimi-linear")


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "kimi_linear_reference", os.path.join(REPO, "benchmark", "families", "kimi_linear_reference.py"))
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
        "mla": {"wq": 25.0, "wkva": 25.0, "wkvb": 10.0, "wo": 10.0},
        "dense": {"w_down": 10.0},
        "moe": {"router": 60.0, "w_down": 40.0, "ws_down": 40.0},
    }
    out = dict(p)
    for group, factors in scale.items():
        out[group] = {k: v * factors.get(k, 1.0) for k, v in p[group].items()}
    out["kda"]["o_norm"] = jax.random.uniform(next(keys), p["kda"]["o_norm"].shape, jnp.float32, 0.25, 4.0)
    out["mla"]["kv_norm"] = jax.random.uniform(next(keys), p["mla"]["kv_norm"].shape, jnp.float32, 0.25, 4.0)
    out["moe"]["router_bias"] = jax.random.normal(next(keys), p["moe"]["router_bias"].shape, jnp.float32) * 0.3
    out["lm_head"] = p["lm_head"] * 10.0
    return out


def reference_weights(params, cfg):
    """The program's per-kind stacks as the reference's list of layers (the
    merged q|k|v projection and conv filters split into the published three)."""
    layers, seen = [], {"kda": 0, "mla": 0}
    for i, kind in enumerate(cfg.layer_kinds):
        lp = {k: v[i] for k, v in params["layers"].items()}
        mixer = {k: v[seen[kind]] for k, v in params[kind].items()}
        seen[kind] += 1
        if kind == "kda":
            for name, part in zip("qkv", jnp.split(mixer.pop("wqkv"), 3, axis=-1)):
                lp["w" + name] = part
            for name, part in zip("qkv", jnp.split(mixer.pop("conv"), 3, axis=-1)):
                lp["conv_" + name] = part
        lp.update(mixer)
        group, j = ("dense", i) if i < cfg.n_dense_layers else ("moe", i - cfg.n_dense_layers)
        lp.update({k: v[j] for k, v in params[group].items()})
        layers.append(lp)
    return {"embed": params["embed"], "layers": layers, "final_norm": params["final_norm"], "lm_head": params["lm_head"]}


def reference_kw(cfg):
    return dict(
        n_heads=cfg.n_heads, kda_heads=cfg.kda_heads, kda_head_dim=cfg.kda_head_dim, kv_rank=cfg.mla_kv_rank,
        nope_dim=cfg.mla_nope_dim, v_dim=cfg.mla_v_dim, norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token,
        routed_scale=cfg.moe_scale, renormalize=cfg.moe_renormalize, expert_offset=cfg.expert_offset,
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


def program_full(params, tokens):
    pos = jnp.arange(tokens.shape[0])[None]
    return forward(params, CFG, tokens[None], pos)[0][0]


def program_cached(params, tokens, chunks=(70, 66)):
    """Prefill in two chunks (the second in a bucket with padding rows, the
    state carried from one launch to the next), then one-token decode steps
    through the cache."""
    cache = init_cache(CFG, 1, 192, dtype=jnp.float32)
    rows, at = [], 0
    for n, bucket in zip(chunks, (70, 96)):
        toks = jnp.pad(tokens[at : at + n], (0, bucket - n))[None]
        pos = (at + jnp.arange(bucket))[None]
        logits, cache = forward(params, CFG, toks, pos, cache, valid=(jnp.arange(bucket) < n)[None])
        rows.append(logits[0, :n])
        at += n
    for i in range(at, tokens.shape[0]):
        step, cache = forward(params, CFG, tokens[None, i : i + 1], jnp.full((1, 1), i), cache)
        rows.append(step[0])
    return jnp.concatenate(rows)


PROGRAMS = pytest.mark.parametrize(
    "program", [program_full, program_cached], ids=["full_forward", "two_chunks_then_decode"])


@PROGRAMS
def test_program_matches_the_plain_reference(case, program):
    params, tokens, want = case
    assert rel(program(params, tokens), want) < TOL


def softmax_router(logits, bias, top_k, scale, renormalize):
    top, chosen = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(top, axis=-1), chosen


def bias_in_the_weights(logits, bias, top_k, scale, renormalize):
    s = jax.nn.sigmoid(logits) + bias
    w, chosen = jax.lax.top_k(s, top_k)
    return w / jnp.sum(w, axis=-1, keepdims=True) * scale, chosen


def rotary(q_rope, k_shared, positions, theta=10_000.0):
    def rope(x):  # [T, ..., r]; rotate-half
        r = x.shape[-1]
        inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
        ang = positions.astype(jnp.float32)[:, None] * inv
        ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,))
        x1, x2 = x[..., : r // 2], x[..., r // 2 :]
        return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)

    return rope(q_rope), rope(k_shared)


_gates = ref.gates
OMISSIONS = {
    "no_conv": ("short_conv", lambda x, w: x),
    "no_decay_gate": ("log_decay", lambda x, lp, heads, dk, act: jnp.zeros((x.shape[0], heads, dk), jnp.float32)),
    "beta_one": ("beta_of", lambda x, lp, act: jnp.ones((x.shape[0], lp["w_beta"].shape[-1]), jnp.float32)),
    "no_l2norm": ("l2norm", lambda x: x),
    "no_output_gate": ("output_gate", lambda x, lp, heads, dk, act: jnp.ones((x.shape[0], heads, dk), jnp.float32)),
    "no_shared_expert": ("shared_expert", lambda x, lp, act: jnp.zeros_like(x)),
    "no_routed_scale": ("gates", lambda logits, bias, k, scale, renorm: _gates(logits, bias, k, 1.0, renorm)),
    "bias_in_the_weights": ("gates", bias_in_the_weights),
    "rotary_in_mla": ("position_embed", rotary),
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


# -- the mechanisms one by one ---------------------------------------------------


def kda_inputs(seed=0, b=2, t=150, h=3, dk=16):
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(b, t, h, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    raw = (rng.normal(size=(b, t, h, dk)), k, rng.normal(size=(b, t, h, dk)),
           np.log(rng.uniform(0.5, 0.999, size=(b, t, h, dk))), rng.uniform(size=(b, t, h)),
           rng.normal(size=(b, h, dk, dk)))
    return [jnp.asarray(x, jnp.float32) for x in raw]


def test_chunked_kda_is_the_token_by_token_recurrence_across_chunks_and_padding():
    """150 tokens: two whole chunks of 64 and a ragged third; then the same
    with 40 padding rows behind them (β = 0, g = 0), which must leave the
    state exactly where the real tokens left it."""
    q, k, v, g, beta, s0 = kda_inputs()
    o_ref, s_ref = kda.kda_recurrent(q, k, v, g, beta, s0)
    o, s = kda.kda_chunked(q, k, v, g, beta, s0)
    assert float(jnp.abs(o - o_ref).max()) < 1e-4 and float(jnp.abs(s - s_ref).max()) < 1e-5
    pad = lambda x: jnp.pad(x, [(0, 0), (0, 40)] + [(0, 0)] * (x.ndim - 2), constant_values=1.0)  # noqa: E731
    valid = jnp.broadcast_to(jnp.arange(190) < 150, (2, 190))
    gp, bp = kda.mask_inputs(pad(g), pad(beta), valid)
    o2, s2 = kda.kda_chunked(pad(q), pad(k), pad(v), gp, bp, s0)
    assert float(jnp.abs(o2[:, :150] - o_ref).max()) < 1e-4 and float(jnp.abs(s2 - s_ref).max()) < 1e-5


@pytest.mark.parametrize("c", [8, 16, 32, 64, 128])
def test_the_blocked_inverse_is_the_inverse_at_every_chunk_length(c):
    """``_unit_lower_inverse`` on ``[2, 3, C, C]`` strictly lower-triangular
    blocks, from a chunk shorter than its 16-token blocks (substitution
    alone) to 128 (three levels of merges), against numpy's float64 inverse;
    the rows of tokens that are masked (zero rows of ``lower``) come back as
    unit rows bit for bit, which is what leaves a masked token's state alone."""
    rng = np.random.default_rng(c)
    lower = np.tril(rng.normal(size=(2, 3, c, c)) * 0.3, -1)
    lower[:, :, [1, c - 2]] = 0.0
    got = np.asarray(kda._unit_lower_inverse(jnp.asarray(lower, jnp.float32)))
    want = np.linalg.inv(np.eye(c) + lower)
    assert np.abs(got - want).max() < 2e-6 * np.abs(want).max()
    assert np.array_equal(got[:, :, [1, c - 2]], np.broadcast_to(np.eye(c, dtype=np.float32)[[1, c - 2]], (2, 3, 2, c)))
    assert np.array_equal(np.triu(got, 1), np.zeros_like(got))


def test_the_blocked_inverse_refuses_a_length_it_cannot_halve():
    with pytest.raises(ValueError, match="power-of-two"):
        kda._unit_lower_inverse(jnp.zeros((48, 48)))


@pytest.mark.parametrize("t, chunk", [(20, 64), (64, 64), (150, 32), (150, 8), (200, 128)])
def test_chunked_kda_at_other_chunk_lengths_and_buckets(t, chunk):
    """The per-channel chunk at a bucket shorter than a chunk (20 tokens
    padded to 64), at one whole chunk, and at chunks of 8, 32 and 128 tokens
    (no merge, one level, three), each with a ragged last chunk: the
    token-by-token recurrence within the bounds of the 64-token chunk."""
    q, k, v, g, beta, s0 = kda_inputs(seed=t, t=t)
    o_ref, s_ref = kda.kda_recurrent(q, k, v, g, beta, s0)
    o, s = kda.kda_chunked(q, k, v, g, beta, s0, chunk=chunk)
    assert o.shape == o_ref.shape
    assert float(jnp.abs(o - o_ref).max()) < 1e-4 and float(jnp.abs(s - s_ref).max()) < 1e-5


def test_a_masked_chunk_leaves_the_state_bit_identical():
    """A lane whose every token of a launch is masked (β = 0, g = 0: a parked
    lane in a prefill bucket) gets its state back bit for bit from the chunked
    rule, beside a lane that steps; so does a lane whose real tokens are
    followed by whole chunks of padding, from the last real token on."""
    q, k, v, g, beta, s0 = kda_inputs(t=150)
    valid = jnp.stack([jnp.zeros(150, bool), jnp.arange(150) < 64])
    gm, bm = kda.mask_inputs(g, beta, valid)
    _, s = kda.kda_chunked(q, k, v, gm, bm, s0)
    assert np.array_equal(np.asarray(s[0]), np.asarray(s0[0]))
    _, s_one = kda.kda_chunked(q[:, :64], k[:, :64], v[:, :64], g[:, :64], beta[:, :64], s0)
    assert np.array_equal(np.asarray(s[1]), np.asarray(s_one[1]))


def test_a_masked_token_leaves_state_and_conv_bit_identical():
    q, k, v, g, beta, s0 = kda_inputs(t=1)
    g0, b0 = kda.mask_inputs(g[:, 0], beta[:, 0], jnp.array([False, True]))
    _, s1 = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g0, b0, s0)
    assert np.array_equal(np.asarray(s1[0]), np.asarray(s0[0])) and not np.array_equal(np.asarray(s1[1]), np.asarray(s0[1]))
    x = jnp.ones((2, 5, 8))
    conv0 = jnp.arange(2 * 3 * 8, dtype=jnp.float32).reshape(2, 3, 8)
    _, conv1 = kda.causal_conv(x, conv0, jnp.ones((4, 8)), jnp.array([0, 5]))
    assert np.array_equal(np.asarray(conv1[0]), np.asarray(conv0[0])) and np.array_equal(np.asarray(conv1[1]), np.ones((3, 8)))


def test_absorbed_mla_is_expanded_mla():
    """Folding W_kvb into the query and the output is the same mathematics
    as expanding every head's keys and values from the latent rows."""
    rng = np.random.default_rng(1)
    b, t, s, h, rank, nope, r, dv = 2, 5, 40, 4, 32, 16, 8, 16
    q = jnp.asarray(rng.normal(size=(b, t, h, nope + r)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(b, s, rank + r)), jnp.float32)
    w_kvb = jnp.asarray(rng.normal(size=(rank, h, nope + dv)), jnp.float32) * 0.3
    pos = jnp.asarray(rng.integers(4, s, size=(b, t)), jnp.int32)
    scale = (nope + r) ** -0.5
    o_lat = mla.attend(mla.absorb_query(q, w_kvb, nope), rows, pos, scale, rank)
    absorbed = jnp.einsum("bthr,rhv->bthv", o_lat, w_kvb[..., nope:])
    want = mla.expanded(q, rows, pos, w_kvb, scale, rank, nope)
    assert float(jnp.abs(absorbed - want).max()) < 1e-4 * float(jnp.abs(want).max())


def test_pallas_kernels_compute_what_their_jnp_twins_do():
    """Interpret mode: the KDA decode kernel against ``kda_step`` (one lane
    masked, the other layers of the stack untouched), the MLA decode kernel
    against ``mla.attend`` at ragged positions."""
    from agentainer_tpu.ops.pallas_kda import kda_decode
    from agentainer_tpu.ops.pallas_mla import mla_decode

    rng = np.random.default_rng(0)
    b, h, dk = 3, 8, 128
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, dk)), jnp.float32) for _ in range(3))
    g = jnp.asarray(np.log(rng.uniform(0.5, 0.999, size=(b, h, dk))), jnp.float32).at[1].set(0.0)
    beta = jnp.asarray(rng.uniform(size=(b, h)), jnp.float32).at[1].set(0.0)
    stack = jnp.asarray(rng.normal(size=(2, b, h, dk, dk)), jnp.float32)
    o_want, s_want = kda.kda_step(q, k, v, g, beta, stack[1])
    o, out = kda_decode(q, k, v, g, beta, stack, 1, interpret=True)
    assert float(jnp.abs(o - o_want).max()) < 1e-3 and float(jnp.abs(out[1] - s_want).max()) < 1e-4
    assert np.array_equal(np.asarray(out[0]), np.asarray(stack[0]))  # another layer
    assert np.array_equal(np.asarray(out[1, 1]), np.asarray(stack[1, 1]))  # the masked lane

    heads, width, rank, s = 4, 128, 96, 300
    lat = jnp.asarray(rng.normal(size=(2, b, s, width)), jnp.float32)
    qf = jnp.asarray(rng.normal(size=(b, heads, width)), jnp.float32)
    pos = jnp.asarray([5, 299, 130], jnp.int32)
    want = mla.attend(qf[:, None], lat[1], pos[:, None], 0.3, rank)[:, 0]
    got = mla_decode(qf, lat, pos, 1, 0, scale=0.3, rank=rank, block_k=128, interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5


# (tokens, first position, arena rows, real tokens, block_k, block_q): a stack of
# 2 layers x 3 lanes, read at layer 1, lane 2
MLA_PREFILL_CASES = {
    "bucket-32-at-offset-0": (32, 0, 300, 32, 128, 16),
    "bucket-64-in-mid-row": (64, 100, 300, 64, 128, 16),
    "bucket-128-ending-at-the-arenas-last-row": (128, 172, 300, 128, 128, 16),
    "bucket-256-over-one-block-that-does-not-divide-S": (256, 0, 300, 256, 512, 16),
    "numerics-childs-192-rows": (192, 0, 256, 192, 128, 16),
    "bucket-256-with-padding-past-n_real": (256, 40, 600, 130, 128, 16),
    "padding-that-runs-past-the-arenas-end": (64, 270, 300, 20, 128, 8),
    "token-tile-that-does-not-divide-T": (40, 33, 300, 40, 128, 16),
    "one-tile-of-every-token": (64, 500, 600, 64, 256, 64),
}


@pytest.mark.parametrize("case", MLA_PREFILL_CASES)
def test_mla_prefill_kernel_is_attend_over_the_stack_where_it_lies(case):
    """Interpret mode: ``pallas_mla.mla_prefill`` against ``mla.attend`` over
    the lane's sliced row, and, through the output projection, against
    ``mla.expanded``. A token past ``n_real`` is handed position -1 (what
    ``mla_mixer`` does with ``valid``): its output is nobody's, and finite."""
    from agentainer_tpu.ops.pallas_mla import mla_prefill

    t, start, s, n_real, block_k, block_q = MLA_PREFILL_CASES[case]
    rng = np.random.default_rng(t + start)
    h, rank, nope, r, dv, width, layer, lane = 4, 96, 16, 8, 16, 128, 1, 2
    stack = jnp.asarray(rng.normal(size=(2, 3, s, width)), jnp.float32).at[..., rank + r :].set(0.0)
    q = jnp.asarray(rng.normal(size=(1, t, h, nope + r)), jnp.float32)
    w_kvb = jnp.asarray(rng.normal(size=(rank, h, nope + dv)), jnp.float32) * 0.3
    pos = jnp.asarray(start + np.arange(t), jnp.int32)[None]
    scale = (nope + r) ** -0.5
    q_full = jnp.pad(mla.absorb_query(q, w_kvb, nope), [(0, 0)] * 3 + [(0, width - rank - r)])
    seen = jnp.where(jnp.arange(t)[None] < n_real, pos, -1)
    got = mla_prefill(q_full, stack, seen, layer, lane, scale=scale, rank=rank,
                      block_q=block_q, block_k=block_k, interpret=True)
    assert got.shape == (1, t, h, rank) and got.dtype == jnp.float32 and bool(jnp.isfinite(got).all())
    rows = stack[layer, lane : lane + 1]
    want = mla.attend(q_full, rows, pos, scale, rank)
    assert float(jnp.abs(got - want)[:, :n_real].max()) < 1e-5
    out = jnp.einsum("bthr,rhv->bthv", got, w_kvb[..., nope:])
    long_way = mla.expanded(q, rows[..., : rank + r], pos, w_kvb, scale, rank, nope)
    assert float(jnp.abs(out - long_way)[:, :n_real].max()) < 1e-4 * float(jnp.abs(long_way).max())


def test_the_sigmoid_router_rule_is_the_references():
    logits = jax.random.normal(jax.random.PRNGKey(0), (40, CFG.n_experts)) * 3.0
    bias = jax.random.normal(jax.random.PRNGKey(1), (CFG.n_experts,)) * 0.5
    g, chosen = llama.moe_gates(logits, CFG, jnp.float32, bias)
    g_ref, chosen_ref = ref.gates(logits, bias, CFG.experts_per_token, CFG.moe_scale, True)
    assert np.array_equal(np.asarray(chosen), np.asarray(chosen_ref))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g.sum(-1)), CFG.moe_scale, rtol=1e-5)
    # the bias chooses and never weighs: a large one changes who is chosen
    assert not np.array_equal(np.asarray(chosen), np.asarray(llama.moe_gates(logits, CFG, jnp.float32, None)[1]))


@pytest.mark.parametrize("path", ["einsum", "sorted"])
def test_shares_add_up_to_the_uncut_layer(path):
    """Expert parallelism without the exchange: 4 chips hold 2 of the 8
    experts each, every chip routes over all 8 and computes its own experts'
    terms. The routed parts of all four shares plus the shared expert ONCE
    equal the uncut reference layer — through both of the program's paths."""
    params = sharp_params(CFG)
    lp = {k: v[0] for k, v in params["moe"].items()}
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 40, CFG.dim), jnp.float32)
    want = ref.moe(h[0], lp, CFG.experts_per_token, CFG.moe_scale, True, 0, lambda x: x)
    total = llama._mlp(h, {"w" + k[2:]: v for k, v in lp.items() if k.startswith("ws_")})[0]
    for chip in range(4):
        share = dataclasses.replace(CFG, experts_held=2, expert_offset=2 * chip)
        mine = {k: (v[2 * chip : 2 * chip + 2] if k in ("w_gate", "w_up", "w_down") else v) for k, v in lp.items()}
        if path == "einsum":
            total = total + llama._moe_mlp(h, mine, share)[0]
        else:
            experts = stacked_experts({k: v[None] for k, v in mine.items()})
            total = total + llama._moe_mlp_sorted(h, mine, share, experts, jnp.int32(0))[0]
    assert rel(total, want) < TOL
    # and a share alone is the reference's share: the same held range, nothing standing in for the rest
    share = dataclasses.replace(CFG, experts_held=2, expert_offset=4)
    mine = {k: (v[4:6] if k in ("w_gate", "w_up", "w_down") else v) for k, v in lp.items()}
    routed = llama._moe_mlp(h, mine, share)[0] + llama._mlp(h, {"w" + k[2:]: v for k, v in lp.items() if k.startswith("ws_")})[0]
    assert rel(routed, ref.moe(h[0], mine, CFG.experts_per_token, CFG.moe_scale, True, 4, lambda x: x)) < TOL


def test_param_count_is_the_pytrees_size_and_the_published_models():
    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    assert CFG.param_count() == sum(x.size for x in jax.tree.leaves(params))
    big = get_config("kimi-linear-48b")
    assert abs(big.param_count() / 49.1e9 - 1.0) < 0.01
    assert big.layer_kinds.count("kda") == 20 and [i + 1 for i, k in enumerate(big.layer_kinds) if k == "mla"] == [4, 8, 12, 16, 20, 24, 27]
    held = dataclasses.replace(big, experts_held=32)
    assert abs(held.param_count() / 7.9e9 - 1.0) < 0.01  # one chip's share of ep = 8
    assert 3.0e9 < big.active_param_count() < 3.6e9  # "A3B"
    assert held.flops_per_token(2048) > 2.0 * held.active_param_count()


# -- the cache manager on a recurrent state ----------------------------------------

ENGINE = {"max_batch": 2, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 32}


def make_engine(**over):
    from agentainer_tpu.engine.llm import LLMEngine

    return LLMEngine.create("tiny-kimi-linear", options={**ENGINE, **over})


TURNS = [("turn one of a session that goes on for a while", 11), ("and a second turn", 9), ("a third", 7)]


async def chat_all(eng, session="s", turns=TURNS):
    return [(await eng.chat(session, text, max_tokens=n))["tokens"] for text, n in turns]


@pytest.fixture(scope="module")
def uninterrupted():
    eng = make_engine()
    try:
        return asyncio.run(chat_all(eng))
    finally:
        eng.shutdown()


def test_engine_tokens_are_the_plain_greedy_decode(uninterrupted):
    """Three turns through the engine (bucketed chunked prefill, pipelined
    decode chunks that run past each reply's end, the last token of a reply
    held out and fed with the next prompt) are the tokens a plain loop over
    ``forward`` gives for the same fed stream: nothing the engine's lanes do
    beside the session's own tokens reaches its state."""
    eng = make_engine(skip_warmup=True)
    try:
        params, tok = eng.params, eng.tokenizer
    finally:
        eng.shutdown()
    cache = init_cache(CFG, 1, 256, dtype=jnp.float32)
    pos, pending, got = 0, [], []
    for (text, n), want in zip(TURNS, uninterrupted):
        feed = pending + tok.encode(text)
        logits, cache = forward(params, CFG, jnp.asarray([feed], jnp.int32), (pos + jnp.arange(len(feed)))[None], cache)
        pos += len(feed)
        out = [int(jnp.argmax(logits[0, -1]))]
        while len(out) < n:
            logits, cache = forward(params, CFG, jnp.asarray([[out[-1]]], jnp.int32), jnp.full((1, 1), pos), cache)
            pos += 1
            out.append(int(jnp.argmax(logits[0, 0])))
        pending = [out[-1]]  # sampled, never fed: it leads the next turn's prompt
        got.append(out)
    assert got == uninterrupted


def test_kill_and_resume_is_token_identical_on_a_recurrent_state(uninterrupted):
    """The signature flow on the new state: snapshot after each turn, kill,
    restore into a new engine, go on — the same tokens as never stopping."""

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

    assert asyncio.run(interrupted()) == uninterrupted


def test_evicted_session_comes_back_token_identical_and_a_reused_lane_starts_from_zero(uninterrupted):
    """One lane: session ``s`` is snapshotted, evicted by another session
    taking its lane (which has to start from ZERO state: its tokens are those
    of a fresh engine), then restored into the lane it lost."""

    async def run():
        eng = make_engine(max_batch=1)
        try:
            eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
            first = (await eng.chat("s", *TURNS[0][:1], max_tokens=TURNS[0][1]))["tokens"]
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
    assert tokens == uninterrupted
    assert other == asyncio.run(fresh())
    assert cache["state_restores"] == 1 and cache["state_snapshots"] == 1 and cache["state_resets"] >= 2


def test_parked_idle_lanes_and_padding_leave_a_sessions_state_bit_identical():
    """Session ``a`` finishes and its lane parks; ``b`` then prefills (bucket
    padding) and decodes well over 32 steps in the lane beside it, the
    pipelined chunks stepping every lane each time. Lane a's state, conv and
    live latent rows are bit-identical afterwards, and so is a never-used
    lane's zero state."""

    async def run():
        eng = make_engine(max_batch=3)
        try:
            await eng.chat("a", "the first session says a few words", max_tokens=13)
            lane = eng.sessions["a"]
            n = eng.slots[lane].position

            def held():
                c = eng.cache
                return [np.asarray(c.state[:, lane]), np.asarray(c.conv[:, lane]), np.asarray(c.latent[:, lane, :n]),
                        np.asarray(c.state[:, 2]), np.asarray(c.conv[:, 2])]

            before = held()
            steps0 = eng.forward_passes
            await eng.chat("b", "the second session talks for much longer than the first did", max_tokens=60)
            assert eng.forward_passes - steps0 > 32
            return before, held()
        finally:
            eng.shutdown()

    before, after = asyncio.run(run())
    for x, y in zip(before, after):
        assert np.array_equal(x, y)
    assert not before[3].any()  # the idle lane never left zero


def test_an_old_format_snapshot_of_a_kv_family_still_restores():
    """SNAP_VERSION 3 blobs (k, v, a dtype header) written before the named
    leaves restore into a ``tiny`` engine and the session goes on exactly."""
    from agentainer_tpu.engine.checkpoint import SNAP_VERSION, deserialize_snapshot
    from agentainer_tpu.engine.llm import LLMEngine

    opts = {"max_batch": 2, "max_seq": 128, "decode_chunk": 4, "prefill_chunk": 32, "speculative": False}

    async def run(old_format: bool):
        eng = LLMEngine.create("tiny", options=opts)
        a = await eng.chat("s", "turn one", max_tokens=6)
        blob = await eng.snapshot_session("s")
        eng.shutdown()
        leaves, header = deserialize_snapshot(blob)
        assert header["version"] == SNAP_VERSION == 4 and set(leaves) == {"k", "v"}
        if old_format:
            old = {k: header[k] for k in ("position", "session", "pending_token")}
            old.update(version=3, dtype=str(leaves["k"].dtype))
            buf = io.BytesIO()
            np.savez_compressed(buf, k=leaves["k"], v=leaves["v"], header=np.frombuffer(json.dumps(old).encode(), dtype=np.uint8))
            blob = buf.getvalue()
        eng = LLMEngine.create("tiny", options=opts)
        assert await eng.restore_session("s", blob) is True
        b = await eng.chat("s", "turn two", max_tokens=6)
        eng.shutdown()
        return a["tokens"], b["tokens"]

    assert asyncio.run(run(True)) == asyncio.run(run(False))


def test_a_snapshot_of_another_family_is_refused_not_misread():
    async def run():
        from agentainer_tpu.engine.llm import LLMEngine

        tiny = LLMEngine.create("tiny", options={"max_batch": 1, "max_seq": 128, "skip_warmup": True, "speculative": False})
        await tiny.chat("s", "hello", max_tokens=4)
        tiny.snapshot_min_gap_s = 0.0
        blob_kv = await tiny.snapshot_session("s")
        eng = make_engine(skip_warmup=True)
        try:
            await eng.chat("s", "hello", max_tokens=4)
            eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
            blob_state = await eng.snapshot_session("s")
            return await eng.restore_session("t", blob_kv), await tiny.restore_session("t", blob_state)
        finally:
            eng.shutdown()
            tiny.shutdown()

    assert asyncio.run(run()) == (False, False)


@pytest.mark.parametrize("option", ["speculative", "paged_kv", "kv_tiering", "fused_decode", "prefix_cache"])
def test_a_feature_the_state_cannot_hold_is_an_error_when_asked_for(option):
    """Off by default with its reason reported; asked for by name, refused
    at build — never a silent fallback."""
    with pytest.raises(ValueError, match=option):
        make_engine(skip_warmup=True, **{option: True})


def test_metrics_name_the_cache_kinds_the_plan_and_what_is_off():
    eng = make_engine(skip_warmup=True)
    try:
        m = eng.metrics()
    finally:
        eng.shutdown()
    cache = m["cache"]
    assert cache["kinds"] == ["latent", "state", "conv"]
    assert cache["bytes_per_lane"] * 2 == cache["latent_bytes"] + cache["state_bytes"] + cache["conv_bytes"] == m["kv_arena_bytes"] - 16
    assert set(cache["off"]) == {"speculative", "prefix_cache", "paged_kv", "fused_decode", "kv_tiering", "mesh"}
    assert m["speculative"] is False and m["prefix_cache"] is False
    assert m["model_arch"]["layer_kinds"] == {"kda": 6, "mla": 3} and m["model_arch"]["dense_layers"] == 1
    assert m["attention"]["kda_decode"] == "xla_step" and m["attention"]["mla_decode"] == "xla_absorbed"
    # the name the ledger's readers of ``attention.mla_prefill`` find: the XLA
    # form off the chip, the plan's string (``pallas_mla_prefill``) on it
    assert m["attention"]["mla_prefill"] == m["attention"]["prefill"] == "xla_absorbed"
    on_chip = plan_hybrid(get_config("kimi-linear-48b"), use_pallas=True).describe()
    assert on_chip["mla_prefill"] == on_chip["prefill"] == "pallas_mla_prefill" and on_chip["arena"] == "stack+layer"
    assert m["moe"]["experts_held"] == 8 and m["moe"]["shared_experts"] == 1 and m["moe"]["router"] == "sigmoid"


def test_engine_with_the_prefill_kernel_planned_returns_the_xla_plans_tokens():
    """A prompt of several chunks and two more turns through an engine whose
    plan names ``pallas_mla_prefill`` (the kernel in interpret mode; the other
    mechanisms as on the CPU): the tokens of the ``xla_absorbed`` engine, and
    ``/metrics`` says which of the two served."""
    import functools

    from agentainer_tpu.models import hybrid
    from agentainer_tpu.ops import pallas_mla

    turns = [("a first prompt long enough to be cut into more than three chunks of thirty-two tokens, "
              "the last of them a bucket with padding", 9), ("and a second turn", 7), ("a third", 5)]

    def tokens(eng):
        try:
            return asyncio.run(chat_all(eng, turns=turns)), eng.metrics()["attention"]
        finally:
            eng.shutdown()

    want, said = tokens(make_engine(skip_warmup=True))
    assert said["mla_prefill"] == "xla_absorbed"
    planned = lambda cfg, use_pallas=None: plan_hybrid(cfg, False)._replace(mla_prefill="pallas_mla_prefill")  # noqa: E731
    interpreted = functools.partial(pallas_mla.mla_prefill, interpret=True)
    with mock.patch.object(hybrid, "plan_hybrid", planned), mock.patch.object(pallas_mla, "mla_prefill", interpreted):
        got, said = tokens(make_engine(skip_warmup=True))
    assert said["mla_prefill"] == said["prefill"] == "pallas_mla_prefill" and said["mla_decode"] == "xla_absorbed"
    assert got == want and sum(map(len, got)) == 21


@pytest.mark.parametrize("step", ["jit_decode_n", "jit_prefill"])
def test_state_and_latent_stacks_ride_in_the_loop_carries(step):
    """Beside ``ArenaRidesInCarry``: the three stacks are carried through the
    layer scan, the mixer's 0-or-1-trip loop and the step scan, each once; a
    write touches the step's rows or the stepping lanes' state and nothing
    else; and every donated leaf aliases its output."""
    eng = make_engine(skip_warmup=True, max_batch=4)
    try:
        b = eng.max_batch
        z = lambda dt: jnp.zeros((b,), dt)  # noqa: E731
        if step == "jit_decode_n":
            keys = jax.random.split(jax.random.PRNGKey(0), 8)
            lowered = eng._decode_n.lower(
                eng.params, eng.cache, z(jnp.int32), z(jnp.int32), z(jnp.float32), z(jnp.int32), z(jnp.float32), keys)
            lanes, t, loops = b, 1, 3
        else:
            toks = jnp.zeros((1, 32), jnp.int32)
            lowered = eng._prefill.lower(eng.params, eng.cache, jnp.int32(1), toks, toks, jnp.int32(5))
            lanes, t, loops = 1, 32, 2
        c = eng.cache
        assert f"module @{step}" in lowered.as_text()
        check(
            lowered.as_text(),
            StacksRideInCarry(
                stacks={"latent": c.latent.shape, "state": c.state.shape, "conv": c.conv.shape},
                updates={
                    "latent": lanes * t * c.latent.shape[-1],
                    "state": lanes * int(np.prod(c.state.shape[2:])),
                    "conv": lanes * c.conv.shape[-1],
                },
                loops=loops,
            ),
        )
        check(lowered.compile().as_text(), DonationAliased(min_count=3))
    finally:
        eng.shutdown()


def test_param_specs_split_the_routed_experts_over_ep_and_nothing_else():
    from jax.sharding import PartitionSpec as P

    from agentainer_tpu.parallel.sharding import hybrid_param_specs

    specs = hybrid_param_specs(CFG)
    params = jax.eval_shape(lambda: init_params(CFG, jax.random.PRNGKey(0)))
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P)) == jax.tree.structure(params)
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): spec
            for path, spec in jax.tree.flatten_with_path(specs, is_leaf=lambda x: isinstance(x, P))[0]}
    split = {k for k, spec in flat.items() if any(axis is not None for axis in spec)}
    assert split == {"moe/w_gate", "moe/w_up", "moe/w_down"} and flat["moe/w_gate"] == P(None, "ep", None, None)
    assert flat["moe/ws_gate"] == P(None, None, None) and flat["moe/router"] == P(None, None, None)


def test_kernel_names_are_the_ones_the_benchmarks_readers_look_for():
    """``benchmark/layer_metrics/{kda,mla}_decode_roofline.py`` find the two
    kernels' device time among the trace's ops by these names: a renamed
    kernel must fail here, not turn a roofline into ``None``."""
    import inspect
    import re

    from agentainer_tpu.ops import pallas_kda, pallas_mla

    for module, name in ((pallas_kda, "kda_decode"), (pallas_mla, "mla_decode")):
        assert f'name="{name}"' in inspect.getsource(module)
        with open(os.path.join(REPO, "benchmark", "layer_metrics", f"{name}_roofline.py")) as f:
            assert re.search(rf'^KERNEL = "{name}"$', f.read(), re.M)
    # no reader yet: the name a trace's ops carry, for the one that will come
    assert 'name="mla_prefill"' in inspect.getsource(pallas_mla)
