"""The hybrid block with latent attention in EVERY layer and no linear mixer
(``models/hybrid.py`` under ``mla_q_rank``, ``mla_rotary``, ``rope_interleave``,
the YaRN fields and ``q_pos_scale_beta``; the softmax router of
``llama.moe_gates`` beside a shared expert and a held share of the experts)
against the benchmark's plain float32 reference
(``benchmark/families/mistral4_reference.py``, which imports nothing of the
program), on the CPU with ``tiny-mistral4`` (3 MLA layers, a query latent of 24,
16 rotated dims, an original context of 32 with factor 8, so that 100 tokens
cross the boundary three times) and seeded weights — and the cache manager's
moves on a cache of latent rows with no per-lane state.

Weights are NOT the 0.02-std init (``tests/test_kimi_linear.py`` says why): the
projections are scaled until the query's norm, the rotation, its pairing, the
YaRN ramp, ``m²``, the query's scale by position, the shared expert and the
renormalisation each move the logits by several per cent at least.

Tolerance: both sides compute in float32 and differ by the order of summation
and the absorbed against the expanded attention; the rms difference over the
logits' standard deviation stays under 1e-3 in the median over positions;
every wrong block has to read over 2e-2.
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

from agentainer_tpu.models import hybrid, llama
from agentainer_tpu.models.configs import get_config
from agentainer_tpu.models.llama import forward, init_cache, init_params
from agentainer_tpu.ops import mla
from agentainer_tpu.ops.moe import stacked_experts
from agentainer_tpu.ops.rope import apply_rope, yarn_frequencies

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-3  # see the module docstring
WRONG = 2e-2
CFG = get_config("tiny-mistral4")
ORIGINAL = CFG.rope_original_max
N_TOKENS = 100


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "mistral4_reference", os.path.join(REPO, "benchmark", "families", "mistral4_reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()


def sharp_params(cfg, seed=3):
    """Seeded float32 weights under which the block's own steps matter."""
    p = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 4))
    scale = {
        "mla": {"wq_a": 25.0, "wq_b": 12.0, "wkva": 25.0, "wkvb": 10.0, "wo": 10.0},
        # the router sharp enough that a token's choice is clear, soft enough that
        # the experts it does not choose keep a third of the mass (the renormalisation)
        "moe": {"router": 8.0, "w_down": 80.0, "ws_down": 30.0},
    }
    out = dict(p)
    for group, factors in scale.items():
        out[group] = {k: v * factors.get(k, 1.0) for k, v in p[group].items()}
    # a norm weight that is not one: leaving the norm out is then not a rescaling
    out["mla"]["q_norm"] = jax.random.uniform(next(keys), p["mla"]["q_norm"].shape, jnp.float32, 0.25, 4.0)
    out["mla"]["kv_norm"] = jax.random.uniform(next(keys), p["mla"]["kv_norm"].shape, jnp.float32, 0.25, 4.0)
    out["lm_head"] = p["lm_head"] * 10.0
    return out


def reference_weights(params, cfg):
    layers = [
        {**{k: v[i] for k, v in params["layers"].items()}, **{k: v[i] for k, v in params["mla"].items()},
         **{k: v[i] for k, v in params["moe"].items()}}
        for i in range(cfg.n_layers)
    ]
    return {"embed": params["embed"], "layers": layers, "final_norm": params["final_norm"], "lm_head": params["lm_head"]}


def reference_kw(cfg):
    return dict(
        n_heads=cfg.n_heads, kv_rank=cfg.mla_kv_rank, nope_dim=cfg.mla_nope_dim, rope_dim=cfg.mla_rope_dim,
        v_dim=cfg.mla_v_dim, norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token, rope_theta=cfg.rope_theta,
        rope_factor=cfg.rope_factor, original_max=cfg.rope_original_max, beta_fast=cfg.rope_beta_fast,
        beta_slow=cfg.rope_beta_slow, mscale_all_dim=cfg.rope_mscale_all_dim, query_beta=cfg.q_pos_scale_beta,
        expert_offset=cfg.expert_offset,
    )


def reference_logits(params, cfg, tokens):
    return ref.forward(reference_weights(params, cfg), tokens, **reference_kw(cfg))


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.median(np.sqrt(np.mean((got - want) ** 2, -1)) / np.std(want, -1)))


def rel_all(got, want):
    """One ratio over all rows: a chip's share is exactly 0 on the rows whose
    experts live elsewhere, and a per-row ratio has nothing to divide by."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))


class Runner:
    """``forward`` under ``jax.jit``, one program a call shape
    (``tests/test_smallthinker.py``'s ``Runner`` says why)."""

    def __init__(self, cfg):
        self.full = jax.jit(lambda p, t, q: forward(p, cfg, t, q)[0])
        self.chunk = jax.jit(lambda p, c, t, q, slot, valid: forward(p, cfg, t, q, c, slot=slot, valid=valid))
        self.step = jax.jit(lambda p, c, t, q: forward(p, cfg, t, q, c))  # any [B, T], no slot


RUN = Runner(CFG)


@pytest.fixture(scope="module")
def case():
    params = sharp_params(CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (N_TOKENS,), 3, CFG.vocab_size)
    return params, tokens, reference_logits(params, CFG, tokens)


def test_the_tiny_configuration_has_every_shape_the_block_adds():
    assert set(CFG.layer_kinds) == {"mla"} and CFG.linear_kind is None and CFG.n_dense_layers == 0
    assert 0 < CFG.mla_q_rank < CFG.dim and CFG.mla_rotary and CFG.rope_interleave
    assert CFG.rope_original_max <= 32 and CFG.rope_factor > 1 and CFG.q_pos_scale_beta > 0
    assert CFG.softmax_mscale == pytest.approx(0.1 * np.log(CFG.rope_factor) + 1.0)
    assert (CFG.moe_router, CFG.moe_renormalize, CFG.n_shared_experts) == ("softmax", True, 1)
    # a pair strictly inside the ramp, one kept and one divided by the factor
    plain = np.asarray(yarn_frequencies(CFG.mla_rope_dim, CFG.rope_theta, 1.0, ORIGINAL))
    ratio = plain / np.asarray(yarn_frequencies(CFG.mla_rope_dim, CFG.rope_theta, CFG.rope_factor, ORIGINAL))
    assert ratio[0] == 1.0 and ratio[-1] == pytest.approx(CFG.rope_factor) and any(1.01 < r < CFG.rope_factor * 0.99 for r in ratio)
    assert hybrid.latent_width(CFG) == 128 and hybrid.latent_width(get_config("mistral-small-4-119b")) == 384


def test_full_forward_matches_the_plain_reference(case):
    params, tokens, want = case
    assert rel(RUN.full(params, tokens[None], jnp.arange(N_TOKENS)[None])[0], want) < TOL


def cached(params, tokens, chunks, bucket=None, max_seq=128, lanes=1, lane=0, cache=None):
    """Prefill ``tokens`` in ``chunks`` (row counts, each padded to ``bucket``
    rows where one is given; the rest one-token decode steps) through the
    cache; the other lanes are parked at the arena's last row."""
    if cache is None:
        cache = init_cache(CFG, lanes, max_seq, dtype=jnp.float32)
    rows, at = [], 0
    for n in chunks:
        width = bucket or n
        toks = jnp.pad(tokens[at : at + n], (0, width - n))[None]
        pos = (at + jnp.arange(width))[None]
        logits, cache = RUN.chunk(params, cache, toks, pos, jnp.int32(lane), (jnp.arange(width) < n)[None])
        rows.append(logits[0, :n])
        at += n
    for i in range(at, tokens.shape[0]):
        tok = jnp.zeros((lanes, 1), jnp.int32).at[lane, 0].set(tokens[i])
        pos = jnp.full((lanes, 1), max_seq - 1, jnp.int32).at[lane, 0].set(i)
        step, cache = RUN.step(params, cache, tok, pos)
        rows.append(step[lane])
    return jnp.concatenate(rows), cache


CACHED = {
    # the whole context inside the original one: plain positions, scale 1
    "under_the_original_context": dict(n=ORIGINAL - 4, chunks=(16,)),
    # the last decode step is the first position (32) whose query is scaled
    "exactly_at_the_boundary": dict(n=ORIGINAL + 1, chunks=(16, 16)),
    # a chunk that starts under the boundary and ends past it, then decode over 64 and 96
    "a_chunk_crosses_the_boundary": dict(n=N_TOKENS, chunks=(20, 20, 20)),
    # chunks that end on the boundary, decode steps across the later ones
    "chunks_end_on_the_boundary": dict(n=N_TOKENS, chunks=(32, 32)),
    # decode steps alone carry the context over every boundary
    "decode_crosses_every_boundary": dict(n=N_TOKENS, chunks=(8,)),
    # bucketed chunks: padding rows past the real ones (positions past the boundary among them)
    "buckets_with_padding": dict(n=N_TOKENS, chunks=(30, 30, 30), bucket=32),
}


@pytest.mark.parametrize("name", sorted(CACHED))
def test_cached_prefill_and_decode_match_the_reference_full_forward(case, name):
    """Logits, not tokens, at EVERY position: chunks through the latent rows
    (the cached row holds the rotated k_r), then one-token steps, against the
    reference's full causal forward."""
    params, tokens, want = case
    spec = CACHED[name]
    got, cache = cached(params, tokens[: spec["n"]], spec["chunks"], spec.get("bucket"))
    assert cache.state is None and cache.conv is None and set(cache.leaves()) == {"latent"}
    assert cache.latent.shape == (CFG.n_layers, 1, 128, hybrid.latent_width(CFG))
    assert rel(got, want[: spec["n"]]) < TOL


def test_a_lane_readmitted_onto_a_used_slot_and_a_parked_lane_beside_it(case):
    """Lane 1 of two: a first context fills its rows, then a fresh one is
    served from position 0 on the same rows (nothing is reset: rows are read
    only up to the position) while lane 0 is parked at the arena's last row —
    the tokens' logits are the reference's, and lane 0's rows below the last
    stay zero."""
    params, tokens, want = case
    other = jax.random.randint(jax.random.PRNGKey(9), (60,), 3, CFG.vocab_size)
    _, cache = cached(params, other, (32,), lanes=2, lane=1)
    got, cache = cached(params, tokens[:50], (20, 20), lanes=2, lane=1, cache=cache)
    assert rel(got, want[:50]) < TOL
    assert not np.asarray(cache.latent[:, 0, :-1]).any()


def _keep_the_key(x, positions, inv_freq, real=ref.rotate):
    return x if x.shape[1] == 1 else real(x, positions, inv_freq)


def _split_halves(x, positions, inv_freq):
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _unnormalised(logits, top_k):
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)


_yarn = ref.yarn_inv_freq
OMISSIONS = {
    "no_q_a_norm": ("query_latent", lambda x, lp, eps, act: act(x) @ lp["wq_a"]),
    "k_r_not_rotated": ("rotate", _keep_the_key),
    "split_halves": ("rotate", _split_halves),
    "no_yarn_ramp": ("yarn_inv_freq", lambda d, theta, factor, *rest: _yarn(d, theta, 1.0, *rest)),
    "every_pair_interpolated": (
        "yarn_inv_freq", lambda d, theta, factor, *rest: _yarn(d, theta, 1.0, *rest) / factor),
    "no_mscale": ("softmax_mscale", lambda factor, all_dim: 1.0),
    "no_query_scale": ("query_scale", lambda positions, beta, original: jnp.ones(positions.shape, jnp.float32)),
    "no_shared_expert": ("shared_expert", lambda x, lp, act: jnp.zeros_like(x)),
    "no_renormalisation": ("gates", _unnormalised),
}


@pytest.mark.parametrize("name", sorted(OMISSIONS))
def test_program_fails_a_reference_that_omits(case, name):
    """The check is not blind: against a reference with one step of the block
    left out or done another way, the same program is far off."""
    params, tokens, _ = case
    attr, wrong = OMISSIONS[name]
    with mock.patch.object(ref, attr, wrong):
        other = reference_logits(params, CFG, tokens)
    assert rel(RUN.full(params, tokens[None], jnp.arange(N_TOKENS)[None])[0], other) > WRONG


# -- the mechanisms one by one ---------------------------------------------------


def test_yarn_frequencies_at_the_published_sizes_are_the_references():
    big = get_config("mistral-small-4-119b")
    got = np.asarray(yarn_frequencies(64, big.rope_theta, big.rope_factor, big.rope_original_max, 32.0, 1.0))
    want = np.asarray(ref.yarn_inv_freq(64, 10_000.0, 128.0, 8192, 32.0, 1.0))
    np.testing.assert_array_equal(got, want)
    plain = 10_000.0 ** (-np.arange(0, 64, 2) / 64)
    # the pairs that turn 32 times or more over 8,192 positions are kept, the
    # slowest are divided by the factor, and the ramp between them is monotone
    assert np.allclose(got[:12], plain[:12], rtol=1e-6) and np.allclose(got[-6:], plain[-6:] / 128, rtol=1e-6)
    assert (np.diff(plain / got) > -1e-4).all()
    assert big.softmax_mscale == pytest.approx(1.4852, abs=1e-4)


def test_adjacent_pairs_rotate_as_the_reference_and_keep_the_distance():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 7, 3, 16))
    pos = jnp.asarray([[0, 1, 31, 32, 33, 200, 16_383]])
    inv = yarn_frequencies(16, 10_000.0, 8.0, 32)
    got = apply_rope(x, pos, 10_000.0, interleave=True, inv_freq=inv)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref.rotate(x[0], pos[0], inv)), atol=1e-6)
    # q(p) . k(s) depends on p - s alone
    q, k = x[:, :1, :1], x[:, 1:2, :1]
    dots = [
        float(jnp.sum(apply_rope(q, jnp.full((1, 1), p), 1e4, interleave=True, inv_freq=inv)
                      * apply_rope(k, jnp.full((1, 1), p - 5), 1e4, interleave=True, inv_freq=inv)))
        for p in (5, 40, 900)
    ]
    assert dots[0] == pytest.approx(dots[1], abs=1e-4) and dots[0] == pytest.approx(dots[2], abs=1e-3)


def test_absorbed_mla_is_expanded_mla_with_a_rotated_shared_key():
    """The cached row holds ``[c, rope(k_r, s)]`` and the query's last r dims
    are ``rope(q_r, p)``: absorbed against expanded at a latent width that is
    not Kimi-Linear's, with the softmax scale's ``m²``."""
    rng = np.random.default_rng(1)
    b, t, s, h, rank, nope, r, dv = 2, 9, 70, 4, 32, 16, 16, 16
    inv = yarn_frequencies(r, 10_000.0, 8.0, 32)
    c = jnp.asarray(rng.normal(size=(b, s, rank)), jnp.float32)
    k_r = apply_rope(jnp.asarray(rng.normal(size=(b, s, 1, r)), jnp.float32), jnp.arange(s)[None], 1e4,
                     interleave=True, inv_freq=inv)[:, :, 0]
    rows = jnp.concatenate([c, k_r], -1)
    pos = jnp.asarray(rng.integers(4, s, size=(b, t)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, t, h, nope + r)), jnp.float32)
    q = jnp.concatenate([q[..., :nope], apply_rope(q[..., nope:], pos, 1e4, interleave=True, inv_freq=inv)], -1)
    w_kvb = jnp.asarray(rng.normal(size=(rank, h, nope + dv)), jnp.float32) * 0.3
    scale = (nope + r) ** -0.5 * CFG.softmax_mscale**2
    o_lat = mla.attend(mla.absorb_query(q, w_kvb, nope), rows, pos, scale, rank)
    absorbed = jnp.einsum("bthr,rhv->bthv", o_lat, w_kvb[..., nope:])
    want = mla.expanded(q, rows, pos, w_kvb, scale, rank, nope)
    assert float(jnp.abs(absorbed - want).max()) < 1e-4 * float(jnp.abs(want).max())


def test_pallas_kernels_serve_a_latent_width_that_is_not_kimis():
    """Interpret mode, at Mistral-Small-4's row (256 + 64 values stored as
    384): ``mla_decode`` at ragged positions and ``mla_prefill`` over a chunk
    in mid-row, against ``mla.attend`` over the lane's sliced row."""
    from agentainer_tpu.ops.pallas_mla import decode_block_rows, mla_decode, mla_prefill

    rng = np.random.default_rng(0)
    big = get_config("mistral-small-4-119b")
    width, rank, live = hybrid.latent_width(big), big.mla_kv_rank, big.mla_kv_rank + big.mla_rope_dim
    assert (width, rank, live) == (384, 256, 320)
    heads, s, b = 4, 300, 3
    stack = jnp.asarray(rng.normal(size=(2, b, s, width)), jnp.float32).at[..., live:].set(0.0)
    qd = jnp.asarray(rng.normal(size=(b, heads, width)), jnp.float32).at[..., live:].set(0.0)
    pos = jnp.asarray([5, 299, 130], jnp.int32)
    want = mla.attend(qd[:, None], stack[1], pos[:, None], 0.2, rank)[:, 0]
    got = mla_decode(qd, stack, pos, 1, 0, scale=0.2, rank=rank, block_k=128, interpret=True)
    assert got.shape == (b, heads, rank) and float(jnp.abs(got - want).max()) < 1e-5
    t, start, lane = 64, 100, 2
    qp = jnp.asarray(rng.normal(size=(1, t, heads, width)), jnp.float32).at[..., live:].set(0.0)
    ppos = jnp.asarray(start + np.arange(t), jnp.int32)[None]
    want = mla.attend(qp, stack[1, lane : lane + 1], ppos, 0.2, rank)
    got = mla_prefill(qp, stack, ppos, 1, lane, scale=0.2, rank=rank, block_q=16, block_k=128, interpret=True)
    assert got.shape == (1, t, heads, rank) and float(jnp.abs(got - want).max()) < 1e-5
    # the block the engine's fetch count divides a position by, at the served sizes
    assert decode_block_rows(width, 2, 16_384) == 512


def test_the_softmax_router_rule_is_the_references():
    logits = jax.random.normal(jax.random.PRNGKey(0), (40, CFG.n_experts)) * 3.0
    g, chosen = llama.moe_gates(logits, CFG, jnp.float32)
    g_ref, chosen_ref = ref.gates(logits, CFG.experts_per_token)
    assert np.array_equal(np.asarray(chosen), np.asarray(chosen_ref))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g.sum(-1)), 1.0, rtol=1e-5)


@pytest.mark.parametrize("path", ["einsum", "sorted"])
def test_shares_add_up_to_the_uncut_layer(path):
    """Expert parallelism without the exchange (ep = 4): 4 chips hold 2 of the
    8 experts each, every chip routes over all 8 and computes its own experts'
    terms. The routed parts of all four shares plus the shared expert ONCE
    equal the uncut reference layer — through both of the program's paths."""
    params = sharp_params(CFG)
    lp = {k: v[0] for k, v in params["moe"].items()}
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 40, CFG.dim), jnp.float32)
    want = ref.moe(h[0], lp, CFG.experts_per_token, 0, lambda x: x)
    total = llama._mlp(h, {"w" + k[2:]: v for k, v in lp.items() if k.startswith("ws_")})[0]
    for chip in range(4):
        share = dataclasses.replace(CFG, experts_held=2, expert_offset=2 * chip)
        mine = {k: (v[2 * chip : 2 * chip + 2] if k in ("w_gate", "w_up", "w_down") else v) for k, v in lp.items()}
        if path == "einsum":
            part = llama._moe_mlp(h, mine, share)[0]
        else:
            experts = stacked_experts({k: v[None] for k, v in mine.items()})
            part = llama._moe_mlp_sorted(h, mine, share, experts, jnp.int32(0))[0]
        # a share alone is the reference's share: the same held range, nothing standing in for the rest
        routed = {k: v for k, v in mine.items() if not k.startswith("ws_")}
        assert rel_all(part, ref.moe(h[0], routed, CFG.experts_per_token, 2 * chip, lambda x: x)) < TOL
        total = total + part
    assert rel(total, want) < TOL


def test_a_chips_share_of_the_whole_model_is_the_references_share(case):
    """``experts_held`` 2 of 8 at offset 4 through ``forward`` (the shared
    expert whole, the routed sum over the held two) against the reference
    given the same two experts."""
    params, tokens, _ = case
    share = dataclasses.replace(CFG, experts_held=2, expert_offset=4)
    mine = dict(params)
    mine["moe"] = {k: (v[:, 4:6] if k in ("w_gate", "w_up", "w_down") else v) for k, v in params["moe"].items()}
    got = forward(mine, share, tokens[None], jnp.arange(N_TOKENS)[None])[0][0]
    assert rel(got, reference_logits(mine, share, tokens)) < TOL


def test_param_count_is_the_pytrees_size_and_the_published_models():
    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    assert sum(x.size for x in jax.tree.leaves(params)) == CFG.param_count()
    assert "router_bias" not in params["moe"] and "wq" not in params["mla"]
    big = get_config("mistral-small-4-119b")
    assert round(big.param_count() / 1e9, 1) == 119.0  # as published
    assert 6.0e9 < big.active_param_count() < 7.0e9  # 6.6 B active
    held = dataclasses.replace(big, n_layers=9, layer_kinds=("mla",) * 9, experts_held=32)
    assert 8.7e9 < held.param_count() < 8.9e9  # one chip's share: 9 layers, 32 experts, the whole vocabulary


@pytest.mark.parametrize(
    "field, value, message",
    [("rope_original_max", 0, "rope_original_max"), ("mla_rope_dim", 15, "mla_rope_dim"), ("rope_theta", 0.0, "rope_theta")],
)
def test_a_configuration_that_cannot_be_served_is_refused_when_built(field, value, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **{field: value})


def test_the_new_parts_of_the_layer_body_are_named_in_the_lowered_step(case):
    """``jax.named_scope``s the device trace and ``/profile`` show: the
    query's low-rank pair, the rotation, the shared expert."""
    params, tokens, _ = case
    cache = init_cache(CFG, 1, 64, dtype=jnp.float32)
    lowered = RUN.step.lower(params, cache, tokens[None, :1], jnp.zeros((1, 1), jnp.int32))
    text = lowered.as_text(debug_info=True)
    for scope in ("mla_q_lora", "mla_rope", "moe_shared_expert"):
        assert scope in text, scope


def test_the_int8_serving_mode_quantises_the_two_low_rank_leaves_and_keeps_the_norms_dense():
    from agentainer_tpu.engine.quant import quantize_params, synthetic_quantized_params
    from agentainer_tpu.ops.quant import QTensor

    for params in (synthetic_quantized_params(CFG, jnp.float32), quantize_params(jax.device_get(sharp_params(CFG)), jnp.float32)):
        m = params["mla"]
        assert all(isinstance(m[k], QTensor) for k in ("wq_a", "wq_b", "wkva", "wkvb", "wo"))
        assert not isinstance(m["q_norm"], QTensor) and m["q_norm"].shape == (CFG.n_layers, CFG.mla_q_rank)
        logits = forward(params, CFG, jnp.arange(3, 43)[None], jnp.arange(40)[None])[0]
        assert bool(jnp.isfinite(logits).all())


# -- the cache manager on latent rows alone -----------------------------------------

ENGINE = {"max_batch": 2, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 32}
LONG = "a document that runs well past the original context of thirty-two positions, and then some more. "
TURNS = [(LONG, 30), ("and a second turn", 9), ("a third", 7)]


def make_engine(**over):
    from agentainer_tpu.engine.llm import LLMEngine

    return LLMEngine.create("tiny-mistral4", options={**ENGINE, **over})


async def chat_all(eng, session="s", turns=TURNS):
    return [(await eng.chat(session, text, max_tokens=n))["tokens"] for text, n in turns]


@pytest.fixture(scope="module")
def uninterrupted():
    eng = make_engine()
    try:
        out = asyncio.run(chat_all(eng))
        return out, eng.metrics()
    finally:
        eng.shutdown()


def test_engine_tokens_are_the_plain_greedy_decode(uninterrupted):
    """Three turns through the engine (bucketed chunked prefill, pipelined
    decode chunks, the last token of a reply held out and fed with the next
    prompt), the context past the original one from the first turn on: the
    tokens a plain loop over ``forward`` gives."""
    tokens, _ = uninterrupted
    eng = make_engine(skip_warmup=True)
    try:
        params, tok = eng.params, eng.tokenizer
    finally:
        eng.shutdown()
    cache = init_cache(CFG, 1, 256, dtype=jnp.float32)
    pos, pending, got = 0, [], []
    for (text, n), want in zip(TURNS, tokens):
        feed = pending + tok.encode(text)
        logits, cache = forward(params, CFG, jnp.asarray([feed], jnp.int32), (pos + jnp.arange(len(feed)))[None], cache)
        pos += len(feed)
        out = [int(jnp.argmax(logits[0, -1]))]
        while len(out) < n:
            logits, cache = RUN.step(params, cache, jnp.asarray([[out[-1]]], jnp.int32), jnp.full((1, 1), pos))
            pos += 1
            out.append(int(jnp.argmax(logits[0, 0])))
        pending = [out[-1]]  # sampled, never fed: it leads the next turn's prompt
        got.append(out)
    assert pos > 3 * ORIGINAL
    assert got == tokens


def test_kill_and_resume_is_token_identical(uninterrupted):
    """The signature flow: snapshot after each turn (the latent rows up to the
    position's bucket, nothing else), kill, restore into a new engine, go on —
    the same tokens as never stopping."""

    async def interrupted():
        out, blob = [], None
        for text, n in TURNS:
            eng = make_engine()
            try:
                if blob is not None:
                    assert await eng.restore_session("s", blob) is True
                out.append((await eng.chat("s", text, max_tokens=n))["tokens"])
                assert eng.slots[eng.sessions["s"]].position > ORIGINAL
                blob = await eng.snapshot_session("s")
                assert blob is not None
            finally:
                eng.shutdown()  # the crash
        return out

    assert asyncio.run(interrupted()) == uninterrupted[0]


def test_evicted_session_comes_back_token_identical_and_a_reused_lane_serves_a_fresh_one(uninterrupted):
    """One lane: session ``s`` is snapshotted, evicted by another session
    taking its lane (whose rows are full of s's: its tokens are those of a
    fresh engine), then restored into the lane it lost."""

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
    assert tokens == uninterrupted[0]
    assert other == asyncio.run(fresh())
    assert cache["state_restores"] == 1 and cache["state_snapshots"] == 1 and cache["state_resets"] == 0


def test_a_parked_lane_keeps_its_rows_while_another_decodes():
    """Session ``a`` finishes and its lane parks; ``b`` then prefills and
    decodes well over 32 steps in the lane beside it, the pipelined chunks
    stepping every lane each time: lane a's live rows are bit-identical
    afterwards, and a's next turn is what it is alone."""

    async def run(with_b: bool):
        eng = make_engine(max_batch=3)
        try:
            await eng.chat("a", "the first session says a few words", max_tokens=13)
            lane = eng.sessions["a"]
            n = eng.slots[lane].position
            before = np.asarray(eng.cache.latent[:, lane, :n])
            if with_b:
                await eng.chat("b", "the second session talks for much longer than the first did", max_tokens=60)
            after = np.asarray(eng.cache.latent[:, lane, :n])
            return before, after, (await eng.chat("a", "and goes on", max_tokens=9))["tokens"]
        finally:
            eng.shutdown()

    before, after, tokens = asyncio.run(run(True))
    assert np.array_equal(before, after)
    assert tokens == asyncio.run(run(False))[2]


@pytest.mark.parametrize("option", ["speculative", "paged_kv", "kv_tiering", "fused_decode", "prefix_cache"])
def test_a_feature_the_latent_leaf_is_not_taken_through_is_an_error_when_asked_for(option):
    """Off by default with its TRUE reason reported (none of them a recurrent
    state's: this cache has none); asked for by name, refused at build."""
    with pytest.raises(ValueError, match=option) as e:
        make_engine(skip_warmup=True, **{option: True})
    assert "recurrent" not in str(e.value) and "latent" in str(e.value)


def test_cache_off_has_a_table_for_each_kind_of_cache():
    from agentainer_tpu.engine import llm

    assert llm._cache_off(CFG)[0] is llm._LATENT_OFF
    assert llm._cache_off(get_config("mistral-small-4-119b"))[0] is llm._LATENT_OFF
    for name in ("tiny-kimi-linear", "kimi-linear-48b", "tiny-olmo-hybrid", "olmo-hybrid-7b"):
        assert llm._cache_off(get_config(name))[0] is llm._RECURRENT_OFF
    assert llm._cache_off(get_config("tiny-smallthinker"))[0] is llm._WINDOW_OFF
    assert llm._cache_off(get_config("tiny-moe")) == (None, "")
    assert set(llm._LATENT_OFF) == set(llm._RECURRENT_OFF) == set(llm._WINDOW_OFF)
    assert not any("recurrent" in why or "state" in why.split("per-lane")[0] for why in llm._LATENT_OFF.values())
    assert not llm.fleet_default_applies("tiny-mistral4", "kv_tiering")


def test_metrics_name_the_latent_leaf_the_counters_the_rotary_kind_and_what_is_off(uninterrupted):
    _, m = uninterrupted
    cache, a = m["cache"], m["attention"]
    assert cache["kinds"] == ["latent"]
    assert cache["latent_bytes"] == CFG.n_layers * 2 * 256 * hybrid.latent_width(CFG) * 4  # float32 on the CPU
    assert cache["bytes_per_lane"] * 2 == cache["latent_bytes"] == m["kv_arena_bytes"] - 16
    assert set(cache["off"]) == {"speculative", "prefix_cache", "paged_kv", "fused_decode", "kv_tiering", "mesh"}
    assert all(len(reason) > 20 and "recurrent" not in reason for reason in cache["off"].values())
    assert cache["state_resets"] == 0
    assert m["speculative"] is False and m["prefix_cache"] is False
    assert m["model_arch"]["layer_kinds"] == {"mla": 3} and m["model_arch"]["dense_layers"] == 0
    assert "kda_decode" not in a and a["mla_decode"] == a["decode"] == "xla_absorbed"
    assert "yarn x8 past 32" in a["mla_rotary"] and "adjacent pairs" in a["mla_rotary"]
    on_chip = hybrid.plan_hybrid(get_config("mistral-small-4-119b"), use_pallas=True).describe()
    assert on_chip["mla_prefill"] == on_chip["prefill"] == "pallas_mla_prefill" and on_chip["arena"] == "stack+layer"
    assert "yarn x128 past 8192" in on_chip["mla_rotary"]
    # counted at every decode launch: what mla_decode's index map fetches against what the arena stores
    assert a["latent_block_positions"] == 256  # an arena of 256 rows is one block
    assert 0 < a["latent_decode_blocks_live"] == a["latent_decode_blocks_stored"]
    # every prefill row and decode step, and those at or past the original context
    assert a["rope_original_max"] == ORIGINAL
    assert 0 < a["rows_past_original_max"] < a["rows_positioned"]
    assert m["moe"]["experts_held"] == 8 and m["moe"]["shared_experts"] == 1 and m["moe"]["router"] == "softmax"


def test_kimi_linears_metrics_gain_the_latent_count_and_no_rotary_kind():
    from agentainer_tpu.engine.llm import LLMEngine

    eng = LLMEngine.create("tiny-kimi-linear", options={**ENGINE, "skip_warmup": True})
    try:
        a = eng.metrics()["attention"]
    finally:
        eng.shutdown()
    assert "mla_rotary" not in a and "rows_positioned" not in a and a["latent_decode_blocks_stored"] == 0
