"""The hybrid block's second positional kind (``models/hybrid.py``: full
attention beside sliding-window attention, each kind with its own stacks and
count of query heads over shared K/V heads, a sigmoid gate a head, a partial
YaRN rotation against plain RoPE, the window layers' rows a ring beside ``k``
and ``v`` in ``HybridCache``; a dense first layer, a softmax router scaled by
2.5, a shared expert, held experts) against the benchmark's plain float32
reference (``benchmark/families/laguna_reference.py``, which imports nothing
of the program), on the CPU with ``tiny-laguna`` (two periods F S S S, 6 and 8
query heads over 2 K/V heads of 16, a window of 16, an original context of 32
stretched 8 times, half a head rotated, 8 experts top-2 x 2.5 beside a shared
one) and seeded weights — and the cache manager's moves on the ring.

Weights are NOT the 0.02-std init (``tests/test_kimi_linear.py`` says why):
the projections are scaled until the window, both rotations, the gate and the
router's scale each move the logits by several per cent at least.

Tolerance: both sides compute in float32 and differ by the order of
summation; the rms difference over the logits' standard deviation stays under
1e-3 in the median over positions and under 5e-3 at the WORST position (as
``tests/test_smallthinker.py``); every wrong block has to read over 2e-2 in
the median.
"""

import asyncio
import dataclasses
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.engine.llm import _WINDOW_OFF, LLMEngine, cache_features
from agentainer_tpu.models import hybrid
from agentainer_tpu.models.configs import get_config
from agentainer_tpu.models.llama import forward, init_cache, init_params
from agentainer_tpu.ops.rope import apply_rope, yarn_frequencies

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL, WORST, WRONG = 1e-3, 5e-3, 2e-2
CFG = get_config("tiny-laguna")
W = CFG.window
N_TOKENS = 100


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "laguna_reference", os.path.join(REPO, "benchmark", "families", "laguna_reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()


def sharp_params(cfg, seed=3):
    """Seeded float32 weights under which the block's own steps matter."""
    p = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    scale = {"wq": 15.0, "wk": 15.0, "wv": 10.0, "wo": 5.0, "wg": 40.0, "router": 30.0, "w_gate": 10.0,
             "w_down": 20.0, "ws_gate": 10.0, "ws_down": 20.0}
    out = {g: ({k: a * scale.get(k, 1.0) for k, a in v.items()} if isinstance(v, dict) else v) for g, v in p.items()}
    out["lm_head"] = p["lm_head"] * 10.0
    return out


def reference_weights(params, cfg):
    layers, seen = [], {"full": 0, "swa": 0, "dense": 0, "moe": 0}
    for i, kind in enumerate(cfg.layer_kinds):
        ffn = "dense" if i < cfg.n_dense_layers else "moe"
        layers.append({k: v[j] for g, j in (("layers", i), (kind, seen[kind]), (ffn, seen[ffn])) for k, v in params[g].items()})
        seen[kind] += 1
        seen[ffn] += 1
    return {"embed": params["embed"], "layers": layers, "final_norm": params["final_norm"], "lm_head": params["lm_head"]}


def reference_kw(cfg):
    return dict(
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token,
        layer_types=tuple("full" if k == "full" else "sliding" for k in cfg.layer_kinds), window=cfg.window,
        full_rope=dict(theta=cfg.rope_theta, factor=cfg.rope_factor, original_max=cfg.rope_original_max,
                       beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
                       attention_factor=cfg.rope_attention_factor, rotary_dim=cfg.rotary_dim),
        sliding_theta=cfg.swa_rope_theta, routed_scale=cfg.moe_scale, expert_offset=cfg.expert_offset,
    )


def reference_logits(params, cfg, tokens, **over):
    kw = reference_kw(cfg)
    kw["full_rope"] = {**kw["full_rope"], **over.pop("full_rope", {})}
    return ref.forward(reference_weights(params, cfg), tokens, **{**kw, **over})


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.median(np.sqrt(np.mean((got - want) ** 2, -1)) / np.std(want, -1)))


def worst(got, want):
    """The largest per-position error over ``WORST / TOL``, so that it reads
    against ``TOL``: a ring that loses one row wrongs only the queries that
    saw it."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.sqrt(np.mean((got - want) ** 2, -1)) / np.std(want, -1))) * TOL / WORST


class Runner:
    """``forward`` under ``jax.jit``, one program a call shape
    (``tests/test_smallthinker.py`` says why)."""

    def __init__(self, cfg):
        self.full = jax.jit(lambda p, t, q: forward(p, cfg, t, q)[0])
        self.chunk = jax.jit(lambda p, c, t, q, slot: forward(p, cfg, t, q, c, slot=slot))
        self.step = jax.jit(lambda p, c, t, q: forward(p, cfg, t, q, c))
        self.mixed = jax.jit(lambda p, c, t, q, slot, lanes, last: forward(p, cfg, t, q, c, slot=slot, lanes=lanes, last=last))


RUN = Runner(CFG)


@pytest.fixture(scope="module")
def case():
    params = sharp_params(CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (N_TOKENS,), 3, CFG.vocab_size)
    return params, tokens, reference_logits(params, CFG, tokens)


def cached(params, tokens, chunks, launch_rows, lanes=2, lane=1, cache=None, arena=128):
    """Prefill ``tokens`` in ``chunks`` at arena row ``lane``, then decode the
    rest a token a step beside a parked lane; the logits of every position."""
    if cache is None:
        cache = init_cache(CFG, lanes, arena, dtype=jnp.float32, launch_rows=launch_rows)
    outs, at = [], 0
    for n in chunks:
        logits, cache = RUN.chunk(params, cache, tokens[None, at : at + n], (at + jnp.arange(n))[None], jnp.int32(lane))
        outs.append(logits[0])
        at += n
    parked = cache.k.shape[2] - 1
    for i in range(at, len(tokens)):
        tok = jnp.zeros((lanes, 1), jnp.int32).at[lane, 0].set(tokens[i])
        pos = jnp.full((lanes, 1), parked, jnp.int32).at[lane, 0].set(i)
        logits, cache = RUN.step(params, cache, tok, pos)
        outs.append(logits[lane])
    return jnp.concatenate(outs, 0), cache


def test_the_tiny_configuration_has_the_published_ratios():
    big = get_config("laguna-xs.2")
    for cfg in (CFG, big):
        assert cfg.layer_kinds[:5] == ("full", "swa", "swa", "swa", "full") and cfg.n_window == 3 * cfg.n_positional
        assert cfg.window_heads > cfg.n_heads and cfg.window_heads % cfg.n_kv_heads == cfg.n_heads % cfg.n_kv_heads == 0
        assert cfg.rotary_dim * 2 == cfg.head_dim and cfg.rope_factor > 1 and cfg.attn_gate and cfg.swa_rope_theta
        assert abs(cfg.rope_attention_factor - (0.1 * np.log(cfg.rope_factor) + 1)) < 1e-6
        assert cfg.n_dense_layers == 1 and cfg.n_shared_experts == 1 and cfg.moe_scale == 2.5 and cfg.moe_router == "softmax"
    assert (big.n_heads, big.window_heads, big.n_kv_heads, big.head_dim, big.window) == (48, 64, 8, 128, 512)
    # the published model but for its norm vectors: 33,442,430,976 matrices' elements
    assert big.param_count() == 33_442_430_976 + (2 * 40 + 1) * 2048
    assert big.active_param_count() == big.param_count() - 39 * 248 * 3 * 2048 * 512
    assert 2.9e9 < big.active_param_count() < 3.1e9  # "33.4B-A3B"
    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    assert sum(a.size for a in jax.tree.leaves(params)) == CFG.param_count()
    assert params["full"]["wq"].shape == (2, 64, 6 * 16) and params["swa"]["wq"].shape == (6, 64, 8 * 16)
    assert params["full"]["wg"].shape == (2, 64, 6) and params["swa"]["wg"].shape == (6, 64, 8)


def test_full_forward_matches_the_plain_reference(case):
    params, tokens, want = case
    got = RUN.full(params, tokens[None], jnp.arange(N_TOKENS)[None])[0]
    assert rel(got, want) < TOL and worst(got, want) < TOL


CACHED = {
    # (chunks, launch_rows): the ring is window + launch_rows rows of a 128-row arena
    "ring_never_wraps": ((50,), None),
    "wraps_between_chunks": ((8,) * 8, 8),  # R = 24: the fourth chunk starts at row 0 again
    "wraps_mid_chunk": ((7,) * 9, 8),  # 21..27 crosses the ring's end
    "wraps_mid_decode": ((8, 8), 8),  # 16 prefilled, position 24 is a decode step's
    "one_token_chunks": ((1,) * 30, 1),  # R = 17: the tightest ring
    "ragged": ((5, 8, 3, 8, 8, 1, 6), 8),
}


@pytest.mark.parametrize("name", sorted(CACHED))
def test_cached_prefill_and_decode_match_the_reference_full_forward(case, name):
    """Prefill in chunks through the cache and decode the rest beside a parked
    lane, with the ring shorter than the context and the window shorter than
    the prefill: every position's logits are the reference's full forward's."""
    params, tokens, want = case
    chunks, launch_rows = CACHED[name]
    got, cache = cached(params, tokens, chunks, launch_rows)
    assert cache.wk.shape[2] == (128 if launch_rows is None else W + launch_rows) and cache.k.shape[2] == 128
    assert rel(got, want) < TOL and worst(got, want) < TOL


def test_a_launch_longer_than_the_ring_was_sized_for_is_refused(case):
    params, tokens, _ = case
    cache = init_cache(CFG, 1, 128, dtype=jnp.float32, launch_rows=8)
    with pytest.raises(ValueError, match="ring"):
        forward(params, CFG, tokens[None, :10], jnp.arange(10)[None], cache, slot=jnp.int32(0))


def test_a_parked_lane_writes_nowhere_in_a_live_lanes_ring_or_its_own(case):
    """Lane 0 finished at 40 and parked; lane 1 then decodes beside it, every
    launch carrying lane 0's row at the parked position. Lane 0's ring is
    bit-identical, and going on from 40 gives the reference's logits."""
    params, tokens, want = case
    _, cache = cached(params, tokens[:40], (8,) * 5, 8, lanes=2, lane=0)
    before = [np.array(cache.wk[:, 0]), np.array(cache.wv[:, 0])]
    _, cache = cached(params, tokens[:60], (), 8, lanes=2, lane=1, cache=cache)
    assert np.array_equal(before[0], np.array(cache.wk[:, 0])) and np.array_equal(before[1], np.array(cache.wv[:, 0]))
    tok = jnp.zeros((2, 1), jnp.int32).at[0, 0].set(tokens[40])
    step, _ = RUN.step(params, cache, tok, jnp.asarray([[40], [60]], jnp.int32))
    assert worst(step[0], want[40][None]) < TOL


@pytest.mark.parametrize("first", ["chunk", "step"])
def test_the_mixed_step_is_a_chunk_and_then_a_step_bit_for_bit(case, first):
    """One launch with a chunk at lane 2 and a step of every lane against the
    two launches it replaces, either group first: the logits of both groups
    and every leaf of the cache are bitwise equal (lane 0 decodes past the
    ring's length, lane 1 is parked, lane 2 — the chunk's — is parked too)."""
    params, tokens, _ = case
    other = jax.random.randint(jax.random.PRNGKey(9), (21,), 3, CFG.vocab_size)
    _, cache = cached(params, tokens[:40], (8,) * 5, 8, lanes=3, lane=0)
    _, cache = cached(params, other[:14], (7, 7), 8, lanes=3, lane=2, cache=cache)
    parked = cache.k.shape[2] - 1
    chunk, chunk_pos = other[None, 14:21], (14 + jnp.arange(7))[None]
    lane_tok = jnp.asarray([[tokens[40]], [0], [0]], jnp.int32)
    lane_pos = jnp.asarray([[40], [parked], [parked]], jnp.int32)
    mixed, mixed_cache = RUN.mixed(params, cache, chunk, chunk_pos, jnp.int32(2), (lane_tok, lane_pos), jnp.int32(6))
    if first == "chunk":
        a, c = RUN.chunk(params, cache, chunk, chunk_pos, jnp.int32(2))
        b, c = RUN.step(params, c, lane_tok, lane_pos)
    else:
        b, c = RUN.step(params, cache, lane_tok, lane_pos)
        a, c = RUN.chunk(params, c, chunk, chunk_pos, jnp.int32(2))
    assert np.array_equal(np.asarray(mixed[0]), np.asarray(a[0, 6]))
    assert np.array_equal(np.asarray(mixed[1]), np.asarray(b[0, 0]))
    for name, leaf in mixed_cache.leaves().items():
        x, y = np.array(leaf), np.array(getattr(c, name))
        if name in ("k", "v"):  # the last row is where parked lanes write: nobody's
            x[:, :, -1] = y[:, :, -1] = 0
        assert np.array_equal(x, y), name


def test_the_gate_is_one_sigmoid_a_head_of_the_normed_input(case):
    """``full_mixer``'s output against a hand-written line: with ``wo`` the
    identity over the heads' concatenation, the gated output over the ungated
    one is ``sigmoid(h W_g)`` a head, whatever the head's values."""
    params, _, _ = case
    cfg = dataclasses.replace(CFG, dim=CFG.n_heads * CFG.head_dim)  # wo square, so it can be the identity
    d = cfg.dim
    key = jax.random.split(jax.random.PRNGKey(2), 5)
    lp = {"wq": jax.random.normal(key[0], (d, d)) * 0.1, "wk": jax.random.normal(key[1], (d, 2 * 16)) * 0.1,
          "wv": jax.random.normal(key[2], (d, 2 * 16)), "wo": jnp.eye(d), "wg": jax.random.normal(key[3], (d, cfg.n_heads))}
    h = jax.random.normal(key[4], (1, 9, d))
    cache = hybrid.init_cache(cfg, 1, 16, jnp.float32)
    plan = hybrid.plan_hybrid(cfg, use_pallas=False)
    args = (cache.k[:1], cache.v[:1], 0, None, jnp.arange(9)[None], jnp.ones((1, 9), bool), plan)
    gated = hybrid.full_mixer(h, lp, cfg, *args)[0]
    plain = hybrid.full_mixer(h, lp, dataclasses.replace(cfg, attn_gate=False), *args)[0]
    want = jax.nn.sigmoid(h @ lp["wg"])  # [1, 9, heads]
    ratio = (gated / plain).reshape(1, 9, cfg.n_heads, cfg.head_dim)
    np.testing.assert_allclose(ratio, np.broadcast_to(want[..., None], ratio.shape), rtol=2e-4)


def test_the_partial_yarn_rotation_against_a_hand_written_line():
    """The first ``r`` dims of a head rotated in rotate-half pairs (i, i +
    r/2) by YaRN's frequencies for an ``r``-wide head, cos and sin both times
    the factor; the dims after them untouched."""
    r, factor = CFG.rotary_dim, CFG.rope_attention_factor
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 3, CFG.head_dim))
    pos = jnp.arange(30, 70)[None]
    got = hybrid._attn_rotate(x, pos, CFG, "full")
    inv = yarn_frequencies(r, CFG.rope_theta, CFG.rope_factor, CFG.rope_original_max, CFG.rope_beta_fast, CFG.rope_beta_slow)
    ang = np.asarray(pos[0], np.float64)[:, None] * np.asarray(inv, np.float64)[None]  # [T, r/2]
    cos, sin = factor * np.cos(ang)[None, :, None], factor * np.sin(ang)[None, :, None]
    xs = np.asarray(x, np.float64)
    x1, x2 = xs[..., : r // 2], xs[..., r // 2 : r]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, xs[..., r:]], -1)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the frequencies: the fastest pair kept, the slowest divided by the factor, a ramp between
    plain = CFG.rope_theta ** (-np.arange(0, r, 2) / r)
    assert np.isclose(inv[0], plain[0]) and np.isclose(inv[-1], plain[-1] / CFG.rope_factor)
    assert any(plain[i] / CFG.rope_factor * 1.001 < inv[i] < plain[i] / 1.001 for i in range(r // 2))
    # a sliding layer: plain RoPE over the whole head, no factor
    np.testing.assert_array_equal(hybrid._attn_rotate(x, pos, CFG, "swa"), apply_rope(x, pos, CFG.swa_rope_theta))
    # a product of two rotated halves takes the factor squared and depends on the distance alone
    q, k = got[0, 5, 0, :r], hybrid._attn_rotate(x, pos + 7, CFG, "full")[0, 5, 0, :r]
    q2, k2 = (hybrid._attn_rotate(x, pos + 11, CFG, "full")[0, 5, 0, :r], hybrid._attn_rotate(x, pos + 18, CFG, "full")[0, 5, 0, :r])
    assert np.isclose(float(q @ k), float(q2 @ k2), rtol=1e-4)


WRONG_BLOCKS = {
    "no_gate": dict(patch=("attention", dict(gated=False))),
    "window_one_short": dict(window=W - 1),
    "no_window": dict(layer_types=("full",) * CFG.n_layers),
    "whole_head_rotated": dict(full_rope=dict(rotary_dim=CFG.head_dim)),
    "no_attention_factor": dict(full_rope=dict(attention_factor=1.0)),
    "plain_rope_in_full_layers": dict(full_rope=dict(factor=1.0)),
    "sliding_theta_is_the_full_layers": dict(sliding_theta=CFG.rope_theta),
    "gates_not_scaled": dict(routed_scale=1.0),
    "no_shared_expert": dict(drop=("ws_down",)),
}


@pytest.mark.parametrize("name", sorted(WRONG_BLOCKS))
def test_program_fails_a_reference_of_another_block(case, name, monkeypatch):
    """Each equation of the block is load-bearing under these weights: the
    program against a reference with one of them changed reads over 2e-2."""
    params, tokens, _ = case
    over = dict(WRONG_BLOCKS[name])
    got = RUN.full(params, tokens[None], jnp.arange(N_TOKENS)[None])[0]
    if "patch" in over:
        fn_name, kw = over.pop("patch")
        real = getattr(ref, fn_name)
        monkeypatch.setattr(ref, fn_name, lambda *a, **k: real(*a, **{**k, **kw}))
    if "drop" in over:
        params = {**params, "moe": {k: (v * 0 if k in over["drop"] else v) for k, v in params["moe"].items()}}
        over.pop("drop")
    if over.get("layer_types"):  # a full layer's weights are its own: only the mask changes
        over = {"window": 10**6}
    assert rel(got, reference_logits(params, CFG, tokens, **over)) > WRONG


@pytest.mark.parametrize("path", ["einsum", "sorted"])
def test_shares_add_up_to_the_uncut_layer(case, path, monkeypatch):
    """Four chips' partial sums (2 of the 8 experts each, routed over all 8)
    with the shared expert counted ONCE are the uncut model's MoE layer: the
    logits of a model whose only MoE layer is cut four ways, summed over the
    shares less three copies of what every share repeats, are the uncut
    model's."""
    from agentainer_tpu.models import llama

    if path == "sorted":
        monkeypatch.setattr(llama, "moe_sorts", lambda *a, **k: True)
    cfg = dataclasses.replace(CFG, n_layers=2, layer_kinds=CFG.layer_kinds[:2])
    params, tokens, _ = case
    cut = lambda a, n: a[:n] if a.ndim else a  # noqa: E731
    p2 = {g: ({k: cut(a, {"layers": 2, "full": 1, "swa": 1, "moe": 1}.get(g, a.shape[0])) for k, a in v.items()}
              if isinstance(v, dict) else v) for g, v in params.items()}
    toks, pos = tokens[None, :64], jnp.arange(64)[None]

    def stream_after_moe(c, p):
        """The residual stream's last-layer MoE term: logits are linear in it
        only before the final norm, so compare the FFN's output itself."""
        h = jax.random.normal(jax.random.PRNGKey(1), (1, 64, c.dim))
        lp = {k: v[0] for k, v in p["moe"].items()}
        logits = hybrid.router_logits(h[0], lp["router"])[None]
        if path == "sorted":
            from agentainer_tpu.ops.moe import stacked_experts

            y = llama._moe_mlp_sorted(h, lp, c, stacked_experts(p["moe"]), 0, logits=logits)
        else:
            y = llama._moe_mlp(h, lp, c, logits=logits)
        shared = hybrid._swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return y, shared

    whole, shared = stream_after_moe(cfg, p2)
    parts = []
    for chip in range(4):
        c = dataclasses.replace(cfg, experts_held=2, expert_offset=2 * chip)
        p = {**p2, "moe": {k: (v[:, 2 * chip : 2 * chip + 2] if k in ("w_gate", "w_up", "w_down") else v) for k, v in p2["moe"].items()}}
        y, s = stream_after_moe(c, p)
        parts.append(y)
        np.testing.assert_array_equal(s, shared)  # every chip computes the shared expert whole
    np.testing.assert_allclose(sum(parts) + shared, whole + shared, atol=2e-4 * float(jnp.abs(whole).max()))
    # and a chip's share of the whole model is the reference's share
    c = dataclasses.replace(cfg, experts_held=2, expert_offset=4)
    p = {**p2, "moe": {k: (v[:, 4:6] if k in ("w_gate", "w_up", "w_down") else v) for k, v in p2["moe"].items()}}
    got = forward(p, c, toks, pos)[0][0]
    assert rel(got, reference_logits(p, c, tokens[:64])) < TOL


# -- the engine: admission, snapshot and restore over k, v and the ring ----------

ENGINE = {"max_batch": 2, "max_seq": 256, "prefill_chunk": 32}
LONG = "the ring keeps the last rows of a window layer and the arena keeps every row of a full one "
TURNS = [(LONG, 24), ("and then what happened to the oldest rows of the ring", 16), ("go on", 12)]


def make_engine(**over):
    return LLMEngine.create("tiny-laguna", options={**ENGINE, **over})


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
    """Three turns through the engine (bucketed chunked prefill through the
    ring, pipelined decode chunks, the reply's last token held out and fed
    with the next prompt) against a plain loop over ``forward`` with a ring as
    long as the arena."""
    tokens, metrics = uninterrupted
    eng = make_engine(skip_warmup=True)
    try:
        params, tok = eng.params, eng.tokenizer
        assert eng.cache.wk.shape[2] == W + ENGINE["prefill_chunk"] == metrics["attention"]["window_rows"]
    finally:
        eng.shutdown()
    cache = init_cache(CFG, 1, 256, dtype=jnp.float32)
    pos, pending, got = 0, [], []
    for (text, n), _ in zip(TURNS, tokens):
        feed = pending + tok.encode(text)
        logits, cache = RUN.step(params, cache, jnp.asarray([feed], jnp.int32), (pos + jnp.arange(len(feed)))[None])
        pos += len(feed)
        out = [int(jnp.argmax(logits[0, -1]))]
        while len(out) < n:
            logits, cache = RUN.step(params, cache, jnp.asarray([[out[-1]]], jnp.int32), jnp.full((1, 1), pos))
            pos += 1
            out.append(int(jnp.argmax(logits[0, 0])))
        pending = [out[-1]]
        got.append(out)
    assert pos > 3 * (W + ENGINE["prefill_chunk"])  # the ring lapped three times
    assert got == tokens


def test_kill_and_resume_past_the_window_is_token_identical(uninterrupted):
    """Snapshot after each turn (``k`` and ``v`` up to the position's bucket,
    the ring whole), kill, restore into a new engine, go on: the same tokens
    as never stopping, with the context past the ring at every snapshot."""

    async def interrupted():
        out, blob = [], None
        for text, n in TURNS:
            eng = make_engine()
            try:
                if blob is not None:
                    assert await eng.restore_session("s", blob) is True
                out.append((await eng.chat("s", text, max_tokens=n))["tokens"])
                assert eng.slots[eng.sessions["s"]].position > W + ENGINE["prefill_chunk"]
                blob = await eng.snapshot_session("s")
                assert blob is not None
            finally:
                eng.shutdown()
        return out

    assert asyncio.run(interrupted()) == uninterrupted[0]


def test_evicted_session_comes_back_token_identical_and_a_reused_lane_serves_a_fresh_one(uninterrupted):
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
    assert cache["state_restores"] == 1 and cache["state_snapshots"] == 1 and cache["state_resets"] == 0


def test_sessions_served_side_by_side_answer_as_they_do_alone(uninterrupted):
    """Two sessions over two lanes: the second's chunks carry the first's
    decode steps (the mixed step through both kinds), each lane's ring wraps,
    and both get the tokens they get alone."""

    async def run():
        eng = make_engine()
        try:
            a = asyncio.create_task(eng.chat("s", TURNS[0][0], max_tokens=TURNS[0][1]))
            b = asyncio.create_task(eng.chat("t", LONG[::-1], max_tokens=20))
            return (await a)["tokens"], (await b)["tokens"], eng.metrics()
        finally:
            eng.shutdown()

    async def alone():
        eng = make_engine()
        try:
            return (await eng.chat("t", LONG[::-1], max_tokens=20))["tokens"]
        finally:
            eng.shutdown()

    a, b, m = asyncio.run(run())
    assert a == uninterrupted[0][0] and b == asyncio.run(alone())
    assert m["attention"]["window_wraps"] == 2 and m["mixed_launches"] > 0


def test_a_snapshot_of_another_cache_is_refused_by_its_leaves_names(uninterrupted):
    """``tiny-olmo-hybrid``'s blob has ``k`` and ``v`` beside a state and no
    ring, ``tiny-mistral4``'s a latent leaf: neither is written into this
    cache (the caller re-prefills)."""

    async def blob_of(model):
        eng = LLMEngine.create(model, options=ENGINE)
        try:
            eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
            await eng.chat("s", LONG, max_tokens=8)
            return await eng.snapshot_session("s")
        finally:
            eng.shutdown()

    async def run():
        blobs = [await blob_of(m) for m in ("tiny-olmo-hybrid", "tiny-mistral4")]
        eng = make_engine(skip_warmup=True)
        try:
            return [await eng.restore_session("s", b) for b in blobs]
        finally:
            eng.shutdown()

    assert asyncio.run(run()) == [False, False]


@pytest.mark.parametrize("option", sorted(_WINDOW_OFF))
def test_a_feature_the_ring_cannot_hold_is_refused_by_name_with_the_windows_reason(option):
    """Off by default with ``_WINDOW_OFF``'s reason reported (the table is
    chosen by the cache's leaves: no fourth table by family); asked for by
    name, refused when built — never a silent fallback."""
    asked = {k: None for k in _WINDOW_OFF}
    feats, off = cache_features(CFG, asked)
    assert not any(feats.values()) and off == _WINDOW_OFF
    with pytest.raises(ValueError, match=option) as e:
        cache_features(CFG, {**asked, option: True})
    assert _WINDOW_OFF[option] in str(e.value) and "ring" in str(e.value)
    if option != "mesh":
        with pytest.raises(ValueError, match=option):
            make_engine(skip_warmup=True, **{option: True})


def test_the_refusal_table_is_chosen_by_the_caches_leaves():
    from agentainer_tpu.engine.llm import _LATENT_OFF, _RECURRENT_OFF, _cache_off

    tables = {m: _cache_off(get_config(m))[0] for m in (
        "tiny", "tiny-smallthinker", "tiny-laguna", "tiny-kimi-linear", "tiny-olmo-hybrid", "tiny-mistral4")}
    assert tables["tiny"] is None and tables["tiny-smallthinker"] is tables["tiny-laguna"] is _WINDOW_OFF
    assert tables["tiny-kimi-linear"] is tables["tiny-olmo-hybrid"] is _RECURRENT_OFF and tables["tiny-mistral4"] is _LATENT_OFF


def test_metrics_name_the_kinds_the_leaves_the_counters_and_what_is_off(uninterrupted):
    _, m = uninterrupted
    cache, a, arch, moe = m["cache"], m["attention"], m["model_arch"], m["moe"]
    assert arch["layer_kinds"] == {"full": 2, "swa": 6} and arch["dense_layers"] == 1
    assert a["heads"] == {"full": 6, "swa": 8} and a["gate"] == "per_head"
    assert "yarn x8 past 32" in a["rotary"]["full"] and "first 8 of 16 dims" in a["rotary"]["full"]
    assert a["rotary"]["swa"].startswith("rope, theta 10000,") and "theta 100," in a["rotary"]["full"]
    assert cache["kinds"] == ["k", "v", "wk", "wv"] and "state_bytes" not in cache
    row = CFG.n_kv_heads * CFG.head_dim * 4  # float32 on the CPU
    assert cache["k_bytes"] == cache["v_bytes"] == 2 * 2 * 256 * row
    assert cache["wk_bytes"] == cache["wv_bytes"] == 6 * 2 * (W + 32) * row
    assert cache["bytes_per_lane"] * 2 == m["kv_arena_bytes"] - 2 * 2 * 4  # the two control leaves
    assert cache["off"] == _WINDOW_OFF and cache["state_resets"] == 0
    assert (a["window"], a["window_layers"], a["global_layers"]) == (W, 6, 2)
    assert (a["window_rows"], a["global_rows"]) == (W + 32, 256)
    assert a["window_wraps"] == 3
    assert 0 < a["window_decode_blocks_live"] <= a["window_decode_blocks_unbounded"]
    assert a["global_decode_blocks_live"] == a["decode_blocks_live"] > 0
    assert 0 < a["window_decode_rows"] < a["global_decode_rows"]
    assert a["rope_original_max"] == 32 and 0 < a["rows_past_original_max"] < a["rows_positioned"]
    assert (moe["experts"], moe["experts_held"], moe["shared_experts"], moe["router"], moe["top_k"]) == (8, 8, 1, "softmax", 2)
    assert moe["assignments"] > 0 and moe["rows_all_experts"] > 0


def test_the_new_parts_of_the_layer_body_are_named_in_the_lowered_step():
    """``jax.named_scope``s a profile finds: both kinds of attention, the
    gate, the partial rotation, the shared expert."""
    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    cache = init_cache(CFG, 2, 64, dtype=jnp.float32, launch_rows=8)
    toks = jnp.zeros((1, 8), jnp.int32)
    text = jax.jit(lambda p, c: forward(p, CFG, toks, jnp.arange(8)[None], c, slot=jnp.int32(0))).lower(
        params, cache).as_text(debug_info=True)
    for scope in ("attn_global", "attn_window", "attn_gate", "rope_partial", "moe_shared_expert"):
        assert scope in text, scope


# -- the six older configurations' step programs are the parent's ------------------

OLDER = json.load(open(os.path.join(os.path.dirname(__file__), "data", "step_programs_parent_pr49.json")))
# Kimi-Linear's and Olmo-Hybrid's since PR 51: the parent's and a barrier in each kind's loop
OLDER_MOVED = json.load(open(os.path.join(os.path.dirname(__file__), "data", "step_programs_pr51.json")))["older"]
OLDER_OPTIONS = {"max_batch": 4, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 128, "skip_warmup": True, "quant": "int8"}


@pytest.fixture(scope="module")
def older_engine():
    built = {}

    def get(model):
        if model not in built:
            built[model] = LLMEngine.create(model, options=OLDER_OPTIONS)
        return built[model]

    yield get
    for eng in built.values():
        eng.shutdown()


def _older_program(eng, program: str) -> str:
    B = eng.max_batch
    z = lambda dt: jnp.zeros((B,), dt)  # noqa: E731
    lanes = (z(jnp.int32), z(jnp.int32), z(jnp.float32), z(jnp.int32), z(jnp.float32))
    tokens = jnp.zeros((1, 128), jnp.int32)
    if program == "jit_decode_n":
        lowered = eng._decode_n.lower(eng.params, eng.cache, *lanes, jax.random.split(jax.random.PRNGKey(0), 8))
    elif program == "jit_prefill.128":
        lowered = eng._prefill.lower(eng.params, eng.cache, jnp.int32(1), tokens, tokens, jnp.int32(4))
    else:
        lowered = eng._prefill_with_decode.lower(
            eng.params, eng.cache, jnp.int32(1), tokens, tokens, jnp.int32(4), *lanes,
            jax.random.split(jax.random.PRNGKey(0), 1))
    return lowered.as_text()


@pytest.mark.parametrize("key", sorted(OLDER))
def test_an_older_configurations_step_program_lowers_to_the_parents_text(older_engine, key):
    """``jit_decode_n``, ``jit_prefill`` at bucket 128 and the mixed step of
    the six blocks the benchmark had (Mixtral's and OLMoE's K/V block,
    SmallThinker's ring, Kimi-Linear, Olmo-Hybrid, Mistral-Small-4), int8 as
    served, lower to the StableHLO the parent commit (c00855d, PR 49) lowered
    on this backend, byte for byte: sha256 of the text, taken there with
    these lowering calls. The second positional kind, the ring in
    ``HybridCache``, ``moe_scale`` in the softmax rule and ``apply_rope``'s
    two keywords leave every call without them what it was.

    Since PR 51 the 12 programs of the four configurations with ONE kind of
    mixer are still PR 49's: that equality is why ``mistral4.docs``,
    ``smallthinker.mixed`` and the ``mixtral`` and ``olmoe`` cells cannot
    move. Kimi-Linear's and Olmo-Hybrid's 6 compare with
    ``tests/data/step_programs_pr51.json`` (a barrier in each kind's loop, and
    nothing else: the next test)."""
    model, program = key.split(".", 1)
    text = _older_program(older_engine(model), program)
    assert hashlib.sha256(text.encode()).hexdigest() == {**OLDER, **OLDER_MOVED}[key]


@pytest.mark.parametrize("key", sorted(OLDER_MOVED))
def test_a_two_kind_configurations_step_less_its_barriers_lowers_to_the_parents_text(older_engine, barrier_is_identity, key):
    """The mixed step included: with ``lax.optimization_barrier`` as the
    identity, Kimi-Linear's and Olmo-Hybrid's three programs are PR 49's text
    byte for byte, so PR 51 added the barriers and changed no arithmetic."""
    model, program = key.split(".", 1)
    text = _older_program(older_engine(model), program)
    assert "optimization_barrier" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == OLDER[key]
