"""The K/V block with per-layer switches (``models/llama.py``: sliding-window
RoPE layers beside global NoPE layers, a head width of its own, a router that
reads the layer's input, ReGLU experts, a cache of two leaves whose window
layers are a ring) against the benchmark's plain float32 reference
(``benchmark/families/smallthinker_reference.py``, which imports nothing of
the program), on the CPU with ``tiny-smallthinker`` (window 16, two periods
G W W W, 6 heads of 16 over a hidden size of 48, GQA group 3, 8 experts top-2)
and seeded weights — and the cache manager's moves on the ring.

Weights are NOT the 0.02-std init (``tests/test_kimi_linear.py`` says why):
the projections are scaled until the window, the missing rotary embedding, the
router's input and the gate's activation each move the logits by several per
cent at least.

Tolerance: both sides compute in float32 and differ by the order of
summation; the rms difference over the logits' standard deviation stays under
1e-3 in the median over positions (it reads 1e-5 to 8e-5) and under 5e-3 at the
WORST position (under the sharpened weights one position of the hundred reads
1.3e-3 through the cache and 4e-4 through the program's own full forward,
whatever the chunking and with a ring as long as the arena: float32, not the
ring); every wrong block, a window one key short among them, has to read over
2e-2 in the median.
"""

import asyncio
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.models import llama
from agentainer_tpu.models.configs import get_config
from agentainer_tpu.models.llama import forward, init_cache, init_params, ring_rows
from agentainer_tpu.ops.moe import stacked_experts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-3  # see the module docstring
WORST = 5e-3
WRONG = 2e-2
CFG = get_config("tiny-smallthinker")
W = CFG.window


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "smallthinker_reference", os.path.join(REPO, "benchmark", "families", "smallthinker_reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()


def sharp_params(cfg, seed=3):
    """Seeded float32 weights under which the block's own steps matter."""
    p = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    scale = {"wq": 25.0, "wk": 25.0, "wv": 10.0, "wo": 10.0, "router": 60.0, "w_gate": 10.0, "w_down": 40.0}
    out = dict(p)
    out["layers"] = {k: v * scale.get(k, 1.0) for k, v in p["layers"].items()}
    out["lm_head"] = p["lm_head"] * 10.0
    return out


def reference_weights(params, cfg):
    layers = [{k: v[i] for k, v in params["layers"].items()} for i in range(cfg.n_layers)]
    return {"embed": params["embed"], "layers": layers, "final_norm": params["final_norm"], "lm_head": params["lm_head"]}


def reference_kw(cfg):
    return dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token, rope_layout=cfg.rope_layers,
        window_layout=cfg.window_layers, window=cfg.window, expert_offset=cfg.expert_offset,
    )


def reference_logits(params, cfg, tokens, **over):
    return ref.forward(reference_weights(params, cfg), tokens, **{**reference_kw(cfg), **over})


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


def worst(got, want):
    """The largest per-position error over ``WORST / TOL``, so that it reads
    against ``TOL``: a median forgives a few wrong rows, and a ring that loses
    one row wrongs only the queries that saw it."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.sqrt(np.mean((got - want) ** 2, -1)) / np.std(want, -1))) * TOL / WORST


N_TOKENS = 100


class Runner:
    """``forward`` under ``jax.jit``, one program a call shape. Called op by
    op, every ``forward`` traces its layer scan anew, and JAX then gets one
    more copy of the same executable from the persistent cache: after a few
    hundred of them in one process XLA:CPU's loader crashed the worker
    (a segmentation fault in ``compilation_cache.get_executable_and_time``,
    reproducibly, in whichever case came after the eight cached ones)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.full = jax.jit(lambda p, t, q: forward(p, cfg, t, q, use_flash=False)[0])
        self.chunk = jax.jit(lambda p, c, t, q, slot: forward(p, cfg, t, q, c, use_flash=False, slot=slot))
        self.step = jax.jit(lambda p, c, t, q: forward(p, cfg, t, q, c, use_flash=False))  # any [B, T], no slot
        self.mixed = jax.jit(
            lambda p, c, t, q, slot, lanes, last: forward(p, cfg, t, q, c, use_flash=False, slot=slot, lanes=lanes, last=last)
        )


RUN = Runner(CFG)


@pytest.fixture(scope="module")
def case():
    params = sharp_params(CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (N_TOKENS,), 3, CFG.vocab_size)
    return params, tokens, reference_logits(params, CFG, tokens)


def test_the_tiny_configuration_has_every_shape_the_block_adds():
    assert CFG.head_dim == 16 != CFG.dim // CFG.n_heads and CFG.n_heads * CFG.head_dim != CFG.dim
    group = CFG.n_heads // CFG.n_kv_heads
    assert group == 3 and group & (group - 1)  # not a power of two
    assert CFG.window_layers == CFG.rope_layers == (0, 1, 1, 1, 0, 1, 1, 1)
    assert (CFG.n_global, CFG.n_window) == (2, 6) and 16 <= W <= 32
    assert (CFG.n_experts, CFG.experts_per_token, CFG.ffn_act, CFG.early_router) == (8, 2, "relu", True)


def test_full_forward_matches_the_plain_reference(case):
    params, tokens, want = case
    got = RUN.full(params, tokens[None], jnp.arange(N_TOKENS)[None])[0]
    assert worst(got, want) < TOL


def cached(params, tokens, chunks, launch_rows, max_seq=128, lanes=1, lane=0, cache=None):
    """Prefill ``tokens`` in ``chunks`` (row counts; the rest one-token decode
    steps) through a two-leaf cache whose ring is sized for ``launch_rows``."""
    if cache is None:
        cache = init_cache(CFG, lanes, max_seq, dtype=jnp.float32, launch_rows=launch_rows)
    rows, at = [], 0
    for n in chunks:
        pos = (at + jnp.arange(n))[None]
        logits, cache = RUN.chunk(params, cache, tokens[None, at : at + n], pos, jnp.int32(lane))
        rows.append(logits[0])
        at += n
    parked = cache.k.shape[2] - 1
    for i in range(at, tokens.shape[0]):
        tok = jnp.zeros((lanes, 1), jnp.int32).at[lane, 0].set(tokens[i])
        pos = jnp.full((lanes, 1), parked, jnp.int32).at[lane, 0].set(i)
        step, cache = RUN.step(params, cache, tok, pos)
        rows.append(step[lane])
    return jnp.concatenate(rows), cache


CACHED = {
    # context stays under the window: the ring never matters
    "under_the_window": dict(n=W - 4, chunks=(6,), launch_rows=8),
    # the last query sees exactly `window` keys, itself included
    "exactly_at_the_window": dict(n=W, chunks=(8, 8), launch_rows=8),
    # one past: the first key falls out of the window
    "one_past_the_window": dict(n=W + 1, chunks=(8, 8), launch_rows=8),
    # R = 24: 100 tokens lap the ring four times, by decode steps alone
    "decode_laps_the_ring": dict(n=N_TOKENS, chunks=(8,), launch_rows=8),
    # by chunks of 8 (R = 24 is whole chunks: a chunk ends on the ring's end)
    "chunks_lap_the_ring": dict(n=N_TOKENS, chunks=(8,) * 11, launch_rows=8),
    # chunks of 7 in a ring of 24: the chunk at 21..27 CROSSES the ring's end
    "a_chunk_crosses_the_rings_end": dict(n=N_TOKENS, chunks=(7,) * 12, launch_rows=8),
    # the longest launch the ring was sized for, and ragged ones after it
    "longest_launch_then_ragged": dict(n=N_TOKENS, chunks=(8, 8, 8, 3, 8, 5, 8, 1, 8), launch_rows=8),
    # a ring the size of the arena (no launch_rows): the plain path's callers
    "ring_as_long_as_the_arena": dict(n=N_TOKENS, chunks=(50, 30), launch_rows=None),
}


@pytest.mark.parametrize("name", sorted(CACHED))
def test_cached_prefill_and_decode_match_the_reference_full_forward(case, name):
    """Logits, not tokens, at EVERY position: chunks through the ring, then
    one-token steps, against the reference's full causal forward."""
    params, tokens, want = case
    spec = CACHED[name]
    got, cache = cached(params, tokens[: spec["n"]], spec["chunks"], spec["launch_rows"])
    if spec["launch_rows"]:
        assert cache.wk.shape == (CFG.n_window, 1, W + spec["launch_rows"], CFG.n_kv_heads, CFG.head_dim)
    assert cache.k.shape == (CFG.n_global, 1, 128, CFG.n_kv_heads, CFG.head_dim)
    assert worst(got, want[: spec["n"]]) < TOL


def test_a_launch_longer_than_the_ring_was_sized_for_is_refused(case):
    params, tokens, _ = case
    cache = init_cache(CFG, 1, 128, dtype=jnp.float32, launch_rows=8)
    with pytest.raises(ValueError, match="ring_rows"):
        RUN.chunk(params, cache, tokens[None, :10], jnp.arange(10)[None], jnp.int32(0))
    # and one row short of it is served: R >= window + T - 1
    RUN.chunk(params, cache, tokens[None, :9], jnp.arange(9)[None], jnp.int32(0))


@pytest.mark.parametrize(
    "window, max_seq, launch, block, rows",
    [
        (4096, 16384, 256, 512, 4608),  # the served configuration: 4352 rounded to the K/V block
        (4096, 16384, 512, 512, 4608),
        (4096, 16384, 1024, 512, 5120),
        (4096, 2048, 256, 512, 2048),  # never more than the arena
        (16, 128, 8, 1, 24),
        (16, 128, None, 1, 128),  # no plan: the arena's length, any launch is safe
        (16, 100, None, 8, 104),
    ],
)
def test_ring_rows_is_the_window_plus_a_launch_in_whole_blocks(window, max_seq, launch, block, rows):
    assert ring_rows(window, max_seq, launch, block) == rows
    if launch is not None and rows < max_seq:
        # a launch at s .. s + T - 1 overwrites positions up to s + T - 1 - R;
        # its first query still sees s - window + 1
        assert launch - 1 - rows < -window + 1


def test_the_mixed_step_reads_each_groups_own_rows(case):
    """A chunk at ``slot`` plus one row of every lane in one launch: lane 0
    decodes PAST the ring's length, lane 1 is parked, lane 2 takes the chunks
    (one of them across the ring's end). The chunk's last logits and lane 0's
    are the reference's for their own sequences."""
    params, tokens, want = case
    other = jax.random.randint(jax.random.PRNGKey(9), (60,), 3, CFG.vocab_size)
    want_other = reference_logits(params, CFG, other)
    _, cache = cached(params, tokens[:40], (8,) * 5, 8, lanes=3, lane=0)
    parked = cache.k.shape[2] - 1
    at, pos0 = 0, 40
    for n in (7, 7, 7, 7, 7, 7, 7, 7):  # 21..27 crosses the ring's end (R = 24)
        chunk_pos = (at + jnp.arange(n))[None]
        lane_tok = jnp.asarray([[tokens[pos0]], [0], [0]], jnp.int32)
        lane_pos = jnp.asarray([[pos0], [parked], [parked]], jnp.int32)
        logits, cache = RUN.mixed(
            params, cache, other[None, at : at + n], chunk_pos, jnp.int32(2), (lane_tok, lane_pos), jnp.int32(n - 1)
        )
        assert logits.shape == (1 + 3, CFG.vocab_size)
        assert worst(logits[0][None], want_other[at + n - 1][None]) < TOL
        assert worst(logits[1][None], want[pos0][None]) < TOL
        at, pos0 = at + n, pos0 + 1
    assert pos0 > 40 + 7 and pos0 > cache.wk.shape[2]


def test_a_lane_readmitted_onto_a_used_slot_sees_none_of_the_old_rows(case):
    """The ring of a lane that held 100 positions is full of rows whose
    positions are AHEAD of a new sequence's: nothing is zeroed at admission,
    and the new sequence's logits are the reference's from position 0."""
    params, tokens, _ = case
    _, cache = cached(params, tokens, (8,) * 11, 8, lanes=2, lane=1)
    assert bool(jnp.abs(cache.wk[:, 1]).min(axis=(0, 2, 3)).all())  # every ring row written
    fresh = jax.random.randint(jax.random.PRNGKey(11), (45,), 3, CFG.vocab_size)
    got, _ = cached(params, fresh, (8, 8, 5), 8, lanes=2, lane=1, cache=cache)
    assert worst(got, reference_logits(params, CFG, fresh)) < TOL


def test_a_parked_lane_writes_nowhere_in_a_live_lanes_ring_or_its_own(case):
    """Lane 0 finished at position 40 and parked; lane 1 then decodes 60
    steps beside it, every launch carrying lane 0's row at the parked
    position. Lane 0's leaves are bit-identical, and going on from 40 gives
    the reference's logits."""
    params, tokens, want = case
    _, cache = cached(params, tokens[:40], (8,) * 5, 8, lanes=2, lane=0)
    before = [np.array(leaf[:, 0]) for leaf in cache]
    # the global leaf's last row is where parked writes land: nobody's
    before[0][:, -1] = before[1][:, -1] = 0
    _, cache = cached(params, tokens[:60], (), 8, lanes=2, lane=1, cache=cache)
    after = [np.array(leaf[:, 0]) for leaf in cache]
    after[0][:, -1] = after[1][:, -1] = 0
    for x, y in zip(before, after):
        assert np.array_equal(x, y)
    tok = jnp.zeros((2, 1), jnp.int32).at[0, 0].set(tokens[40])
    pos = jnp.asarray([[40], [60]], jnp.int32)
    step, _ = RUN.step(params, cache, tok, pos)
    assert worst(step[0], want[40][None]) < TOL


WRONG_BLOCKS = {
    # a router on the normed post-attention stream (every other MoE block here)
    "late_router": dict(layer=dict(early_router=False)),
    # SwiGLU in ReGLU's place
    "swiglu": dict(layer=dict(gate_act=jax.nn.silu)),
    # every layer global / every layer rotated / no layer rotated / a window one short
    "no_window": dict(window_layout=(0,) * CFG.n_layers),
    "rope_everywhere": dict(rope_layout=(1,) * CFG.n_layers),
    "rope_nowhere": dict(rope_layout=(0,) * CFG.n_layers),
    "window_one_short": dict(window=W - 1),
}


@pytest.mark.parametrize("name", sorted(WRONG_BLOCKS))
def test_program_fails_a_reference_of_another_block(case, name, monkeypatch):
    """The program differs from a reference that routes from the
    post-attention stream, gates with SiLU, has no window, rotates the global
    layers too or not at all, or whose window is one key short: each by more
    than 20 times the tolerance."""
    params, tokens, want = case
    over = dict(WRONG_BLOCKS[name])
    patch = over.pop("layer", None)
    if patch:
        plain = ref.layer
        monkeypatch.setattr(ref, "layer", lambda x, lp, **kw: plain(x, lp, **{**kw, **patch}))
    other = reference_logits(params, CFG, tokens, **over)
    got = RUN.full(params, tokens[None], jnp.arange(N_TOKENS)[None])[0]
    assert rel(got, want) < TOL
    assert rel(got[W + 4 :], other[W + 4 :]) > WRONG, name


@pytest.mark.parametrize("path", ["einsum", "sorted"])
def test_shares_add_up_to_the_uncut_layer(path):
    """Expert parallelism without the exchange: 4 chips hold 2 of the 8
    experts each, every chip routes over all 8 from the early logits and
    computes its own experts' terms. The four shares' MoE outputs sum to the
    uncut reference layer's — through both of the program's paths."""
    params = sharp_params(CFG)
    lp = {k: v[1] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, CFG.dim), jnp.float32)
    h2 = jax.random.normal(jax.random.PRNGKey(3), (1, 40, CFG.dim), jnp.float32)
    logits = x @ lp["router"]  # from the layer's input, not from h2
    want = ref.moe(h2[0], logits[0], lp, CFG.experts_per_token, 0, lambda a: a)
    total = jnp.zeros_like(want)
    for chip in range(4):
        share = dataclasses.replace(CFG, experts_held=2, expert_offset=2 * chip)
        mine = {k: (v[2 * chip : 2 * chip + 2] if k in ("w_gate", "w_up", "w_down") else v) for k, v in lp.items()}
        if path == "einsum":
            part = llama._moe_mlp(h2, mine, share, logits=logits)[0]
        else:
            experts = stacked_experts({k: v[None] for k, v in mine.items()})
            part = llama._moe_mlp_sorted(h2, mine, share, experts, jnp.int32(0), logits=logits)[0]
        # a share alone is the reference's share: the same held range
        assert rel_all(part, ref.moe(h2[0], logits[0], mine, CFG.experts_per_token, 2 * chip, lambda a: a)) < TOL
        total = total + part
    assert rel(total, want) < TOL
    # ... and the logits the program would have taken from h2 choose otherwise
    late = ref.moe(h2[0], (h2 @ lp["router"])[0], lp, CFG.experts_per_token, 0, lambda a: a)
    assert rel(total, late) > WRONG


def test_a_chips_share_of_the_whole_model_is_the_references_share(case):
    """The served cut, end to end: chip 1 of ep = 4 (experts 2-3 of 8 in every
    layer) through the cached path against the reference given the same
    share."""
    params, tokens, _ = case
    share = dataclasses.replace(CFG, experts_held=2, expert_offset=2, name="tiny-smallthinker-share")
    mine = dict(params)
    mine["layers"] = {k: (v[:, 2:4] if k in ("w_gate", "w_up", "w_down") else v) for k, v in params["layers"].items()}
    want = reference_logits(mine, share, tokens[:60])
    cache = init_cache(share, 1, 128, dtype=jnp.float32, launch_rows=8)
    run, rows, at = Runner(share), [], 0
    for n in (8,) * 7 + (4,):
        logits, cache = run.chunk(mine, cache, tokens[None, at : at + n], (at + jnp.arange(n))[None], jnp.int32(0))
        rows.append(logits[0])
        at += n
    assert worst(jnp.concatenate(rows), want) < TOL


def test_param_count_is_the_pytrees_size_and_the_published_models():
    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    assert CFG.param_count() == sum(x.size for x in jax.tree.leaves(params))
    big = get_config("smallthinker-21b")
    assert big.head_dim == 128 and big.n_heads * big.head_dim == 3584 != big.dim
    assert abs(big.param_count() / 21.5e9 - 1.0) < 0.01
    assert (big.n_global, big.n_window) == (13, 39)
    assert [i for i, w in enumerate(big.window_layers) if not w] == list(range(0, 52, 4))
    held = dataclasses.replace(big, experts_held=16)
    assert abs(held.param_count() / 6.78e9 - 1.0) < 0.01  # one chip's share of ep = 4
    assert 2.8e9 < big.active_param_count() < 3.9e9  # "A3B"
    shared = init_params(dataclasses.replace(CFG, experts_held=2), jax.random.PRNGKey(0), jnp.float32)
    assert shared["layers"]["w_gate"].shape[1] == 2 and shared["layers"]["router"].shape[-1] == 8


@pytest.mark.parametrize(
    "field, value, message",
    [
        ("window_layers", (0, 1), "one 0/1 flag a layer"),
        ("rope_layers", (2,) * 8, "one 0/1 flag a layer"),
        ("window", 0, "window is 0"),
        ("ffn_act", "gelu", "silu or relu"),
    ],
)
def test_a_configuration_that_cannot_be_served_is_refused_when_built(field, value, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **{field: value})


def test_the_new_parts_of_the_layer_body_are_named_in_the_lowered_step(case):
    """``attn_window``, ``attn_global`` and ``moe_early_router`` are scopes a
    trace names; a model without the switches lowers to a step with none of
    them (they are static absences there, not operands that happen to be 0)."""
    params, tokens, _ = case
    cache = init_cache(CFG, 2, 128, dtype=jnp.float32, launch_rows=8)
    rows = jnp.zeros((2, 1), jnp.int32)
    text = jax.jit(lambda p, c, t, q: forward(p, CFG, t, q, c, use_flash=False)).lower(params, cache, rows, rows).as_text(debug_info=True)
    for scope in ("attn_window", "attn_global", "moe_early_router"):
        assert scope in text, scope
    plain = get_config("tiny-moe")
    p2 = init_params(plain, jax.random.PRNGKey(0), jnp.float32)
    c2 = init_cache(plain, 2, 128, dtype=jnp.float32)
    other = jax.jit(lambda p, c, t, q: forward(p, plain, t, q, c, use_flash=False)).lower(p2, c2, rows, rows).as_text(debug_info=True)
    for scope in ("attn_window", "attn_global", "moe_early_router"):
        assert scope not in other, scope


# -- the cache manager on a ring ---------------------------------------------------

ENGINE = {"max_batch": 2, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 32}
LONG = "a prompt that runs well past the window of sixteen and past the ring of forty-eight rows as well. "
TURNS = [(LONG, 30), ("and a second turn", 9), ("a third", 7)]


def make_engine(**over):
    from agentainer_tpu.engine.llm import LLMEngine

    return LLMEngine.create("tiny-smallthinker", options={**ENGINE, **over})


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
    ring, pipelined decode chunks, the last token of a reply held out and fed
    with the next prompt), the context at 150 positions against a ring of 48:
    the tokens a plain loop over ``forward`` gives with a ring as long as the
    arena."""
    tokens, metrics = uninterrupted
    eng = make_engine(skip_warmup=True)
    try:
        params, tok = eng.params, eng.tokenizer
        assert eng.cache.wk.shape[2] == W + ENGINE["prefill_chunk"] == metrics["attention"]["window_rows"]
    finally:
        eng.shutdown()
    cache = init_cache(CFG, 1, 256, dtype=jnp.float32)
    pos, pending, got = 0, [], []
    for (text, n), want in zip(TURNS, tokens):
        feed = pending + tok.encode(text)
        logits, cache = RUN.step(params, cache, jnp.asarray([feed], jnp.int32), (pos + jnp.arange(len(feed)))[None])
        pos += len(feed)
        out = [int(jnp.argmax(logits[0, -1]))]
        while len(out) < n:
            logits, cache = RUN.step(params, cache, jnp.asarray([[out[-1]]], jnp.int32), jnp.full((1, 1), pos))
            pos += 1
            out.append(int(jnp.argmax(logits[0, 0])))
        pending = [out[-1]]  # sampled, never fed: it leads the next turn's prompt
        got.append(out)
    assert pos > 3 * (W + ENGINE["prefill_chunk"])  # the ring lapped three times
    assert got == tokens


def test_kill_and_resume_past_the_window_is_token_identical(uninterrupted):
    """The signature flow on the ring: snapshot after each turn (the global
    rows up to the position's bucket, the ring whole), kill, restore into a
    new engine, go on — the same tokens as never stopping, with the context
    past the window at every snapshot."""

    async def interrupted():
        out, blob = [], None
        for text, n in TURNS:
            eng = make_engine()
            try:
                if blob is not None:
                    assert await eng.restore_session("s", blob) is True
                out.append((await eng.chat("s", text, max_tokens=n))["tokens"])
                assert eng.slots[eng.sessions["s"]].position > W
                blob = await eng.snapshot_session("s")
                assert blob is not None
            finally:
                eng.shutdown()  # the crash
        return out

    assert asyncio.run(interrupted()) == uninterrupted[0]


def test_evicted_session_comes_back_token_identical_and_a_reused_lane_serves_a_fresh_one(uninterrupted):
    """One lane: session ``s`` is snapshotted, evicted by another session
    taking its lane (whose ring is full of s's rows: its tokens are those of
    a fresh engine), then restored into the lane it lost."""

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
    assert cache["state_restores"] == 1 and cache["state_snapshots"] == 1


def test_sessions_served_side_by_side_answer_as_they_do_alone(uninterrupted):
    """Two sessions at once over two lanes: the second's prefill chunks carry
    the first's decode steps (the mixed step), each lane's ring wraps, and
    both get the tokens they get alone."""

    async def run():
        eng = make_engine()
        try:
            a = asyncio.create_task(eng.chat("s", TURNS[0][0], max_tokens=TURNS[0][1]))
            await asyncio.sleep(0.05)
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
    assert m["attention"]["window_wraps"] == 2


def test_a_staged_snapshot_still_on_the_device_defers_the_next():
    """A staged snapshot is a lane's leaves in fresh device buffers (the
    ring whole: 0.8 GB at the served sizes). ``snapshot_session`` reads them
    to the host and lets go of them before it compresses, and while one
    still stands on the device the limiter defers the next: on the chip
    three of them stacked to within 0.5 GB of the device's memory."""
    import gc

    from agentainer_tpu.engine.llm import SnapshotDeferred

    async def run():
        eng = make_engine()
        try:
            eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
            await eng.chat("s", LONG, max_tokens=8)
            first = await eng.snapshot_session("s")
            gc.collect()
            assert first and not eng._staged_on_device()  # packed: nothing of it is left on the device
            held = {"k": jnp.zeros(3)}  # someone still holds a staged leaf
            eng._hold_staged(held)
            assert eng._staged_on_device() and eng._snap_gate("s")
            with pytest.raises(SnapshotDeferred):
                await eng.snapshot_session("s")
            del held
            gc.collect()
            second = await eng.snapshot_session("s")
            assert await eng.restore_session("t", second) is True
            return first == second
        finally:
            eng.shutdown()

    assert asyncio.run(run())


@pytest.mark.parametrize("option", ["speculative", "paged_kv", "kv_tiering", "fused_decode", "prefix_cache"])
def test_a_feature_the_ring_cannot_hold_is_an_error_when_asked_for(option):
    """Off by default with its reason reported; asked for by name, refused
    at build — never a silent fallback."""
    with pytest.raises(ValueError, match=option):
        make_engine(skip_warmup=True, **{option: True})


def test_metrics_name_the_two_leaves_the_counters_and_what_is_off(uninterrupted):
    _, m = uninterrupted
    cache, a = m["cache"], m["attention"]
    assert cache["kinds"] == ["kv", "kv_ring"]
    row = 2 * CFG.n_kv_heads * CFG.head_dim * 4  # K and V, float32 on the CPU
    assert cache["kv_bytes"] == CFG.n_global * 2 * 256 * row
    assert cache["kv_ring_bytes"] == CFG.n_window * 2 * (W + 32) * row
    assert cache["bytes_per_lane"] * 2 == cache["kv_bytes"] + cache["kv_ring_bytes"] == m["kv_arena_bytes"]
    assert set(cache["off"]) == {"speculative", "prefix_cache", "paged_kv", "fused_decode", "kv_tiering", "mesh"}
    assert all(len(reason) > 20 for reason in cache["off"].values())
    assert (a["window"], a["window_layers"], a["global_layers"]) == (W, CFG.n_window, CFG.n_global)
    assert (a["window_rows"], a["global_rows"]) == (W + 32, 256)
    assert a["window_wraps"] == 3  # every turn ended with its context past the ring's length
    # counted at every decode step: what the bounds let through, what an
    # unbounded read of the same lanes would fetch, and the rows themselves
    assert 0 < a["window_decode_blocks_live"] <= a["window_decode_blocks_unbounded"]
    assert a["global_decode_blocks_live"] == a["decode_blocks_live"] > 0
    assert 0 < a["window_decode_rows"] < a["global_decode_rows"]
    steps = a["global_decode_blocks_stored"] // (2 * -(-256 // a["decode_block_positions"]))
    assert a["window_decode_rows"] <= steps * 2 * W
