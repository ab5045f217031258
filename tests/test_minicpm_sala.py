"""MiniCPM-SALA's block through ``models/hybrid.py`` (block-sparse attention
that chooses its key blocks from pooled keys beside lightning linear
attention; a dense SwiGLU in every layer; MiniCPM's µP scalings) against the
benchmark's plain float32 reference (``benchmark/families/
minicpm_sala_reference.py``, which imports nothing of the program), on the CPU
with ``tiny-minicpm-sala`` (every sparse size scaled down with the widths:
pooled keys of 8 rows every 4, blocks of 16, 6 blocks chosen past 96 rows) and
seeded weights — and the cache manager's moves on a slot that is K/V rows up
to a position, pooled keys of those rows AND a recurrent state.

q and k take an RMSNorm a head, so the scale of their projections is nothing;
the weights are sharpened through the norms' own weights and the gates'
projections. Tolerance: both sides compute in float32 and differ by the order
of summation, the chunked against the token-by-token recurrence and the
gathered or masked against the full softmax: the rms difference over the
logits' standard deviation stays under 1e-3 (it reads 1e-6); every omission
has to read over 2e-2.
"""

import asyncio
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.models import hybrid
from agentainer_tpu.models.configs import get_config
from agentainer_tpu.models.llama import forward, init_cache, init_params
from agentainer_tpu.ops import lightning
from agentainer_tpu.ops import sparse_attention as sparse

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-3
WRONG = 2e-2
CFG = get_config("tiny-minicpm-sala")
SIZES = sparse.SparseSizes.of(CFG)


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "minicpm_sala_reference", os.path.join(REPO, "benchmark", "families", "minicpm_sala_reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()


def sharp_params(cfg, seed=3):
    """Seeded float32 weights under which the block's own steps matter."""
    p = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))
    uniform = lambda a, lo, hi: jax.random.uniform(next(keys), a.shape, jnp.float32, lo, hi)  # noqa: E731
    out = dict(p)
    for group in ("sparse", "lightning"):
        out[group] = dict(p[group], wg=p[group]["wg"] * 20.0, wv=p[group]["wv"] * 10.0)
        out[group]["q_norm"] = uniform(p[group]["q_norm"], 0.5, 3.0)
        out[group]["k_norm"] = uniform(p[group]["k_norm"], 0.5, 3.0)
    out["lightning"]["o_norm"] = uniform(p["lightning"]["o_norm"], 0.25, 4.0)
    out["layers"] = {k: uniform(v, 0.5, 2.0) for k, v in p["layers"].items()}
    out["lm_head"] = p["lm_head"] * 10.0
    return out


def reference_weights(params, cfg):
    layers, seen = [], {"sparse": 0, "lightning": 0}
    for i, kind in enumerate(cfg.layer_kinds):
        lp = {k: v[i] for k, v in params["layers"].items()}
        lp.update({k: v[seen[kind]] for k, v in params[kind].items()})
        seen[kind] += 1
        lp.update({k: v[i] for k, v in params["dense"].items()})
        layers.append(lp)
    return {"embed": params["embed"], "layers": layers, "final_norm": params["final_norm"], "lm_head": params["lm_head"]}


def reference_kw(cfg, **over):
    kw = dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, lin_heads=cfg.kda_heads,
        lin_dim=cfg.kda_head_dim, norm_eps=cfg.norm_eps, rope_theta=cfg.lightning_rope_theta,
        sparse=SIZES._asdict(), embed_scale=cfg.embed_scale, residual_scale=cfg.residual_scale,
        logit_divisor=cfg.logit_divisor,
    )
    sizes = {k: over.pop(k) for k in list(over) if k in kw["sparse"]}
    return {**kw, **over, "sparse": {**kw["sparse"], **sizes}}


def reference_logits(params, cfg, tokens, **over):
    kw = reference_kw(cfg, **over)
    return jax.jit(lambda w, t: ref.forward(w, t, **kw))(reference_weights(params, cfg), tokens)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.median(np.sqrt(np.mean((got - want) ** 2, -1)) / np.std(want, -1)))


@pytest.fixture(scope="module")
def case():
    params = sharp_params(CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (230,), 3, CFG.vocab_size)
    return params, tokens, reference_logits(params, CFG, tokens)


def program_full(params, tokens):
    pos = jnp.arange(tokens.shape[0])[None]
    return forward(params, CFG, tokens[None], pos)[0][0]


def program_cached(params, tokens, chunks=(70, 66, 50)):
    """Prefill in three chunks that split pooling kernels (70 and 136 are no
    multiple of the stride; the second and third in buckets with padding
    rows), the second crossing ``dense_len``, then one-token decode steps
    through the cache; logits, not tokens, against the reference's full
    forward."""
    cache = init_cache(CFG, 1, 256, dtype=jnp.float32)
    rows, at = [], 0
    launch = jax.jit(lambda toks, pos, cache, valid: forward(params, CFG, toks, pos, cache, valid=valid))
    for n, bucket in zip(chunks, (70, 96, 64)):
        toks = jnp.pad(tokens[at : at + n], (0, bucket - n))[None]
        pos = (at + jnp.arange(bucket))[None]
        logits, cache = launch(toks, pos, cache, (jnp.arange(bucket) < n)[None])
        rows.append(logits[0, :n])
        at += n
    step = jax.jit(lambda tok, pos, cache: forward(params, CFG, tok, pos, cache))
    for i in range(at, tokens.shape[0]):
        logits, cache = step(tokens[None, i : i + 1], jnp.full((1, 1), i), cache)
        rows.append(logits[0])
    return jnp.concatenate(rows)


@pytest.fixture(scope="module")
def cached(case):
    """``program_cached``'s logits, computed once for the tests that hold other references against them."""
    return program_cached(case[0], case[1])


def program_short(params, tokens):
    """A context that never passes ``dense_len``: chunks and steps, all dense."""
    return program_cached(params, tokens[:90], chunks=(37, 30))


@pytest.mark.parametrize(
    "program", [program_full, program_cached, program_short], ids=["full_forward", "three_chunks_then_decode", "under_dense_len"]
)
def test_program_matches_the_plain_reference(case, program):
    params, tokens, want = case
    got = program(params, tokens)
    assert rel(got, want[: got.shape[0]]) < TOL
    if program is program_short:  # the reference's own short forward, not a prefix of the long one's
        assert rel(got, reference_logits(params, CFG, tokens[:90])) < TOL


OMISSIONS = {
    "the_residual_scale": {"residual_scale": 1.0},
    "the_embedding_scale": {"embed_scale": 1.0},
    "the_logit_divisor": {"logit_divisor": 1.0},
    "the_lightning_rotation": {"rope_theta": 1e9},
    "two_of_the_chosen_blocks": {"topk": 4},
    "the_forced_window": {"window": 0},
    "the_sparse_layers_sparsity": {"dense_len": 1 << 20},
}


@pytest.mark.parametrize("name", sorted(OMISSIONS))
def test_program_fails_a_reference_that_omits(case, cached, name):
    params, tokens, want = case
    assert rel(cached, reference_logits(params, CFG, tokens, **OMISSIONS[name])) > WRONG


@pytest.mark.parametrize("leaf", ["slope", "wg_sparse", "wg_lightning", "o_norm", "q_norm_sparse"])
def test_program_reads_every_new_leaf(case, cached, leaf):
    """A reference whose weights lack what one leaf says is far off."""
    params, tokens, _ = case
    got = cached
    group = "sparse" if leaf.endswith("_sparse") else "lightning"
    name = leaf.removesuffix("_sparse").removesuffix("_lightning")
    flat = {"slope": jnp.full_like(params["lightning"]["slope"], 1e-4)}.get(name, jnp.zeros_like(params[group][name]) + (name != "wg"))
    other = {**params, group: {**params[group], name: flat}}
    assert rel(got, reference_logits(other, CFG, tokens)) > WRONG


# -- the selection ---------------------------------------------------------------


def tapped_selection(params, tokens, run):
    """The block sets the program chose, a sparse layer at a time, while
    ``run(params, tokens)`` ran: ``{layer: {position: [KV, topk]}}``."""
    taken, plain = [], sparse.select_blocks

    def tapped(scores, positions, sizes):
        blocks = plain(scores, positions, sizes)
        jax.debug.callback(lambda p, b: taken.append((np.asarray(p), np.asarray(b))), positions, blocks, ordered=True)
        return blocks

    sparse.select_blocks = tapped
    try:
        jax.block_until_ready(run(params, tokens))
        jax.effects_barrier()
    finally:
        sparse.select_blocks = plain
    by_layer = {i: {} for i in range(CFG.n_sparse)}
    for call, (pos, blocks) in enumerate(taken):
        for p, b in zip(pos.reshape(-1), blocks.reshape(-1, *blocks.shape[2:])):
            by_layer[call % CFG.n_sparse][int(p)] = b
    return by_layer


@pytest.mark.parametrize("program", [program_full, program_cached], ids=["full_forward", "three_chunks_then_decode"])
def test_the_programs_selected_block_sets_equal_the_references(case, program):
    params, tokens, _ = case
    mine = tapped_selection(params, tokens, program)
    theirs: list = []
    ref.forward(reference_weights(params, CFG), tokens, **reference_kw(CFG), selection=theirs)
    assert len(theirs) == CFG.n_sparse
    forced_rows = 0
    for layer, want in enumerate(np.asarray(t) for t in theirs):
        for p in range(SIZES.dense_len, tokens.shape[0]):
            got = mine[layer][p]  # [KV, topk]
            for g in range(CFG.n_kv_heads):
                assert set(got[g].tolist()) == set(np.flatnonzero(want[p, g]).tolist()), (layer, p, g)
                # the first block and the window's are always among them
                current = p // SIZES.block
                forced = set(range(SIZES.init_blocks)) | set(range(current - SIZES.window // SIZES.block + 1, current + 1))
                assert forced <= set(got[g].tolist()) and len(set(got[g].tolist())) == SIZES.topk
                forced_rows += 1
    assert forced_rows == CFG.n_sparse * CFG.n_kv_heads * (tokens.shape[0] - SIZES.dense_len)


def test_pooled_keys_do_not_depend_on_how_the_rows_were_cut_into_launches():
    """Rows appended 1, 7, 40 or 230 a launch leave the same pooled-key leaf:
    the mean of the stored rows ``stride·j .. stride·j + kernel − 1``, for
    every kernel whose last row was written and no other."""
    n, s, kv, hd = 230, 256, 2, 16
    k_rows = jax.random.normal(jax.random.PRNGKey(0), (n, kv, hd), jnp.float32)
    want = np.asarray(ref.pooled_keys(k_rows, SIZES.kernel, SIZES.stride))
    for step in (1, 7, 40, 230):
        k = jnp.zeros((2, 3, s, kv, hd), jnp.float32)
        pooled = jnp.full((2, 3, s // SIZES.stride, kv, hd), 7.0, jnp.float32)
        for at in range(0, n, step):
            t = min(step, n - at)
            k = k.at[1, 2, at : at + t].set(k_rows[at : at + t])
            pooled = sparse.append_pooled(
                pooled, k, jnp.int32(1), jnp.asarray([2]), jnp.asarray([at]), jnp.asarray([t]), step, SIZES)
        got = np.asarray(pooled)
        np.testing.assert_allclose(got[1, 2, : want.shape[0]], want, atol=1e-6)
        assert (got[1, 2, want.shape[0] :] == 7.0).all() and (got[0] == 7.0).all() and (got[1, :2] == 7.0).all()


def test_a_lane_that_does_not_step_appends_no_pooled_key():
    k = jnp.ones((1, 2, 64, 2, 16), jnp.float32)
    pooled = jnp.zeros((1, 2, 16, 2, 16), jnp.float32)
    out = sparse.append_pooled(pooled, k, jnp.int32(0), jnp.arange(2), jnp.asarray([7, 63]), jnp.asarray([1, 0]), 1, SIZES)
    assert float(out[0, 0, 0].min()) == 1.0 and not out[0, 1].any() and not out[0, 0, 1:].any()


def test_block_counts_are_the_selections_own_arithmetic():
    c = sparse.block_counts([10, 95, 96, 200], SIZES)
    assert (c["steps_dense"], c["steps_sparse"]) == (2, 2)
    assert c["blocks_live"] == 7 + 13 and c["blocks_selected"] == 12 and c["blocks_forced"] == 6
    assert c["rows_live"] == 11 + 96 + 97 + 201 and c["rows_read"] == 11 + 96 + (5 * 16 + 1) + (5 * 16 + 9)
    assert c["pooled_rows_scored"] == (96 - 8 + 1) // 4 + 1 + (200 - 8 + 1) // 4 + 1


# -- lightning ---------------------------------------------------------------------


def test_lightning_chunked_is_recurrent_is_step_and_masked_tokens_leave_the_state():
    b, t, h, dk = 2, 150, 4, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v = (jax.random.normal(kk, (b, t, h, dk), jnp.float32) for kk in keys[:3])
    slope = 2.0 ** (-8.0 * (jnp.arange(h) + 1.0) / h)
    valid = jnp.arange(t)[None, :] < jnp.asarray([150, 97])[:, None]
    g, k = lightning.mask_inputs(jnp.broadcast_to(-slope, (b, t, h)), k, valid)
    s0 = jax.random.normal(keys[3], (b, h, dk, dk), jnp.float32)
    o_r, s_r = lightning.lightning_recurrent(q, k, v, g, s0)
    o_c, s_c = lightning.lightning_chunked(q, k, v, g, s0)
    np.testing.assert_allclose(o_c, o_r, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s_c, s_r, rtol=2e-4, atol=2e-4)
    s = s0
    for i in range(t):
        o, s = lightning.lightning_step(q[:, i], k[:, i], v[:, i], g[:, i], s)
        np.testing.assert_allclose(o, o_r[:, i], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, s_r, rtol=2e-4, atol=2e-4)
    # lane 1's state is what its 97 valid tokens made it, bit for bit untouched after
    _, s_97 = lightning.lightning_recurrent(q[1:, :97], k[1:, :97], v[1:, :97], g[1:, :97], s0[1:])
    np.testing.assert_array_equal(np.asarray(s_r[1]), np.asarray(s_97[0]))


def test_the_slopes_are_lightning_attention_2s():
    got = hybrid.vector_values("slope", (3, 32), None, jnp.float32, "lightning")
    assert got.dtype == jnp.float32 and got.shape == (3, 32)
    np.testing.assert_allclose(got[1], [2.0 ** (-(h + 1) / 4) for h in range(32)], rtol=1e-6)


# -- the mixed step ----------------------------------------------------------------


def test_a_chunk_with_the_lanes_step_beside_it_is_the_two_launches(case):
    """Lane 0 under ``dense_len``, lane 1 past it, lane 2 takes a chunk past
    it: one launch with ``lanes=`` leaves the logits and every leaf that the
    chunk's launch and then the lanes' step leave."""
    params, tokens, _ = case
    cache = init_cache(CFG, 3, 256, dtype=jnp.float32, live=False)
    at = {0: 50, 1: 150, 2: 120}
    for lane, n in at.items():
        cache = hybrid.admit_lane(cache, lane, True, 0 if lane == 2 else hybrid.NO_STOP, -1)
        _, cache = forward(params, CFG, tokens[None, :n] + lane, jnp.arange(n)[None], cache, slot=lane)
    chunk = tokens[None, 120:157]
    pos = (120 + jnp.arange(37))[None]
    lane_tok = jnp.asarray([[5], [9], [3]], jnp.int32)
    lane_pos = jnp.asarray([[50], [150], [255]], jnp.int32)  # the chunk's own lane parked at the arena's last row
    lg_c, two = forward(params, CFG, chunk, pos, cache, slot=2)
    lg_l, two = forward(params, CFG, lane_tok, lane_pos, two)
    lg_m, one = forward(params, CFG, chunk, pos, cache, slot=2, lanes=(lane_tok, lane_pos), last=jnp.int32(36))
    np.testing.assert_allclose(lg_m[0], lg_c[0, 36], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(lg_m[1:3], lg_l[:2, 0], rtol=1e-4, atol=1e-5)
    live = {"k": [(0, 51), (1, 151), (2, 157)], "v": [(0, 51), (1, 151), (2, 157)], "ck": [(0, 11), (1, 36), (2, 38)]}
    for name, lanes in live.items():
        for lane, n in lanes:
            np.testing.assert_allclose(getattr(one, name)[:, lane, :n], getattr(two, name)[:, lane, :n], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(one.state, two.state, rtol=1e-4, atol=1e-5)
    assert one.conv is None and two.conv is None


# -- configuration, plan, parameters ---------------------------------------------------


def test_param_count_is_the_pytrees_size_and_the_published_models():
    for name in ("tiny-minicpm-sala", "minicpm-sala"):
        cfg = get_config(name)
        shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        assert cfg.param_count() == sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    big = get_config("minicpm-sala")
    c = big._hybrid_counts()
    # the matrices are the issue's 9,476,833,280; the vectors (norms, slopes) 373,504
    assert big.param_count() == 9_476_833_280 + 373_504
    assert c["sparse"] + c["dense"] == 253_755_392 + 256 and c["lightning"] + c["dense"] == 285_212_672 + 4_384
    assert big.layer_kinds.count("sparse") == 8 and [i for i, k in enumerate(big.layer_kinds) if k == "sparse"] == [0, 9, 16, 17, 22, 29, 30, 31]
    assert abs(big.residual_scale - 0.24749) < 1e-5 and big.logit_divisor == 16.0 and big.embed_scale == 12.0


def test_flops_per_token_stop_growing_with_the_context_past_dense_len():
    big = get_config("minicpm-sala")
    dense = big.flops_per_token(8192) - big.flops_per_token(4096)
    assert dense == pytest.approx(8 * 4.0 * 32 * 128 * 4096)
    past = big.flops_per_token(40_000) - big.flops_per_token(20_000)
    assert past == pytest.approx(8 * 2.0 * 32 * 128 * 20_000 / 16)  # the pooled keys alone


def test_the_plan_and_the_cache_name_both_kinds():
    plan = hybrid.plan_hybrid(CFG, use_pallas=False)
    assert plan.kinds() == {"lightning": ("xla_chunked", "xla_step"),
                            "sparse": ("xla:block_mask", "xla:attention_reference+xla:block_gather")}
    big = hybrid.plan_hybrid(get_config("minicpm-sala"), use_pallas=True)
    assert big.sparse_decode == "pallas:flash_decode+pallas:sparse_decode" and big.describe()["arena"] == "stack+layer"
    cache = jax.eval_shape(lambda: init_cache(get_config("minicpm-sala"), 8, 49152))
    assert list(cache.leaves()) == ["k", "v", "ck", "state"] and cache.conv is None
    assert cache.k.shape == (8, 8, 49152, 2, 128) and cache.ck.shape == (8, 8, 3072, 2, 128)
    assert cache.state.shape == (24, 8, 32, 128, 128) and cache.state.dtype == jnp.float32


def test_init_cache_says_what_it_allows():
    import dataclasses

    two = dataclasses.replace(CFG, layer_kinds=("sparse", "full", "lightning", "lightning") * 2)
    with pytest.raises(ValueError, match=r"at most one of \('mla', 'full', 'sparse'\).*\['full', 'sparse'\]"):
        init_cache(two, 1, 64)
    with pytest.raises(ValueError, match="whole key blocks of 16"):
        init_cache(CFG, 1, 100)


# -- the cache manager on K/V rows, pooled keys and a recurrent state in one slot -------

ENGINE = {"max_batch": 2, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 32}
TURNS = [("turn one of a session that goes on for a while and says a good deal before it ends", 11),
         ("and a second turn that is not much shorter", 9), ("a third", 7),
         ("the fourth turn brings a tool's output back", 8), ("and the fifth ends it", 6)]


def make_engine(name="tiny-minicpm-sala", **over):
    from agentainer_tpu.engine.llm import LLMEngine

    return LLMEngine.create(name, options={**ENGINE, **over})


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


def test_five_turns_through_the_engine_are_a_plain_loop_over_forward(uninterrupted):
    eng = make_engine(skip_warmup=True)
    try:
        params, tok = eng.params, eng.tokenizer
    finally:
        eng.shutdown()
    cache = init_cache(CFG, 1, 256, dtype=jnp.float32)
    pos, pending, got = 0, [], []
    run = jax.jit(lambda toks, at, cache: forward(params, CFG, toks, at, cache))
    for text, n in TURNS:
        feed = pending + tok.encode(text)
        logits, cache = run(jnp.asarray([feed], jnp.int32), (pos + jnp.arange(len(feed)))[None], cache)
        pos += len(feed)
        out = [int(jnp.argmax(logits[0, -1]))]
        while len(out) < n:
            logits, cache = run(jnp.asarray([[out[-1]]], jnp.int32), jnp.full((1, 1), pos), cache)
            pos += 1
            out.append(int(jnp.argmax(logits[0, 0])))
        pending = [out[-1]]
        got.append(out)
    assert pos > SIZES.dense_len + 40  # the session passed ``dense_len``: its later turns chose their blocks
    assert got == uninterrupted[0]


def test_kill_and_resume_after_every_turn_is_token_identical(uninterrupted):
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


def test_four_sessions_over_two_lanes_evict_snapshot_restore_like_never_evicting():
    """Every turn but the first two finds its session evicted, restores its
    snapshot (K/V rows, pooled keys, state) into a lane another session just
    left, and goes on: the tokens are those of four lanes."""
    names = ["a", "b", "c", "d"]
    said = {n: [(f"{n} says: {text}", k) for text, k in TURNS[:3]] for n in names}

    async def run(lanes: int):
        eng = make_engine(max_batch=lanes)
        eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
        blobs, out = {}, {n: [] for n in names}
        try:
            for turn in range(3):
                for n in names:
                    if not eng.has_session(n) and n in blobs:
                        assert await eng.restore_session(n, blobs[n]) is True
                    text, k = said[n][turn]
                    out[n].append((await eng.chat(n, text, max_tokens=k))["tokens"])
                    blobs[n] = await eng.snapshot_session(n)
            return out, eng.metrics()
        finally:
            eng.shutdown()

    evicting, m = asyncio.run(run(2))
    roomy, m4 = asyncio.run(run(4))
    assert evicting == roomy
    assert m["cache"]["state_restores"] == 8 and m["session_evictions_total"] >= 8 and m4["cache"]["state_restores"] == 0
    assert m["phases"]["engine.restore"]["n"] == 8 and m["phases"]["engine.snapshot"]["n"] >= 12
    assert m["attention"]["sparse"]["steps_sparse"] > 0  # the sessions' last turns were past ``dense_len``


def test_a_parked_sessions_leaves_are_bit_identical_after_another_lanes_steps():
    async def run():
        eng = make_engine(max_batch=3)
        try:
            await eng.chat("a", "the first session says a few words " * 3, max_tokens=13)
            lane = eng.sessions["a"]
            n = eng.slots[lane].position

            def held():
                c = eng.cache
                return [np.asarray(x) for x in (c.k[:, lane, :n], c.v[:, lane, :n], c.ck[:, lane, : (n - 8) // 4 + 1],
                                                c.state[:, lane], c.state[:, 2])]

            before = held()
            steps0 = eng.forward_passes
            await eng.chat("b", "the second session talks for much longer than the first did " * 2, max_tokens=60)
            assert eng.forward_passes - steps0 > 32
            return before, held(), n
        finally:
            eng.shutdown()

    before, after, n = asyncio.run(run())
    for x, y in zip(before, after):
        assert np.array_equal(x, y)
    assert n > SIZES.dense_len and before[2].any() and before[3].any() and not before[4].any()


def test_a_snapshot_ships_the_pooled_keys_with_k_and_no_conv():
    from agentainer_tpu.engine.checkpoint import deserialize_snapshot

    async def run():
        engines = {"sala": make_engine(skip_warmup=True), "olmo": make_engine("tiny-olmo-hybrid", skip_warmup=True)}
        try:
            blobs = {}
            for name, eng in engines.items():
                await eng.chat("s", "hello there, this is a document of some length", max_tokens=4)
                eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
                blobs[name] = await eng.snapshot_session("s")
            crossed = [await engines["olmo"].restore_session("t", blobs["sala"]),
                       await engines["sala"].restore_session("t", blobs["olmo"])]
            own = await engines["sala"].restore_session("u", blobs["sala"])
            position = engines["sala"].slots[engines["sala"].sessions["s"]].position
            return deserialize_snapshot(blobs["sala"]), crossed, own, position
        finally:
            for e in engines.values():
                e.shutdown()

    (leaves, header), crossed, own, position = asyncio.run(run())
    assert set(leaves) == {"k", "v", "ck", "state"} and header["position"] == position
    assert leaves["k"].shape == (4, position, 2, 16) == leaves["v"].shape
    assert leaves["ck"].shape == (4, 64 // 4, 2, 16)  # the snapshot's bucket of 64 rows, a pooled key every 4
    assert leaves["state"].shape == (4, 4, 16, 16)
    assert header["leaves"]["ck"]["positional"] is False
    assert crossed == [False, False] and own is True


@pytest.mark.parametrize("option", ["speculative", "paged_kv", "kv_tiering", "fused_decode", "prefix_cache"])
def test_a_feature_the_state_cannot_hold_is_an_error_when_asked_for(option):
    with pytest.raises(ValueError, match=option):
        make_engine(skip_warmup=True, **{option: True})


def test_metrics_name_the_cache_kinds_the_plan_the_selection_and_what_is_off(uninterrupted):
    m = uninterrupted[1]
    cache = m["cache"]
    assert cache["kinds"] == ["k", "v", "ck", "state"]
    total = cache["k_bytes"] + cache["v_bytes"] + cache["ck_bytes"] + cache["state_bytes"]
    assert cache["bytes_per_lane"] * 2 == total == m["kv_arena_bytes"] - 16
    assert set(cache["off"]) == {"speculative", "prefix_cache", "paged_kv", "fused_decode", "kv_tiering", "mesh"}
    assert m["model_arch"]["layer_kinds"] == {"lightning": 4, "sparse": 4} and m["model_arch"]["dense_layers"] == 8
    att = m["attention"]
    assert (att["lightning_prefill"], att["lightning_decode"]) == ("xla_chunked", "xla_step")
    assert att["sparse_prefill"] == att["prefill"] == "xla:block_mask"
    assert att["sparse_decode"] == att["decode"] == "xla:attention_reference+xla:block_gather"
    sp = att["sparse"]
    assert {k: sp[k] for k in SIZES._fields} == SIZES._asdict() and sp["layers"] == 4
    assert sp["steps_dense"] > 0 and sp["steps_sparse"] > 0 and sp["rows_read"] < sp["rows_live"]
    assert sp["blocks_forced"] <= sp["blocks_selected"] <= sp["blocks_live"] and sp["pooled_rows_scored"] > 0
    lin = m["linear"]
    assert lin["kind"] == "lightning" and lin["conv"] is False and lin["state_bytes_lane"] == 4 * 4 * 16 * 16 * 4
    assert lin["rows_chunked"] > 0 and lin["steps"] > 0
    assert sp["steps_dense"] + sp["steps_sparse"] == lin["rows_chunked"] + lin["steps"]
    assert m["mixed_launches"] == 0 and m["moe"]["impl"] == "none"


# -- a long session's snapshot is hundreds of MB: the store takes it in parts ---------


def test_a_blob_over_the_stores_frame_goes_in_parts_and_is_read_back_whole_or_not_at_all(monkeypatch):
    """The store socket closes a connection on a frame over 64 MiB (my chip
    run, PR 54: every snapshot of a 20k-row session failed with a connection
    reset). ``StoreClient.set_bytes`` writes such a blob as parts and a
    manifest, the manifest last; a reader gets the whole blob of ONE
    generation or nothing; the generation before is deleted."""
    from agentainer_tpu.runtime import store_client
    from agentainer_tpu.runtime.store_client import StoreClient

    monkeypatch.setattr(store_client, "PART_BYTES", 1000)
    rng = np.random.default_rng(0)

    async def run():
        client = StoreClient()  # not connected: the ops run on its own dict, as the engine's tests do
        small, big, bigger = (rng.integers(0, 256, n, dtype=np.uint8).tobytes() for n in (1000, 4321, 9000))
        await client.set_bytes("k", small)
        assert await client.get_bytes("k") == small and await client.keys("k*") == ["k"]
        await client.set_bytes("k", big)
        first = sorted(await client.keys("k*"))
        assert await client.get_bytes("k") == big and len(first) == 1 + 5
        await client.set_bytes("k", bigger)
        second = sorted(await client.keys("k*"))
        assert await client.get_bytes("k") == bigger and len(second) == 1 + 9 and not set(first[1:]) & set(second)
        await client.delete(second[3])  # a part lost or expired: no snapshot, never a torn one
        assert await client.get_bytes("k") is None
        await client.set_bytes("k", small)  # a short blob again: one key holds it
        assert await client.get_bytes("k") == small
        assert await client.get_bytes("absent") is None

    asyncio.run(run())
