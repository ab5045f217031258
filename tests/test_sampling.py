"""Sampler filter edges: top_k must clamp to the vocab.

``jnp.sort(...)[:, -top_k]`` with top_k > V wraps around to an arbitrary
mid-distribution threshold and silently corrupts the filter — top_k >= V
must mean "keep everything" (the filter disabled), and top_k = V-1 must
exclude exactly the lowest-logit token.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.engine.llm import LLMEngine
from agentainer_tpu.engine.sampling import APPROX_SEG, sample, sample_step

V = 8


def test_top_k_at_or_above_vocab_is_a_no_op():
    """top_k == V and top_k > V both keep the full distribution: with the
    same key they sample the exact token the unfiltered sampler picks."""
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(jax.random.PRNGKey(1), (4, V))
    for i in range(16):
        k = jax.random.fold_in(key, i)
        want = sample(logits, k, temperature=1.0, top_k=0)
        assert sample(logits, k, temperature=1.0, top_k=V).tolist() == want.tolist()
        assert (
            sample(logits, k, temperature=1.0, top_k=V + 7).tolist() == want.tolist()
        )


def test_top_k_vocab_minus_one_excludes_only_the_min():
    """top_k = V-1 masks exactly the argmin: over many keys at a hot
    temperature every token EXCEPT the argmin shows up, and the argmin
    never does."""
    logits = jnp.asarray(
        np.linspace(0.0, 1.0, V, dtype=np.float32)[None, :]
    )  # argmin = 0, unique
    seen = set()
    for i in range(300):
        t = sample(
            logits, jax.random.PRNGKey(i), temperature=20.0, top_k=V - 1
        )
        seen.add(int(t[0]))
    assert 0 not in seen, seen
    assert seen == set(range(1, V)), seen


# ---------------------------------------------------------------------------
# sample vs sample_step parity: the fused decode loop's in-loop sampler
# must draw the EXACT token sample() draws from the same key — fused
# bit-exactness (test_fused_decode.py) reduces to this battery.


def _step(logits, key, t, k, p):
    B = logits.shape[0]
    return sample_step(
        logits,
        key,
        jnp.full((B,), t, jnp.float32),
        jnp.full((B,), k, jnp.int32),
        jnp.full((B,), p, jnp.float32),
    )


def _parity(t, k, p, keys=16, batch=4, seed=1):
    logits = jax.random.normal(jax.random.PRNGKey(seed), (batch, V))
    for i in range(keys):
        kk = jax.random.fold_in(jax.random.PRNGKey(0), i)
        want = sample(logits, kk, temperature=t, top_k=k, top_p=p)
        got = _step(logits, kk, t, k, p)
        assert got.tolist() == want.tolist(), (t, k, p, i)


def test_step_parity_greedy():
    _parity(0.0, 0, 1.0)


def test_step_parity_temperature():
    _parity(1.0, 0, 1.0)
    _parity(0.3, 0, 1.0, seed=2)
    _parity(2.5, 0, 1.0, seed=3)


def test_step_parity_top_k():
    _parity(1.0, 3, 1.0)
    # the clamp edges from the tests above, now through the array sampler
    _parity(1.0, V, 1.0, seed=2)
    _parity(1.0, V + 7, 1.0, seed=3)
    _parity(20.0, V - 1, 1.0, seed=4)


def test_step_parity_top_p():
    _parity(1.0, 0, 0.5)
    _parity(1.0, 0, 0.9, seed=2)
    _parity(1.0, 0, 1e-6, seed=3)  # keeps exactly the top token


def test_step_parity_top_k_and_top_p():
    _parity(0.7, 4, 0.8)
    _parity(1.3, 2, 0.6, seed=2)


def test_step_mixed_lane_batch():
    """One batch mixing greedy / temperature / top-k / top-p lanes: each
    lane must match what sample() produces when the whole batch runs at
    that lane's settings (per-lane masks can't bleed across rows)."""
    B = 4
    lanes = [(0.0, 0, 1.0), (1.0, 0, 1.0), (0.8, 3, 1.0), (1.2, 0, 0.7)]
    logits = jax.random.normal(jax.random.PRNGKey(9), (B, V))
    temps = jnp.asarray([t for t, _, _ in lanes], jnp.float32)
    topks = jnp.asarray([k for _, k, _ in lanes], jnp.int32)
    topps = jnp.asarray([p for _, _, p in lanes], jnp.float32)
    for i in range(16):
        kk = jax.random.fold_in(jax.random.PRNGKey(5), i)
        got = sample_step(logits, kk, temps, topks, topps)
        for lane, (t, k, p) in enumerate(lanes):
            want = sample(logits, kk, temperature=t, top_k=k, top_p=p)
            assert int(got[lane]) == int(want[lane]), (lane, i)


# ---------------------------------------------------------------------------
# approx_topk (segmented top-k via lax.approx_max_k): opt-in, exact is the
# default. Greedy is untouched; within the segment it's bit-exact; past the
# segment the filter is STRICTLY STRONGER than exact, which bounds divergence.


def _step_approx(logits, key, t, k, p):
    B = logits.shape[0]
    return sample_step(
        logits,
        key,
        jnp.full((B,), t, jnp.float32),
        jnp.full((B,), k, jnp.int32),
        jnp.full((B,), p, jnp.float32),
        approx_topk=True,
    )


def test_approx_topk_greedy_unaffected():
    logits = jax.random.normal(jax.random.PRNGKey(7), (4, V))
    kk = jax.random.PRNGKey(8)
    assert (
        _step_approx(logits, kk, 0.0, 0, 1.0).tolist()
        == _step(logits, kk, 0.0, 0, 1.0).tolist()
    )


def test_approx_topk_exact_when_vocab_fits_segment():
    """V <= APPROX_SEG: the segment IS the full sorted vocab, so the
    segmented path must be token-identical to the exact one."""
    assert V <= APPROX_SEG
    for t, k, p, seed in [(1.0, 3, 1.0, 1), (0.7, 4, 0.8, 2), (1.0, 0, 0.5, 3)]:
        logits = jax.random.normal(jax.random.PRNGKey(seed), (4, V))
        for i in range(16):
            kk = jax.random.fold_in(jax.random.PRNGKey(11), i)
            want = _step(logits, kk, t, k, p)
            got = _step_approx(logits, kk, t, k, p)
            assert got.tolist() == want.tolist(), (t, k, p, i)


def _exact_kept(logits_np, k, p):
    """The exact sampler's kept-token mask, recomputed independently."""
    B, Vn = logits_np.shape
    desc = np.sort(logits_np, -1)[:, ::-1]
    keep = np.ones_like(logits_np, bool)
    if k > 0:
        kth = desc[:, min(k, Vn) - 1][:, None]
        keep &= logits_np >= kth
        desc = np.where(desc < kth, -1e30, desc)
    if p < 1.0:
        e = np.exp(desc - desc.max(-1, keepdims=True))
        cum = np.cumsum(e / e.sum(-1, keepdims=True), -1)
        cutoff_idx = (cum < p).sum(-1)
        cutoff = np.take_along_axis(desc, cutoff_idx[:, None], -1)
        keep &= logits_np >= cutoff
    return keep


def test_approx_topk_divergence_bounded_by_exact_filter():
    """V > APPROX_SEG: every approx-sampled token must lie inside BOTH the
    exact path's kept set (the segmented filter only ever drops more) and
    the top-APPROX_SEG candidate set — the two halves of the documented
    divergence bound."""
    Vbig = APPROX_SEG * 2
    logits = jax.random.normal(jax.random.PRNGKey(21), (4, Vbig)) * 3.0
    lnp = np.asarray(logits)
    seg_floor = np.sort(lnp, -1)[:, ::-1][:, APPROX_SEG - 1]
    for t, k, p in [(1.0, 8, 1.0), (1.0, 0, 0.9), (0.8, 16, 0.7)]:
        keep = _exact_kept(lnp, k, p)
        for i in range(24):
            kk = jax.random.fold_in(jax.random.PRNGKey(31), i)
            got = np.asarray(_step_approx(logits, kk, t, k, p))
            for b in range(lnp.shape[0]):
                tok = int(got[b])
                assert keep[b, tok], (t, k, p, i, b, tok)
                assert lnp[b, tok] >= seg_floor[b], (t, k, p, i, b, tok)


def test_step_mixed_lane_batch_jits_once():
    """The whole point of the array sampler: different per-lane settings
    are DATA, not compile-time constants — one jitted fn serves them all."""
    fn = jax.jit(sample_step)
    logits = jax.random.normal(jax.random.PRNGKey(3), (2, V))
    key = jax.random.PRNGKey(4)
    a = fn(
        logits, key,
        jnp.asarray([0.0, 1.0], jnp.float32),
        jnp.asarray([0, 3], jnp.int32),
        jnp.asarray([1.0, 0.8], jnp.float32),
    )
    b = fn(
        logits, key,
        jnp.asarray([1.0, 0.0], jnp.float32),
        jnp.asarray([5, 0], jnp.int32),
        jnp.asarray([0.5, 1.0], jnp.float32),
    )
    assert a.shape == b.shape == (2,)
    assert fn._cache_size() == 1


# ---------------------------------------------------------------------------
# the engine's first-token program (ISSUE 24): sample_step compiled once per
# engine must draw what the eager call it replaces drew, and must not shift
# the engine's key stream.

TINY = {"max_batch": 4, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 32, "skip_warmup": True}
# the engine's two static choices: exact or approximate top-k, and (under a
# mesh) no batch-wide greedy conditional
ENGINES = {
    "dense": {},
    "approx_topk": {"approx_topk": True},
    "meshed": {"tp": 2},
    "paged_fused": {"paged_kv": True, "fused_decode": True},
}


@pytest.fixture(scope="module", params=["dense", "approx_topk", "meshed"])
def sampler_engine(request):
    eng = LLMEngine.create("tiny", options=dict(TINY, **ENGINES[request.param]))
    yield eng
    eng.shutdown()


@pytest.mark.parametrize(
    "t, k, p",
    [
        (0.0, 0, 1.0),  # greedy
        (0.0, 3, 0.5),  # greedy ignores the filters
        (0.9, 0, 1.0),  # temperature
        (1.0, 1, 1.0),  # top-k at its edges: one token, the whole vocab, beyond it
        (1.0, 512, 1.0),
        (1.0, 519, 1.0),
        (1.0, 0, 0.8),  # top-p
        (1.3, 40, 0.95),  # both
    ],
)
def test_first_token_program_draws_what_eager_sample_step_draws(sampler_engine, t, k, p):
    eng = sampler_engine
    V = eng.cfg.vocab_size
    assert V == 512  # the top-k edges above are written against it
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(7), (V,), jnp.float32)
    params = (np.float32(t), np.int32(k), np.float32(p))
    rng = jax.random.PRNGKey(11)
    for i in range(2):
        # the engine's key stream: one split per first token, as the eager
        # call site made it
        want_rng, key = jax.random.split(rng)
        want = sample_step(
            logits[None], key, *(jnp.asarray(x)[None] for x in params),
            greedy_cond=eng.mesh is None, approx_topk=eng.approx_topk,
        )
        rng, first, tok = eng._first_token(logits, rng, *params)
        assert rng.tolist() == want_rng.tolist()
        assert first.shape == (1,) and first.dtype == jnp.int32
        assert tok.shape == () and tok.dtype == jnp.int32
        assert first.tolist() == want.tolist() == [int(tok)], (t, k, p, i)
        if t == 0.0:
            assert int(tok) == int(jnp.argmax(logits))
    assert eng._first_token._cache_size() == 1


# replies of the parent commit (7e3d951, eager sample_step) on this backend:
# a fresh tiny engine serves these three requests in this order
SEEDED_REQUESTS = [
    {"prompt": "the quick brown fox"},
    {"prompt": "the quick brown fox", "temperature": 0.9, "top_k": 50, "top_p": 0.95},
    {"prompt": "jumps over the lazy dog and runs far away from here today", "temperature": 1.3},
]
PARENT_TOKENS = {
    "dense": [
        [373, 166, 349, 189, 82, 250, 50, 144, 373, 166],
        [306, 133, 392, 391, 38, 464, 100, 61, 187, 269],
        [481, 466, 263, 418, 455, 134, 256, 463, 287, 81],
    ],
    "paged_fused": [
        [373, 166, 349, 189, 82, 250, 50, 144, 373, 166],
        [379, 164, 157, 420, 308, 241, 192, 141, 28, 414],
        [266, 276, 354, 441, 431, 239, 279, 191, 147, 114],
    ],
}


@pytest.mark.parametrize("kind", sorted(PARENT_TOKENS))
def test_seeded_engine_replies_as_the_parent_did(kind):
    """The key stream did not shift: one split of the engine's key per first
    token, as before, so greedy and sampled replies are the parent's."""
    eng = LLMEngine.create("tiny", options=dict(TINY, **ENGINES[kind]))
    try:
        async def drive():
            return [
                (await eng.generate(max_tokens=10, ignore_eos=True, **kw))["tokens"]
                for kw in SEEDED_REQUESTS
            ]

        assert asyncio.run(drive()) == PARENT_TOKENS[kind]
    finally:
        eng.shutdown()
