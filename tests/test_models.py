"""Model correctness tests (CPU, float32 for determinism).

The critical invariant for the serving engine: prefill+decode through the
static KV cache must reproduce the full no-cache forward pass.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.engine.sampling import sample
from agentainer_tpu.models.configs import get_config
from agentainer_tpu.models.llama import KVCache, forward, greedy_decode, init_params
from agentainer_tpu.ops.attention import attention_reference, causal_mask
from agentainer_tpu.ops.rope import apply_rope


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def test_forward_shapes(tiny):
    cfg, params = tiny
    tokens = jnp.zeros((2, 5), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(5), (2, 5))
    logits, cache = forward(params, cfg, tokens, positions)
    assert logits.shape == (2, 5, cfg.vocab_size)
    assert cache is None


def test_causality(tiny):
    """Changing a future token must not change past logits."""
    cfg, params = tiny
    key = jax.random.PRNGKey(1)
    t1 = jax.random.randint(key, (1, 8), 0, cfg.vocab_size)
    t2 = t1.at[0, 6].set((t1[0, 6] + 1) % cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(8), (1, 8))
    l1, _ = forward(params, cfg, t1, pos)
    l2, _ = forward(params, cfg, t2, pos)
    np.testing.assert_allclose(l1[0, :6], l2[0, :6], rtol=1e-5, atol=1e-5)
    assert not np.allclose(l1[0, 6:], l2[0, 6:])


def test_kv_cache_matches_full_forward(tiny):
    """Prefill + token-by-token decode through the cache == full forward."""
    cfg, params = tiny
    b, t, s = 2, 10, 16
    tokens = jax.random.randint(jax.random.PRNGKey(2), (b, t), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    full_logits, _ = forward(params, cfg, tokens, pos)

    # prefill first 4 tokens, then decode the rest one at a time
    cache = KVCache.create(cfg, b, s, dtype=jnp.float32)
    pre = 4
    logits, cache = forward(params, cfg, tokens[:, :pre], pos[:, :pre], cache)
    np.testing.assert_allclose(logits, full_logits[:, :pre], rtol=2e-4, atol=2e-4)
    for i in range(pre, t):
        step_logits, cache = forward(
            params, cfg, tokens[:, i : i + 1], pos[:, i : i + 1], cache
        )
        np.testing.assert_allclose(
            step_logits[:, 0], full_logits[:, i], rtol=2e-4, atol=2e-4
        )


def test_ragged_positions_in_one_batch(tiny):
    """Two sequences at different decode positions in one batch — the
    continuous-batching case — must each match their solo result."""
    cfg, params = tiny
    s = 16
    toks_a = jax.random.randint(jax.random.PRNGKey(3), (1, 6), 0, cfg.vocab_size)
    toks_b = jax.random.randint(jax.random.PRNGKey(4), (1, 3), 0, cfg.vocab_size)

    # solo references
    la, _ = forward(params, cfg, toks_a, jnp.arange(6)[None])
    lb, _ = forward(params, cfg, toks_b, jnp.arange(3)[None])

    # batched prefill of the common 3-token span
    cache = KVCache.create(cfg, 2, s, dtype=jnp.float32)
    both = jnp.concatenate([toks_a[:, :3], toks_b], axis=0)  # [2,3]
    pos = jnp.broadcast_to(jnp.arange(3), (2, 3))
    logits, cache = forward(params, cfg, both, pos, cache)
    np.testing.assert_allclose(logits[0], la[0, :3], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(logits[1], lb[0], rtol=2e-4, atol=2e-4)
    # ragged decode step: row 0 consumes a's 4th token at pos 3, row 1
    # re-feeds its last token at pos 2 (an idle/pad write) — row 0's logits
    # must still match a's solo forward
    step, cache = forward(
        params,
        cfg,
        jnp.stack([toks_a[0, 3:4], toks_b[0, 2:3]]),
        jnp.array([[3], [2]]),
        cache,
    )
    np.testing.assert_allclose(step[0, 0], la[0, 3], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(step[1, 0], lb[0, 2], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("group", ["decode", "lanes"])
def test_a_lane_parked_at_the_arenas_last_row_reads_one_row_and_still_writes_there(tiny, group):
    """The engine parks idle lanes at the arena's last row. Such a lane's
    one-token row is handed to the attention at position 0 (one K/V block for
    the kernel to fetch, not the arena row), in the T == 1 step and in the
    lanes' group of the mixed step alike; its K/V rows are still written at
    the last row, and a lane one row short of it attends as before."""
    from agentainer_tpu.ops.attention import _reference_dense

    cfg, params = tiny
    lanes, s = 3, 64
    cache = KVCache.create(cfg, lanes, s, dtype=jnp.float32)
    tok = jnp.array([[3], [4], [5]], jnp.int32)
    pos = jnp.array([[10], [s - 1], [s - 2]], jnp.int32)
    seen = jnp.array([[10], [0], [s - 2]], jnp.int32)

    def told(q, ck, cv, positions, *rest):
        # the one-token rows read up to ``seen`` whatever they were handed
        return _reference_dense(q, ck, cv, seen if q.shape[1] == 1 else positions, *rest)

    if group == "decode":
        call = lambda **kw: forward(params, cfg, tok, pos, cache, **kw)  # noqa: E731
    else:
        chunk = jnp.arange(1, 9, dtype=jnp.int32)[None]
        call = lambda **kw: forward(  # noqa: E731
            params, cfg, chunk, chunk - 1, cache, slot=jnp.int32(1), lanes=(tok, pos), last=jnp.int32(7), **kw
        )
    logits, new = call()
    want, _ = call(cache_attn_impl=told)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    # and not what reading the whole row gives: the parked lane's logits moved
    whole, _ = call(cache_attn_impl=lambda q, ck, cv, positions, *rest: _reference_dense(
        q, ck, cv, pos if q.shape[1] == 1 else positions, *rest))
    rows = slice(0, 3) if group == "decode" else slice(1, 4)
    moved = np.abs(np.asarray(logits)[rows] - np.asarray(whole)[rows]).reshape(3, -1).max(-1)
    assert moved[0] == 0 and moved[2] == 0 and moved[1] > 0
    assert np.isfinite(np.asarray(logits)).all()
    written = np.asarray(new.k)
    assert written[:, 1, s - 1].any() and written[:, 0, 10].any() and written[:, 2, s - 2].any()
    assert not written[:, 1, 0].any() or group == "lanes"  # row 0 is the chunk's in the mixed step


def test_gqa_against_naive_numpy():
    """attention_reference (grouped einsum) vs a naive per-head numpy loop."""
    rng = np.random.default_rng(0)
    b, tq, tk, h, kv, hd = 2, 4, 6, 4, 2, 8
    q = rng.standard_normal((b, tq, h, hd)).astype(np.float32)
    k = rng.standard_normal((b, tk, kv, hd)).astype(np.float32)
    v = rng.standard_normal((b, tk, kv, hd)).astype(np.float32)
    mask = rng.random((b, tq, tk)) > 0.3

    out = np.asarray(attention_reference(jnp.array(q), jnp.array(k), jnp.array(v), jnp.array(mask)))

    group = h // kv
    expected = np.zeros((b, tq, h, hd), np.float32)
    for bi in range(b):
        for hi in range(h):
            kvh = hi // group
            scores = (q[bi, :, hi] @ k[bi, :, kvh].T) / np.sqrt(hd)
            scores = np.where(mask[bi], scores, -1e30)
            e = np.exp(scores - scores.max(axis=-1, keepdims=True))
            p = e / e.sum(axis=-1, keepdims=True)
            expected[bi, :, hi] = p @ v[bi, :, kvh]
    np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-4)


def test_rope_properties():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, 16))
    pos = jnp.broadcast_to(jnp.arange(4), (1, 4))
    rot = apply_rope(x, pos, theta=10_000.0)
    # norms preserved (rotation), position 0 is identity
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(rot), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )
    np.testing.assert_allclose(rot[0, 0], x[0, 0], rtol=1e-6)
    # relative property: dot(q_rot(p), k_rot(p+d)) depends only on d
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 16))
    def dot_at(p, d):
        qr = apply_rope(q, jnp.array([[p]]), 10_000.0)
        kr = apply_rope(k, jnp.array([[p + d]]), 10_000.0)
        return float(jnp.sum(qr * kr))
    assert abs(dot_at(0, 3) - dot_at(5, 3)) < 1e-3


def test_greedy_decode_matches_nocache(tiny):
    cfg, params = tiny
    prompt = jax.random.randint(jax.random.PRNGKey(5), (1, 4), 0, cfg.vocab_size)
    out = greedy_decode(params, cfg, prompt, max_new_tokens=5, cache_len=16, dtype=jnp.float32)
    assert out.shape == (1, 5)
    # step-by-step argmax with full recompute (no cache)
    seq = prompt
    expected = []
    for _ in range(5):
        pos = jnp.broadcast_to(jnp.arange(seq.shape[1]), seq.shape)
        logits, _ = forward(params, cfg, seq, pos)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        expected.append(int(nxt[0]))
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    assert [int(t) for t in out[0]] == expected


def test_moe_forward_runs():
    cfg = get_config("tiny-moe")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = jnp.zeros((2, 4), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(4), (2, 4))
    logits, _ = forward(params, cfg, tokens, pos)
    assert logits.shape == (2, 4, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_sampling():
    logits = jnp.array([[0.0, 10.0, 0.0, 0.0], [5.0, 0.0, 0.0, 0.0]], jnp.float32)
    key = jax.random.PRNGKey(0)
    # greedy
    assert sample(logits, key, temperature=0.0).tolist() == [1, 0]
    # top-k=1 == greedy even at high temperature
    assert sample(logits, key, temperature=5.0, top_k=1).tolist() == [1, 0]
    # per-request temperature: row0 greedy, row1 sampled (top_k=1 → still argmax)
    assert sample(logits, key, temperature=jnp.array([0.0, 2.0]), top_k=1).tolist() == [1, 0]
    # top_p tiny → nucleus collapses to argmax
    assert sample(logits, key, temperature=3.0, top_p=1e-6).tolist() == [1, 0]
