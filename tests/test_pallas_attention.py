"""Pallas flash-attention kernels vs the XLA reference (interpret mode).

CPU CI runs the exact TPU kernel bodies under ``interpret=True``; the XLA
``attention_reference`` + ``cache_mask`` pair is the behavioral spec
(SURVEY.md §4: promote intent to real tests with TPU-less fixtures).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.ops.attention import (
    attention_reference,
    cache_mask,
    causal_mask,
    gather_pages,
)
from agentainer_tpu.ops.pallas_attention import (
    flash_decode,
    flash_prefill,
    fused_paged_flash_decode,
    fused_paged_flash_prefill,
)


def _rand(key, *shape):
    return jax.random.normal(key, shape, jnp.float32)


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (2, 2), (8, 1)])
def test_prefill_causal_matches_reference(heads, kv_heads):
    b, t, hd = 2, 40, 128
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(k1, b, t, heads, hd)
    k = _rand(k2, b, t, kv_heads, hd)
    v = _rand(k3, b, t, kv_heads, hd)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    got = flash_prefill(q, k, v, positions, interpret=True)
    mask = jnp.broadcast_to(causal_mask(t), (b, t, t))
    want = attention_reference(q, k, v, mask=mask)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_prefill_cached_ragged_positions():
    """Continuous-batching shape: each sequence prefills at its own offset
    into a shared arena; arena length not a multiple of the KV block."""
    b, t, heads, kv_heads, hd, s = 3, 16, 4, 2, 128, 384
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    q = _rand(keys[0], b, t, heads, hd)
    ck = _rand(keys[1], b, s, kv_heads, hd)
    cv = _rand(keys[2], b, s, kv_heads, hd)
    offsets = jnp.array([0, 77, 300], jnp.int32)
    positions = offsets[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]

    got = flash_prefill(q, ck, cv, positions, interpret=True)
    want = attention_reference(q, ck, cv, mask=cache_mask(positions, s))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_prefill_multiple_q_blocks():
    b, t, heads, kv_heads, hd, s = 1, 320, 4, 4, 128, 320
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(keys[0], b, t, heads, hd)
    k = _rand(keys[1], b, s, kv_heads, hd)
    v = _rand(keys[2], b, s, kv_heads, hd)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    got = flash_prefill(q, k, v, positions, block_q=128, block_k=128, interpret=True)
    want = attention_reference(q, k, v, mask=cache_mask(positions, s))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block_k", [128, 512])
def test_decode_matches_reference(block_k):
    b, heads, kv_heads, hd, s = 4, 4, 2, 128, 384
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(keys[0], b, heads, hd)
    ck = _rand(keys[1], b, s, kv_heads, hd)
    cv = _rand(keys[2], b, s, kv_heads, hd)
    positions = jnp.array([0, 5, 200, 383], jnp.int32)

    got = flash_decode(q, ck, cv, positions, block_k=block_k, interpret=True)
    want = attention_reference(
        q[:, None], ck, cv, mask=cache_mask(positions[:, None], s)
    )[:, 0]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# fused paged kernels: the block-table walk via scalar prefetch must agree
# with the gather-then-flash reference path (the dispatch seam's other half)
# on the exact same pool — including shared pages and ragged positions.


def _paged_fixture(seed, b, nb, ps, kv, hd, n_pages):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool_k = _rand(keys[0], n_pages, kv, ps, hd)
    pool_v = _rand(keys[1], n_pages, kv, ps, hd)
    # non-trivial mapping: scrambled page ids, lane 0 and 1 SHARE page 7
    # (paged prefix sharing) — the walk must not assume contiguity or
    # exclusivity
    table = np.array(
        jax.random.permutation(keys[2], n_pages)[: b * nb], np.int32
    ).reshape(b, nb)
    if b >= 2:
        table[0, 0] = 7
        table[1, 0] = 7
    return pool_k, pool_v, jnp.asarray(table)


def test_fused_paged_decode_matches_gather_path():
    b, heads, kv, hd, ps, nb = 3, 4, 2, 128, 16, 4
    pool_k, pool_v, table = _paged_fixture(5, b, nb, ps, kv, hd, n_pages=16)
    q = _rand(jax.random.PRNGKey(6), b, heads, hd)
    positions = jnp.array([0, 30, 63], jnp.int32)

    got = fused_paged_flash_decode(
        q, pool_k, pool_v, table, positions, interpret=True
    )
    ck, cv = gather_pages(pool_k, pool_v, table)
    want = flash_decode(q, ck, cv, positions, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    ref = attention_reference(
        q[:, None], ck, cv, mask=cache_mask(positions[:, None], nb * ps)
    )[:, 0]
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_fused_paged_prefill_ragged_matches_gather_path():
    """Chunked prefill at per-lane offsets (continuous batching): each lane
    attends its own pages at its own position — the single masking rule,
    now walked through the table."""
    b, t, heads, kv, hd, ps, nb = 3, 16, 4, 2, 128, 16, 4
    pool_k, pool_v, table = _paged_fixture(7, b, nb, ps, kv, hd, n_pages=16)
    q = _rand(jax.random.PRNGKey(8), b, t, heads, hd)
    offsets = jnp.array([0, 21, 48], jnp.int32)
    positions = offsets[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]

    got = fused_paged_flash_prefill(
        q, pool_k, pool_v, table, positions, interpret=True
    )
    ck, cv = gather_pages(pool_k, pool_v, table)
    want = flash_prefill(q, ck, cv, positions, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    ref = attention_reference(q, ck, cv, mask=cache_mask(positions, nb * ps))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_fused_paged_prefill_multiple_q_blocks():
    b, t, heads, kv, hd, ps, nb = 1, 160, 4, 4, 128, 32, 8
    pool_k, pool_v, table = _paged_fixture(9, b, nb, ps, kv, hd, n_pages=8)
    q = _rand(jax.random.PRNGKey(10), b, t, heads, hd)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    got = fused_paged_flash_prefill(
        q, pool_k, pool_v, table, positions, block_q=64, interpret=True
    )
    ck, cv = gather_pages(pool_k, pool_v, table)
    want = attention_reference(q, ck, cv, mask=cache_mask(positions, nb * ps))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_fused_paged_decode_bf16():
    b, heads, kv, hd, ps, nb = 2, 4, 2, 128, 16, 4
    pool_k, pool_v, table = _paged_fixture(11, b, nb, ps, kv, hd, n_pages=16)
    pool_k = pool_k.astype(jnp.bfloat16)
    pool_v = pool_v.astype(jnp.bfloat16)
    q = _rand(jax.random.PRNGKey(12), b, heads, hd).astype(jnp.bfloat16)
    positions = jnp.array([15, 62], jnp.int32)

    got = fused_paged_flash_decode(
        q, pool_k, pool_v, table, positions, interpret=True
    )
    assert got.dtype == jnp.bfloat16
    ck, cv = gather_pages(pool_k, pool_v, table)
    want = attention_reference(
        q[:, None], ck, cv, mask=cache_mask(positions[:, None], nb * ps)
    )[:, 0]
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), rtol=3e-2, atol=3e-2
    )


def test_decode_bf16():
    b, heads, kv_heads, hd, s = 2, 4, 2, 128, 256
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand(keys[0], b, heads, hd).astype(jnp.bfloat16)
    ck = _rand(keys[1], b, s, kv_heads, hd).astype(jnp.bfloat16)
    cv = _rand(keys[2], b, s, kv_heads, hd).astype(jnp.bfloat16)
    positions = jnp.array([31, 255], jnp.int32)

    got = flash_decode(q, ck, cv, positions, interpret=True)
    want = attention_reference(
        q[:, None], ck, cv, mask=cache_mask(positions[:, None], s)
    )[:, 0]
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), rtol=3e-2, atol=3e-2
    )
