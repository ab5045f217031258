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
    plan_cache_attention,
)
from agentainer_tpu.ops.pallas_attention import (
    _kv_block,
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

    got = flash_prefill(q, k[None], v[None], positions, 0, interpret=True)
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

    got = flash_prefill(q, ck[None], cv[None], positions, 0, interpret=True)
    want = attention_reference(q, ck, cv, mask=cache_mask(positions, s))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_prefill_multiple_q_blocks():
    b, t, heads, kv_heads, hd, s = 1, 320, 4, 4, 128, 320
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(keys[0], b, t, heads, hd)
    k = _rand(keys[1], b, s, kv_heads, hd)
    v = _rand(keys[2], b, s, kv_heads, hd)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    got = flash_prefill(q, k[None], v[None], positions, 0, block_q=128, block_k=128, interpret=True)
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

    got = flash_decode(q, ck[None], cv[None], positions, 0, block_k=block_k, interpret=True)
    want = attention_reference(
        q[:, None], ck, cv, mask=cache_mask(positions[:, None], s)
    )[:, 0]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the layer-indexed kernels: the engine hands them the STACKED arena
# [L, B, S, KV, hd] as the layer scan carries it, with the layer (and the
# prefilling lane's slot) as scalars. They must read exactly what the
# per-layer call reads of that layer's own arrays — and that must be right.


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
@pytest.mark.parametrize(
    "kv_heads,groups,sized",
    [
        (8, 4, False),
        (16, 1, False),
        # two blocks of 16 heads: the head-block grid axis
        (32, 1, False),
        # no 16-head divisor: every head in a block, and the DEFAULT block
        # sizes, which are then cut to the VMEM plan (shorter K/V and q tiles)
        (40, 1, True),
    ],
    ids=["gqa8x4", "mha16", "mha32", "mha40-sized"],
)
@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_kernels_read_layer_of_the_stack(phase, kv_heads, groups, sized, layer):
    n_layers, lanes, s, hd = 3, 3, 384, 128
    heads = kv_heads * groups
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    # every layer (and lane) holds different values: reading the wrong one
    # cannot pass; bf16 is what the engine stores (two heads a 32-bit word)
    stack_k = _rand(keys[0], n_layers, lanes, s, kv_heads, hd).astype(jnp.bfloat16)
    stack_v = _rand(keys[1], n_layers, lanes, s, kv_heads, hd).astype(jnp.bfloat16)
    lay = jnp.int32(layer)
    if phase == "decode":
        q = _rand(keys[2], lanes, heads, hd).astype(jnp.bfloat16)
        # ragged: a fresh lane, one mid-arena, one parked at the scratch row
        positions = jnp.array([5, 200, s - 1], jnp.int32)
        kw = dict(interpret=True) if sized else dict(block_k=128, interpret=True)
        got = flash_decode(q, stack_k, stack_v, positions, lay, **kw)
        per_layer = flash_decode(q, stack_k[layer][None], stack_v[layer][None], positions, 0, **kw)
        want = attention_reference(
            q[:, None], stack_k[layer], stack_v[layer],
            mask=cache_mask(positions[:, None], s),
        )[:, 0]
    else:
        # one lane's prompt chunk against ITS row of the whole arena (slot 2
        # of 3), three q blocks (the last one partial), starting mid-arena
        t, slot = 160, 2
        q = _rand(keys[2], 1, t, heads, hd).astype(jnp.bfloat16)
        positions = (100 + jnp.arange(t, dtype=jnp.int32))[None]
        kw = dict(interpret=True) if sized else dict(block_q=64, block_k=128, interpret=True)
        got = flash_prefill(q, stack_k, stack_v, positions, lay, jnp.int32(slot), **kw)
        row_k, row_v = stack_k[layer, slot : slot + 1], stack_v[layer, slot : slot + 1]
        per_layer = flash_prefill(q, row_k[None], row_v[None], positions, 0, **kw)
        want = attention_reference(q, row_k, row_v, mask=cache_mask(positions, s))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got), np.asarray(per_layer))
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), rtol=3e-2, atol=3e-2
    )


@pytest.mark.parametrize(
    "kv_heads,block,decode_positions",
    [
        (8, 8, 512),  # Mixtral, Llama-3: every head, the measured blocks
        (16, 16, 512),  # OLMoE
        (32, 16, 512),  # two head blocks
        (40, 40, 128),  # no 16-head divisor: whole, and a shorter block
        (24, 24, 256),
        (2, 2, 512),  # a tp shard: padded to a 16-row tile, still in budget
    ],
)
def test_kv_block_is_sized_to_the_vmem_plan(kv_heads, block, decode_positions):
    """K and V blocks, two buffers each, stay inside the call's VMEM plan
    whatever the head count (a block with every head of a 32- or 40-head
    MHA model at 512 positions does not fit the chip's 16 MiB)."""
    from agentainer_tpu.ops.pallas_attention import _DECODE_KV_VMEM

    heads, bk = _kv_block(kv_heads, 128, jnp.bfloat16, 2048, 512, _DECODE_KV_VMEM)
    assert (heads, bk) == (block, decode_positions)
    assert kv_heads % heads == 0 and bk % 128 == 0
    padded = -(-heads // 16) * 16  # bf16 sublane tile
    assert bk == 128 or 4 * bk * padded * 128 * 2 <= _DECODE_KV_VMEM
    # a short arena is one block, whatever the plan allows
    assert _kv_block(kv_heads, 128, jnp.bfloat16, 100, 512, _DECODE_KV_VMEM)[1] == 128


@pytest.mark.parametrize(
    "heads,kv_heads,head_dim,ok",
    [
        (32, 8, 128, True),
        (16, 16, 128, True),
        (40, 40, 128, True),
        (8, 1, 128, True),  # MQA
        (28, 4, 128, True),
        (8, 2, 256, True),
        (40, 10, 128, False),  # [KV, hd] rows are stored padded: XLA would
        (12, 12, 128, False),  # copy the whole arena into a temporary
        (32, 8, 64, False),  # head_dim not lane-aligned
        (32, 12, 128, False),  # no whole GQA groups
    ],
)
def test_kernel_supported_shapes(heads, kv_heads, head_dim, ok):
    from agentainer_tpu.ops.pallas_attention import kernel_supported

    assert kernel_supported(heads, kv_heads, head_dim) is ok


@pytest.mark.parametrize(
    "backend,plan_kw,arena",
    [
        ("tpu", {}, "stack+layer"),  # the dense kernels index the stack
        ("tpu", {"page_size": 64}, "layer_slice"),  # the pool's kernels do not
        ("tpu", {"use_pallas": False}, "layer_slice"),  # GSPMD: XLA reference
        ("cpu", {}, "layer_slice"),
    ],
    ids=["dense-kernels", "paged-kernels", "gspmd-reference", "cpu-reference"],
)
def test_plan_says_how_the_arena_reaches_the_kernel(monkeypatch, backend, plan_kw, arena):
    """What /metrics ``attention.arena`` and the build log line print."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    plan = plan_cache_attention(32, 8, 128, **plan_kw)
    assert plan.arena == arena and plan.describe()["arena"] == arena
    assert ("pallas" in plan.decode) == (backend == "tpu" and "use_pallas" not in plan_kw)


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
    want = flash_decode(q, ck[None], cv[None], positions, 0, interpret=True)
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
    want = flash_prefill(q, ck[None], cv[None], positions, 0, interpret=True)
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

    got = flash_decode(q, ck[None], cv[None], positions, 0, interpret=True)
    want = attention_reference(
        q[:, None], ck, cv, mask=cache_mask(positions[:, None], s)
    )[:, 0]
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), rtol=3e-2, atol=3e-2
    )


# ---------------------------------------------------------------------------
# the dense kernels fetch only what their rows can see (ISSUE 33): the K/V
# index maps name no block past the one that holds the rows' last position
# (the live blocks are a row's last grid steps, the steps before them hold
# block 0 and do nothing), and a step whose block index did not change issues
# no DMA. What a live row computes is the parent's arithmetic, bit for bit:
# the parent's map (step ik reads block ik) stays reachable here alone, by
# swapping the one function the specs and the bodies are built with.


def _unclamped(monkeypatch, kernel):
    """``kernel`` as the parent built it: every grid step names its own block."""
    from agentainer_tpu.ops import pallas_attention

    def parent(*args, **kw):
        with monkeypatch.context() as m:
            m.setattr(pallas_attention, "kv_block_index", lambda ik, n_steps, last_pos, block_k: ik)
            return kernel.__wrapped__(*args, **kw)  # untraced: no cached program

    return parent


@pytest.mark.parametrize("block_k,arena", [(128, 512), (256, 1024), (128, 384)])
def test_kv_block_index_never_names_a_block_past_the_position(block_k, arena):
    from agentainer_tpu.ops.pallas_attention import kv_block_index

    n = -(-arena // block_k)
    ik, pos = np.meshgrid(np.arange(n), np.arange(arena + block_k), indexing="ij")
    got = np.asarray(kv_block_index(jnp.asarray(ik), n, jnp.asarray(pos), block_k))
    live = np.minimum(pos // block_k + 1, n)
    np.testing.assert_array_equal(got, ik - (n - live))
    fetched = np.maximum(got, 0)  # what the index maps name
    assert (fetched <= pos // block_k).all() and (fetched < n).all()
    for p in (0, block_k - 1, block_k, arena - 2, arena - 1, arena + 5):
        # a row's steps: idle ones first (block 0 held, nothing computed), then
        # every block it can see once, in order, ending on its last step
        steps = got[:, p]
        assert (steps[steps < 0].size + live[0, p]) == n
        np.testing.assert_array_equal(steps[steps >= 0], np.arange(live[0, p]))
    # a lane one short of the arena's last row fetches what it fetched before
    np.testing.assert_array_equal(got[:, arena - 2], np.arange(n))
    # a lane at position 0 holds block 0 through every step: one fetch
    assert not fetched[:, 0].any()


@pytest.mark.parametrize("slot", [0, 2], ids=["slot0", "slot2"])
@pytest.mark.parametrize(
    "kv_heads,groups,q_heads",
    [(8, 4, 32), (16, 1, 16), (32, 1, 30)],
    ids=["gqa8x4", "mha16", "mha32-stored-for-30"],
)
def test_decode_fetches_live_blocks_and_computes_what_the_parent_did(monkeypatch, kv_heads, groups, q_heads, slot):
    """Lanes at 0, bk − 1, bk, 2·bk + 3 and S − 2 in one batch, reading arena
    rows ``slot ..`` of layer 1: the reference's numbers, and the unclamped
    kernel's bits."""
    from agentainer_tpu.ops.attention import _reference_dense, pallas_dense

    bk, s, hd, n_layers = 128, 512, 128, 2
    positions = jnp.array([0, bk - 1, bk, 2 * bk + 3, s - 2], jnp.int32)
    lanes = positions.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(33), 3)
    ck = _rand(keys[0], n_layers, lanes + slot, s, kv_heads, hd).astype(jnp.bfloat16)
    cv = _rand(keys[1], n_layers, lanes + slot, s, kv_heads, hd).astype(jnp.bfloat16)
    q = _rand(keys[2], lanes, q_heads, hd).astype(jnp.bfloat16)
    # a K/V leaf stored with more heads than the model has: zero query heads
    q = jnp.pad(q, [(0, 0), (0, kv_heads * groups - q_heads), (0, 0)])
    lay, slt = jnp.int32(1), jnp.int32(slot)

    got = flash_decode(q, ck, cv, positions, lay, slt, block_k=bk, interpret=True)
    parent = _unclamped(monkeypatch, flash_decode)(q, ck, cv, positions, lay, slt, block_k=bk, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(parent))
    want = _reference_dense(q[:, None], ck, cv, positions[:, None], None, lay, slt)[:, 0]
    np.testing.assert_allclose(got.astype(np.float32), want.astype(np.float32), rtol=3e-2, atol=3e-2)
    # the default block (sized by the VMEM plan) through the dispatch the steps trace
    planned = pallas_dense(q[:, None], ck, cv, positions[:, None], None, lay, slt, interpret=True)[:, 0]
    np.testing.assert_allclose(planned.astype(np.float32), want.astype(np.float32), rtol=3e-2, atol=3e-2)


def test_a_parked_lane_reads_one_row_and_leaves_the_live_lanes_alone(monkeypatch):
    """The engine parks a lane at the arena's last row; the model runner hands
    the kernel position 0 for it (``models/llama._seen``). Its output is
    finite and nobody's; the lanes beside it read what they read before."""
    from agentainer_tpu.models.llama import _seen

    bk, s, kv_heads, hd = 128, 512, 16, 128
    positions = jnp.array([300, s - 1, 7, s - 1], jnp.int32)
    seen = _seen(positions, s)
    np.testing.assert_array_equal(np.asarray(seen), [300, 0, 7, 0])
    np.testing.assert_array_equal(np.asarray(_seen(jnp.array([s - 2]), s)), [s - 2])
    keys = jax.random.split(jax.random.PRNGKey(34), 3)
    ck = _rand(keys[0], 1, 4, s, kv_heads, hd).astype(jnp.bfloat16)
    cv = _rand(keys[1], 1, 4, s, kv_heads, hd).astype(jnp.bfloat16)
    q = _rand(keys[2], 4, kv_heads, hd).astype(jnp.bfloat16)
    got = flash_decode(q, ck, cv, seen, 0, block_k=bk, interpret=True)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    # one row seen: the softmax over it is 1, the output is that row's values
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(cv[0, 3, 0]))
    before = _unclamped(monkeypatch, flash_decode)(q, ck, cv, positions, 0, block_k=bk, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[0::2]), np.asarray(before[0::2]))


@pytest.mark.parametrize("offset", [0, 256, 1024 - 256], ids=["first", "second", "last"])
def test_prefill_chunk_fetches_up_to_its_last_row(monkeypatch, offset):
    """A 256-row chunk at ``offset`` of a 1,024-row arena (slot 1 of 2, GQA,
    two q tiles): the reference's numbers, the unclamped kernel's bits, and a
    bucket's padding rows that run past the arena's end do not move the map
    out of it."""
    from agentainer_tpu.ops.attention import _reference_dense

    t, s, kv_heads, groups, hd = 256, 1024, 8, 2, 128
    keys = jax.random.split(jax.random.PRNGKey(35), 3)
    ck = _rand(keys[0], 1, 2, s, kv_heads, hd).astype(jnp.bfloat16)
    cv = _rand(keys[1], 1, 2, s, kv_heads, hd).astype(jnp.bfloat16)
    q = _rand(keys[2], 1, t, kv_heads * groups, hd).astype(jnp.bfloat16)
    # the last chunk's positions as the engine sends a padded bucket's: the
    # real tokens stop short of the end and the padding's run on past it
    start = offset if offset + t < s else offset + 64
    positions = (start + jnp.arange(t, dtype=jnp.int32))[None]
    slot = jnp.int32(1)
    kw = dict(block_q=128, block_k=256, interpret=True)
    got = flash_prefill(q, ck, cv, positions, 0, slot, **kw)
    parent = _unclamped(monkeypatch, flash_prefill)(q, ck, cv, positions, 0, slot, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(parent))
    want = _reference_dense(q, ck, cv, positions, None, jnp.int32(0), slot)
    real = np.asarray(positions[0]) < s
    np.testing.assert_allclose(
        got[0, real].astype(np.float32), want[0, real].astype(np.float32), rtol=3e-2, atol=3e-2
    )


# ---- a window layer's ring (ISSUE 37) ---------------------------------------
#
# A window layer's leaf is a ring of R rows (row of position p: p mod R) and a
# row sees its last ``window`` positions. The index maps bound a row's blocks
# from BELOW as well as from above: of the ring's blocks only those that hold
# a position in lo .. last are named, in the order of their positions, as the
# row's last grid steps. The spec is ``ring_mask`` + ``attention_reference``.


@pytest.mark.parametrize("block_k,ring", [(128, 512), (128, 640), (256, 1024), (512, 4608)])
def test_ring_block_index_names_only_the_blocks_between_the_bounds(block_k, ring):
    from agentainer_tpu.ops.pallas_attention import ring_block_index, ring_positions

    n = ring // block_k
    window = ring - block_k  # as ``ring_rows`` sizes it: the window plus a launch
    for last in (0, 5, block_k - 1, block_k, window - 1, window, ring - 1, ring, ring + 3, 3 * ring + block_k + 7, 7 * ring - 1):
        lo = max(last - (window - 1), 0)
        blk, live = (np.asarray(x) for x in ring_block_index(jnp.arange(n), n, jnp.int32(lo), jnp.int32(last), block_k))
        want = [(b % n) for b in range(lo // block_k, last // block_k + 1)]
        assert len(want) <= n
        # the idle steps come first and hold the first live block (no fetch of
        # their own); the live ones follow in the order of their positions
        assert live.sum() == len(want) and not live[: n - len(want)].any()
        np.testing.assert_array_equal(blk[live], want)
        np.testing.assert_array_equal(blk[~live], [want[0]] * (n - len(want)))
        # blocks wholly under the lower bound are not named: an unbounded read
        # of the same row would name last // block_k + 1 of them
        assert len(want) <= min(last // block_k + 1, window // block_k + 1)
        # every row of a named block holds the newest position at or before
        # ``last`` that lands on it, and every position lo .. last is there once
        held = np.concatenate([np.asarray(ring_positions(jnp.int32(b), jnp.int32(last), block_k, ring))[0] for b in want])
        seen = held[(held >= lo) & (held <= last)]
        np.testing.assert_array_equal(np.sort(seen), np.arange(lo, last + 1))
        assert ((held <= last) & (held % ring == (np.concatenate([np.arange(b * block_k, (b + 1) * block_k) for b in want])))).all()


@pytest.mark.parametrize(
    "positions",
    [[0, 5, 299], [300, 511, 512], [700, 1023, 1500], [5000, 127, 128]],
    ids=["under_the_window", "at_the_ring_end", "wrapped", "far_past_and_block_edges"],
)
@pytest.mark.parametrize("kv_heads,groups", [(2, 3), (4, 7)], ids=["gqa2x3", "gqa4x7"])
def test_windowed_decode_reads_the_ring(positions, kv_heads, groups):
    """Lanes at assorted positions of a ring of 512 rows with a window of 300
    (not whole blocks), layer 1 of 2: ``flash_decode`` with ``window`` against
    the ring mask over the same leaf. GQA group 7 is SmallThinker's."""
    from agentainer_tpu.ops.attention import _reference_dense, pallas_dense

    ring, window, hd = 512, 300, 128
    keys = jax.random.split(jax.random.PRNGKey(37), 3)
    ck = _rand(keys[0], 2, 3, ring, kv_heads, hd)
    cv = _rand(keys[1], 2, 3, ring, kv_heads, hd)
    q = _rand(keys[2], 3, 1, kv_heads * groups, hd)
    pos = jnp.asarray(positions, jnp.int32)[:, None]
    want = _reference_dense(q, ck, cv, pos, None, 1, None, window=window)
    got = pallas_dense(q, ck, cv, pos, None, 1, None, interpret=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    short = flash_decode(q[:, 0], ck, cv, pos[:, 0], 1, block_k=128, interpret=True, window=window)
    np.testing.assert_allclose(np.asarray(short), np.asarray(want[:, 0]), rtol=2e-5, atol=2e-5)
    # the window matters: the unbounded kernel over the same rows reads more
    if max(positions) >= window:
        plain = pallas_dense(q, ck, cv, jnp.minimum(pos, ring - 1), None, 1, None, interpret=True)
        assert float(jnp.abs(plain - want).max()) > 1e-3


@pytest.mark.parametrize(
    "start,rows",
    [(0, 64), (250, 64), (480, 64), (1000, 200), (1336, 213)],
    ids=["first", "across_the_window", "across_the_ring_end", "wrapped_two_q_tiles", "ragged_wrapped"],
)
@pytest.mark.parametrize("block_k", [128, 256])
def test_windowed_prefill_chunk_reads_the_ring(start, rows, block_k):
    """A chunk of ``rows`` rows at ``start`` on slot 1 of a ring of 512 with a
    window of 300: rows of one q tile see different lower bounds, the chunk's
    own rows may straddle the ring's end, and blocks wholly behind every row's
    window are skipped."""
    from agentainer_tpu.ops.attention import _reference_dense

    ring, window, kv_heads, groups, hd = 512, 300, 2, 3, 128
    assert rows <= ring - window + 1  # what ``ring_rows`` guarantees a launch
    keys = jax.random.split(jax.random.PRNGKey(38), 3)
    ck = _rand(keys[0], 2, 3, ring, kv_heads, hd)
    cv = _rand(keys[1], 2, 3, ring, kv_heads, hd)
    q = _rand(keys[2], 1, rows, kv_heads * groups, hd)
    pos = (start + jnp.arange(rows, dtype=jnp.int32))[None]
    got = flash_prefill(q, ck, cv, pos, 1, 1, block_k=block_k, interpret=True, window=window)
    want = _reference_dense(q, ck, cv, pos, None, 1, 1, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_a_ring_that_is_not_whole_blocks_is_refused():
    ck = jnp.zeros((1, 1, 300, 2, 128), jnp.float32)
    q = jnp.zeros((1, 6, 128), jnp.float32)
    with pytest.raises(ValueError, match="whole K/V blocks"):
        flash_decode(q, ck, ck, jnp.zeros((1,), jnp.int32), 0, block_k=128, interpret=True, window=100)
    with pytest.raises(ValueError, match="whole K/V blocks"):
        flash_prefill(q[None], ck, ck, jnp.zeros((1, 1), jnp.int32), 0, 0, block_k=128, interpret=True, window=100)


def test_ring_mask_is_the_window_over_positions():
    """The XLA spec itself, against the definition: row r of a ring of R holds
    the newest position at or before the query's that lands on it."""
    from agentainer_tpu.ops.attention import ring_mask

    ring, window = 24, 16
    for i in (0, 3, 15, 16, 23, 24, 25, 47, 100):
        mask = np.asarray(ring_mask(jnp.asarray([[i]]), ring, window))[0, 0]
        seen = sorted(j for j in range(max(0, i - window + 1), i + 1))
        assert sorted(np.flatnonzero(mask)) == sorted(j % ring for j in seen)
        assert mask.sum() == min(i + 1, window)


# ---- the prefill tile as served (ISSUE 49) -----------------------------------
#
# bfloat16 q and arena, the MXU's operands in that dtype (the probabilities
# rounded to it before the value matmul), float32 scores, state and
# accumulator; the tile a function of the call's shapes (``prefill_plan``):
# 256 query rows against K/V blocks of 256 at every shape a cell serves.

SERVED = {
    # name: (query heads, stored KV heads, arena rows, window, chunk start, real rows)
    "smallthinker_window_lapped": (28, 4, 4608, 4096, 9100, 213),  # 9100 mod 4608 = 4492: the chunk laps the ring's end
    "smallthinker_global_past_4096": (28, 4, 4400, 0, 4200, 190),  # the padding runs over the last row and past the arena
    "mixtral": (32, 8, 2048, 0, 1536, 256),
    "olmoe": (16, 16, 2048, 0, 1536, 256),
    "olmo_hybrid_32_stored_for_30": (32, 32, 2048, 0, 1536, 256),
}


@pytest.mark.parametrize("shape", sorted(SERVED))
def test_prefill_in_bfloat16_at_the_served_tile(shape):
    """A 256-row chunk on slot 1, layer 1, bfloat16 as served, against the
    float32 reference over the same values: the real rows agree to bfloat16's
    rounding; a bucket's padding rows carry positions that run on past the
    real tokens (over the arena's last row, where lanes are parked, and past
    its end in the global case) and poison nothing."""
    from agentainer_tpu.ops.attention import _reference_dense
    from agentainer_tpu.ops.pallas_attention import prefill_plan

    h, kv, s, window, start, real = SERVED[shape]
    t, hd = 256, 128
    assert prefill_plan(t, h, kv, hd, s, jnp.bfloat16, jnp.bfloat16)[1:3] == (256, 256)
    keys = jax.random.split(jax.random.PRNGKey(49), 3)
    ck = _rand(keys[0], 2, 2, s, kv, hd).astype(jnp.bfloat16)
    cv = _rand(keys[1], 2, 2, s, kv, hd).astype(jnp.bfloat16)
    q = _rand(keys[2], 1, t, h, hd).astype(jnp.bfloat16)
    if shape.startswith("olmo_hybrid"):
        q = q.at[:, :, 30:].set(0)  # the hybrid block pads the model's 30 heads with zero heads
    positions = (start + jnp.arange(t, dtype=jnp.int32))[None]
    kw = {"window": window} if window else {}
    got = flash_prefill(q, ck, cv, positions, 1, 1, interpret=True, **kw)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    want = _reference_dense(f32(q), f32(ck), f32(cv), positions, None, jnp.int32(1), jnp.int32(1), **kw)
    assert got.dtype == jnp.bfloat16 and bool(jnp.isfinite(f32(got)).all())
    np.testing.assert_allclose(np.asarray(f32(got))[0, :real], np.asarray(want)[0, :real], rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("shape", sorted(SERVED))
def test_prefill_plan_fits_what_the_call_asks_for(shape):
    """The q tile's rows and the K/V blocks, counted here from the kernel's
    buffers, fit the plan's bytes; the call asks Mosaic for those plus 8 MiB,
    a third of a v5e's 128 MiB of VMEM at most; a short chunk is one tile."""
    from agentainer_tpu.ops.pallas_attention import _PREFILL_KV_VMEM, _PREFILL_Q_VMEM, prefill_plan, prefill_tile

    h, kv, s = SERVED[shape][:3]
    heads, bq, bk, vmem = prefill_plan(256, h, kv, 128, s, jnp.bfloat16, jnp.bfloat16)
    g, rows = h // kv, h // kv * bq
    tile = 2 * 2 * heads * rows * 128 * 2  # q and the output, two buffers each
    tile += heads * rows * 128 * 4 + 2 * heads * rows * 128 * 4  # acc; m and l, a lane tile a row
    tile += 2 * g * bq * 128 * 4  # positions
    tile += 2 * rows * bk * (4 + 4 + 2)  # two heads' scores, exponentials, rounded probabilities
    blocks = 4 * bk * max(heads, 16) * 128 * 2
    assert tile <= _PREFILL_Q_VMEM and blocks <= _PREFILL_KV_VMEM
    assert tile + blocks <= vmem <= 40 << 20
    assert kv % heads == 0 and bk % 128 == 0 and bq % 8 == 0
    assert prefill_tile(256, h, kv, 128, s, jnp.bfloat16, jnp.bfloat16) == {"bq": bq, "bk": bk, "operands": "bfloat16"}
    assert prefill_plan(40, h, kv, 128, s, jnp.float32, jnp.float32)[1] == 40
    # 64 query heads over 8: a 256-row tile would not fit, so the plan halves it
    assert prefill_plan(256, 64, 8, 128, 2048, jnp.bfloat16, jnp.bfloat16)[1] == 128


def test_ring_block_is_what_every_registered_configuration_had():
    """The ring of a window layer is whole multiples of ``ring_block()``: the
    cache's layout. The prefill plan moved in PR 49; this did not."""
    from agentainer_tpu.models.configs import get_config, list_configs
    from agentainer_tpu.ops.pallas_attention import kernel_supported, ring_block

    had = {  # (bfloat16, float32) at c5c67e8; ``laguna-xs.2`` came with PR 50 (8 K/V heads of 128, as Llama's)
        # and ``minicpm-sala`` with PR 54 (2 K/V heads of 128; it has no ring: the dense kernels' shapes alone),
        # ``solar-open2`` with PR 57 (8 K/V heads of 128 under 64 query heads; no ring either)
        "bench-1b": (512, 512), "laguna-xs.2": (512, 512), "llama3-8b": (512, 512), "minicpm-sala": (512, 512),
        "mistral-small-4-119b": (512, 256),
        "mixtral-8x7b": (512, 512), "olmoe-1b-7b": (512, 256), "smallthinker-21b": (512, 512),
        "solar-open2": (512, 512),
    }
    now = {}
    for name in list_configs():
        cfg = get_config(name)
        if kernel_supported(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim):
            now[name] = tuple(ring_block(cfg.n_kv_heads, cfg.head_dim, d) for d in (jnp.bfloat16, jnp.float32))
    assert now == had


# -- sparse_decode: a lane's step over its listed key blocks --------------------

SP_BLOCK, SP_TOPK, SP_ROWS = 64, 16, 2048  # two chunks of 8 places a list; 32 blocks a lane


def _sparse_case(case: str):
    """``(blocks [b, 2, topk], positions [b], layer, slot)`` of a named case:
    lists as ``select_blocks`` would make them (block 0 and the blocks of the
    last 512 rows forced, the same for both heads and first in the list) but
    for what the case says."""
    rng = np.random.default_rng(7)
    layer, slot, positions = 1, 0, [1500, 1337]
    n_blocks = SP_ROWS // SP_BLOCK

    def lists(pos, free=(None, None), order=False):
        cur = pos // SP_BLOCK
        forced = [0] + list(range(cur - 7, cur + 1))
        out = []
        for h in range(2):
            rest = [x for x in range(1, cur - 7)]
            pick = list(rng.choice(rest, SP_TOPK - len(forced), replace=False)) if free[h] is None else list(free[h])
            got = forced + pick
            out.append(list(rng.permutation(got)) if order and h else got)
        return out

    if case == "short_context":  # fewer blocks than places: -1 fills the lists
        positions = [700, 70]
        blocks = [[list(range(p // SP_BLOCK + 1)) + [-1] * (SP_TOPK - p // SP_BLOCK - 1)] * 2 for p in positions]
    elif case == "none_first":  # a first chunk with nothing in it, and a lane with nothing at all
        blocks = [[[-1] * 8 + [0, 3, 5, 9, 20, 21, 22, 23], [-1] * 8 + [23, 22, 0, 1, -1, 2, -1, 4]], [[-1] * SP_TOPK] * 2]
    elif case == "mid_block":
        positions = [1500 - 1500 % SP_BLOCK + 31, 1337 - 1337 % SP_BLOCK + 1]
        blocks = [lists(p) for p in positions]
    elif case == "heads_equal":
        blocks = [[lists(p)[0]] * 2 for p in positions]
    elif case == "heads_disjoint":  # but for the forced blocks
        blocks = [lists(p, free=(range(1, 8), range(8, 15))) for p in positions]
    elif case == "heads_reordered":  # the same sets, head 1's in another order
        blocks = [lists(p, free=(range(2, 9), range(2, 9)), order=True) for p in positions]
    elif case == "slot2":
        slot, blocks = 2, [lists(p) for p in positions]
    elif case == "layer0":
        layer, blocks = 0, [lists(p) for p in positions]
    elif case == "layer_last":
        layer, blocks = 2, [lists(p) for p in positions]
    elif case == "arena_end":  # the last block of the arena, read to its last row and short of it
        positions = [SP_ROWS - 1, SP_ROWS - 9]
        blocks = [lists(p) for p in positions]
        assert all(n_blocks - 1 in b[0] for b in blocks)
    else:
        raise AssertionError(case)
    return jnp.asarray(blocks, jnp.int32), jnp.asarray(positions, jnp.int32), layer, slot


def _sparse_arena(dtype, lanes=4, layers=3, kv=2, hd=32):
    k = _rand(jax.random.PRNGKey(11), layers, lanes, SP_ROWS, kv, hd).astype(dtype)
    v = _rand(jax.random.PRNGKey(12), layers, lanes, SP_ROWS, kv, hd).astype(dtype)
    return k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "case",
    ["short_context", "none_first", "mid_block", "heads_equal", "heads_disjoint", "heads_reordered", "slot2", "layer0",
     "layer_last", "arena_end"],
)
def test_sparse_decode_is_attend_blocks(case, dtype):
    """The kernel that copies a lane's listed blocks out of the stack against
    ``sparse_attention.attend_blocks``, XLA's gather of the same rows: float32
    arenas to rounding, bfloat16 arenas to the rounding of the probabilities
    (the kernel rounds them against a chunk's running maximum)."""
    from agentainer_tpu.ops.pallas_attention import sparse_decode
    from agentainer_tpu.ops.sparse_attention import attend_blocks

    blocks, positions, layer, slot = _sparse_case(case)
    k, v = _sparse_arena(dtype)
    q = _rand(jax.random.PRNGKey(13), 2, 6, k.shape[-1])  # 3 query heads a K/V head
    want = attend_blocks(q, k, v, layer, jnp.arange(2) + slot, blocks, positions, 2, SP_BLOCK)
    got = sparse_decode(q, k, v, blocks, positions, layer, slot, block=SP_BLOCK, interpret=True)
    assert got.shape == want.shape == (2, 6, k.shape[-1]) and got.dtype == want.dtype == jnp.float32
    tol = 2e-6 if dtype == jnp.float32 else 4e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)
    if case == "none_first":
        assert not np.asarray(got[1]).any()  # nothing listed: zeros, as the reference gives


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_sparse_decode_sees_no_row_it_was_not_given(dtype):
    """A row of a listed block past the lane's position, and every row of a
    block that is not listed (for the head that does not list it), may hold
    anything: large values there leave the output as it was, to the bit."""
    from agentainer_tpu.ops.pallas_attention import sparse_decode

    blocks, positions, layer, slot = _sparse_case("heads_disjoint")
    positions = positions - 20  # the current block is listed and ends 20 + rows past the position
    k, v = _sparse_arena(dtype)
    q = _rand(jax.random.PRNGKey(13), 2, 6, k.shape[-1])
    before = sparse_decode(q, k, v, blocks, positions, layer, slot, block=SP_BLOCK, interpret=True)
    row = np.arange(SP_ROWS)
    unseen = np.ones((2, SP_ROWS, 2), bool)  # lane, row, head
    for b in range(2):
        for h in range(2):
            listed = np.isin(row // SP_BLOCK, np.asarray(blocks[b, h]))
            unseen[b, :, h] = ~(listed & (row <= int(positions[b])))
    assert unseen[:, :, 0].sum() != unseen[:, :, 1].sum() or (unseen[:, :, 0] != unseen[:, :, 1]).any()
    big = jnp.asarray(unseen)[..., None]
    k2 = k.at[layer, slot:slot + 2].set(jnp.where(big, 3e4, k[layer, slot:slot + 2]).astype(dtype))
    v2 = v.at[layer, slot:slot + 2].set(jnp.where(big, -3e4, v[layer, slot:slot + 2]).astype(dtype))
    after = sparse_decode(q, k2, v2, blocks, positions, layer, slot, block=SP_BLOCK, interpret=True)
    np.testing.assert_array_equal(np.asarray(after), np.asarray(before))
    assert np.isfinite(np.asarray(after)).all()


@pytest.mark.parametrize(
    "kv, stored, hd, dtype, block, topk, per",
    [
        (2, 2, 128, jnp.bfloat16, 64, 64, 8),  # minicpm-sala as served: 512 rows a head, 2 MB of scratch
        (2, 2, 128, jnp.float32, 64, 64, 8),
        (2, 2, 32, jnp.float32, 64, 16, 8),  # the cases above: two chunks
        (4, 4, 128, jnp.bfloat16, 64, 64, 4),  # more heads: the scratch's 4 MiB cut the chunk
        (8, 8, 128, jnp.bfloat16, 64, 64, 1),
        (2, 2, 128, jnp.bfloat16, 64, 12, 6),  # a divisor of the list's places
        (2, 2, 128, jnp.bfloat16, 1024, 4, 1),  # a block longer than a chunk
    ],
)
def test_sparse_chunk_follows_the_shapes(kv, stored, hd, dtype, block, topk, per):
    from agentainer_tpu.ops.pallas_attention import _SPARSE_KV_VMEM, sparse_chunk

    got = sparse_chunk(kv, stored, hd, dtype, block, topk)
    assert got == per and topk % got == 0
    if got > 1:
        assert 4 * kv * got * block * stored * hd * jnp.dtype(dtype).itemsize <= _SPARSE_KV_VMEM


def test_sparse_decode_refuses_an_arena_that_is_not_whole_blocks():
    from agentainer_tpu.ops.pallas_attention import sparse_decode

    k = jnp.zeros((1, 1, 96, 2, 32), jnp.float32)
    with pytest.raises(ValueError, match="not whole key blocks"):
        sparse_decode(jnp.zeros((1, 4, 32)), k, k, jnp.zeros((1, 2, 1), jnp.int32), jnp.zeros((1,), jnp.int32), 0,
                      block=64, interpret=True)
