"""Block-sparse attention that chooses its key blocks from pooled keys
(InfLLM v2 as MiniCPM4 and MiniCPM-SALA's ``minicpm4`` mixer run it, arXiv
2506.07900): the three pieces a "sparse" layer of ``models/hybrid.py`` adds to
softmax attention over a K/V arena, each written once for prefill, the mixed
step and decode.

With a kernel of ``K`` rows every ``s`` (the pooled key ``c_j`` is the mean of
keys ``s·j .. s·j + K − 1``, visible to a query at position ``t`` once its
last row is: ``s·j + K − 1 ≤ t``), key blocks of ``B`` rows, and ``G`` query
heads a K/V head:

1. **The pooled-key leaf** (:func:`append_pooled`): ``[n, lanes, S / s, KV,
   hd]`` beside ``k`` and ``v``, row ``j`` written by the launch that writes
   row ``s·j + K − 1`` of the lane. It is computed from the rows AS STORED
   (float32 sum of the arena's values), so it does not depend on how a
   context was cut into launches: a kernel that a chunk boundary splits reads
   its first rows from the arena, where the earlier launch left them.
2. **The selection** (:func:`select_blocks`): ``p_h = softmax_j(q_h · c_j /
   √hd)`` over the visible ``j``, exactly, a head; summed over the group's
   ``G`` heads; a block's score is the largest among the kernels that overlap
   it (a max-pool of ``(B + K) / s − 1`` every ``B / s``, ``K / s − 1`` of
   padding); the first blocks and the blocks of the last ``window`` rows
   score +∞; the ``topk`` highest are the query's blocks, one set a query row
   and K/V head. float32 at ``highest`` precision: the scores are a few
   thousandths of the layer's projections, and a near-tie at the cut is a
   discontinuity like a router's.
3. **Attention over the chosen blocks**: for one query row a lane the listed
   blocks' rows are read and nothing else of the arena: on a TPU by
   ``pallas_attention.sparse_decode``, a kernel that copies the listed blocks
   out of the arena where it lies; elsewhere, and as that kernel's reference,
   by XLA's gather (:func:`attend_blocks`; :func:`blocks_step` is the choice).
   For a chunk of rows (:func:`attend_masked`) the sets become a row-by-block
   mask over the lane's rows, read a run of blocks at a time up to the chunk's
   last position with an online softmax. A query whose context is at most
   ``dense_len`` rows reads all of it: in a chunk its mask is every block, and
   a lane's step takes the dense kernel (``models/hybrid.sparse_mixer``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30
_HI = lax.Precision.HIGHEST
KEY_RUN = 2048  # rows of a lane that :func:`attend_masked` reads a trip, at most


class SparseSizes(NamedTuple):
    kernel: int
    stride: int
    block: int
    init_blocks: int
    window: int
    topk: int
    dense_len: int

    @classmethod
    def of(cls, cfg) -> "SparseSizes":
        return cls(cfg.sparse_kernel, cfg.sparse_stride, cfg.sparse_block, cfg.sparse_init_blocks,
                   cfg.sparse_window, cfg.sparse_topk, cfg.sparse_dense_len)


def append_pooled(pooled, k, idx, lanes, start, n_valid, t: int, sizes: SparseSizes):
    """Layer ``idx`` of the pooled-key stack ``[n, B, P, KV, hd]`` with the
    kernels that a launch completed: ``lanes [b]`` each wrote its ``n_valid``
    rows from position ``start`` (of ``t`` in the launch) into layer ``idx``
    of ``k [n, B, S, KVs, hd]``, which already holds them."""
    kernel, stride = sizes.kernel, sizes.stride
    n_kernels = (t - 1) // stride + 1  # at most this many end inside t consecutive rows
    kv = pooled.shape[3]
    first = jnp.maximum(-(-(start - kernel + 1) // stride), 0)  # [b]: the first kernel that ends at or past ``start``
    j = first[:, None] + jnp.arange(n_kernels)  # [b, nk]
    ends = stride * j + kernel - 1
    done = (ends >= start[:, None]) & (ends < (start + n_valid)[:, None])
    rows = jnp.minimum(stride * j[..., None] + jnp.arange(kernel), k.shape[2] - 1)  # [b, nk, kernel]
    got = k[idx, lanes[:, None, None], rows][..., :kv, :]  # [b, nk, kernel, KV, hd]
    mean = jnp.mean(got.astype(jnp.float32), axis=2).astype(pooled.dtype)
    at = jnp.where(done, j, pooled.shape[2])  # a kernel not completed here is written nowhere
    return pooled.at[idx, lanes[:, None], at].set(mean, mode="drop")


def block_scores(q, pooled, positions, sizes: SparseSizes):
    """Stage 1: ``q [b, t, H, hd]`` float32 against ``pooled [b, P, KV, hd]``
    for queries at ``positions [b, t]`` → ``[b, t, KV, S / block]`` float32:
    every block's score before any is forced (−∞: no visible kernel overlaps
    it)."""
    b, t, h, hd = q.shape
    kernel, stride, block = sizes.kernel, sizes.stride, sizes.block
    n_pooled, kv = pooled.shape[1], pooled.shape[2]
    qg = q.reshape(b, t, kv, h // kv, hd)
    s = jnp.einsum("btkgd,bpkd->btkgp", qg, pooled.astype(jnp.float32), precision=_HI) * hd**-0.5
    visible = (stride * jnp.arange(n_pooled) + kernel - 1 <= positions[..., None])[:, :, None, None, :]
    top = jnp.max(jnp.where(visible, s, NEG_INF), axis=-1, keepdims=True)
    e = jnp.where(visible, jnp.exp(jnp.minimum(s - top, 0.0)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)  # the exact softmax over the visible kernels
    per_group = jnp.where(visible[:, :, :, 0], jnp.sum(p, axis=3), -jnp.inf)  # [b, t, KV, P]
    before = (kernel - 1) // stride  # kernels that start before a block and still overlap it
    return lax.reduce_window(
        per_group, -jnp.inf, lax.max, (1, 1, 1, (block - 1) // stride + before + 1), (1, 1, 1, block // stride),
        ((0, 0), (0, 0), (0, 0), (before, 0)),
    )


def select_blocks(scores, positions, sizes: SparseSizes):
    """The ``topk`` blocks of each query row and K/V head from
    :func:`block_scores`' ``scores``: ``[b, t, KV, topk]`` int32 block
    numbers, −1 where fewer than ``topk`` blocks can be seen. Forced: the
    first ``init_blocks`` and the blocks of the last ``window`` rows."""
    block_of = jnp.arange(scores.shape[-1])
    current = (positions // sizes.block)[..., None, None]
    forced = (block_of < sizes.init_blocks) | (block_of > current - sizes.window // sizes.block)
    scores = jnp.where(forced, jnp.inf, scores)
    scores = jnp.where(block_of > current, -jnp.inf, scores)
    top, chosen = lax.top_k(scores, min(sizes.topk, scores.shape[-1]))
    return jnp.where(top > -jnp.inf, chosen, -1).astype(jnp.int32)


def attend_blocks(q, k, v, idx, lanes, blocks, positions, n_kv: int, block: int):
    """One query row a lane over its chosen blocks: ``q [b, H, hd]``, ``blocks
    [b, KV, topk]``, ``positions [b]``, lane ``lanes[i]`` of layer ``idx`` of
    the stacks ``k``, ``v`` ``[n, B, S, KVs, hd]`` → ``[b, H, hd]`` float32.
    Only the listed blocks' rows are read (of the head that reads them)."""
    b, h, hd = q.shape
    rows = (blocks[..., None] * block + jnp.arange(block)).reshape(b, n_kv, -1)  # [b, KV, R]
    seen = jnp.repeat(blocks >= 0, block, axis=-1) & (rows <= positions[:, None, None])
    at = (idx, lanes[:, None, None], jnp.clip(rows, 0, k.shape[2] - 1), jnp.arange(n_kv)[None, :, None])
    kk, vv = k[at], v[at]  # [b, KV, R, hd]
    qg = q.reshape(b, n_kv, h // n_kv, hd).astype(k.dtype)
    s = jnp.einsum("bkgd,bkrd->bkgr", qg, kk, preferred_element_type=jnp.float32) * hd**-0.5
    s = jnp.where(seen[:, :, None], s, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(seen[:, :, None], p, 0.0)
    o = jnp.einsum("bkgr,bkrd->bkgd", p.astype(v.dtype), vv, preferred_element_type=jnp.float32)
    return (o / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)).reshape(b, h, hd)


def blocks_step(kernel: bool):
    """:func:`attend_blocks` as a plan names it, for lanes ``lane .. lane + b``
    (``f(q, k, v, idx, lane, blocks, positions, n_kv, block)``): the Pallas
    kernel that copies the listed blocks out of the arena where it lies
    (``pallas_attention.sparse_decode``), or XLA's gather."""
    if not kernel:
        return lambda q, k, v, idx, lane, *rest: attend_blocks(q, k, v, idx, jnp.arange(q.shape[0]) + lane, *rest)
    from .pallas_attention import sparse_decode

    return lambda q, k, v, idx, lane, blocks, positions, n_kv, block: sparse_decode(
        q, k, v, blocks, positions, idx, lane, block=block)


def rows_by_blocks(blocks, positions, n_blocks: int, dense_len: int):
    """The chosen sets as a mask ``[b, t, KV, n_blocks]``; every block for a
    row whose context is at most ``dense_len`` rows."""
    chosen = jnp.any(blocks[..., None] == jnp.arange(n_blocks), axis=-2)
    return chosen | (positions < dense_len)[..., None, None]


def key_run(n_rows: int, block: int) -> int:
    """Rows :func:`attend_masked` reads a trip: whole blocks that divide the
    arena's length, :data:`KEY_RUN` at most."""
    n_blocks = n_rows // block
    return block * next(m for m in range(min(n_blocks, max(KEY_RUN // block, 1)), 0, -1) if n_blocks % m == 0)


def attend_masked(q, k, v, idx, lane, positions, last, mask, n_kv: int, block: int):
    """A chunk of query rows over the blocks its mask allows: ``q [b, t, H,
    hd]``, ``positions [b, t]``, ``mask [b, t, KV, S / block]``, lanes ``lane
    .. lane + b`` of layer ``idx`` of the stacks → ``[b, t, H, hd]`` float32.
    Reads the lanes' rows a run of blocks at a time up to position ``last``
    (the largest any row sees), and a row sees a key iff the mask has its
    block and the key is not after it."""
    b, t, h, hd = q.shape
    run = key_run(k.shape[2], block)
    g = h // n_kv
    qg = jnp.moveaxis(q.reshape(b, t, n_kv, g, hd), 1, 3).astype(k.dtype)  # [b, KV, G, t, hd]
    mask = jnp.moveaxis(mask, 1, 2)  # [b, KV, t, NB]

    def trip(c, carry):
        m, l, acc = carry
        take = lambda a: lax.dynamic_slice(  # noqa: E731
            a, (idx, lane, c * run, 0, 0), (1, b, run, a.shape[3], hd))[0][:, :, :n_kv]
        kk, vv = take(k), take(v)  # [b, run, KV, hd]
        s = jnp.einsum("bkgtd,bskd->bkgts", qg, kk, preferred_element_type=jnp.float32) * hd**-0.5
        cols = c * run + jnp.arange(run)
        blocks = lax.dynamic_slice_in_dim(mask, c * (run // block), run // block, axis=3)
        see = jnp.repeat(blocks, block, axis=3) & (cols <= positions[:, None, :, None])  # [b, KV, t, run]
        s = jnp.where(see[:, :, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(see[:, :, None], jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgts,bskd->bkgtd", p.astype(vv.dtype), vv, preferred_element_type=jnp.float32)
        return m_new, l * alpha + jnp.sum(p, axis=-1), acc

    init = (jnp.full((b, n_kv, g, t), NEG_INF, jnp.float32), jnp.zeros((b, n_kv, g, t), jnp.float32),
            jnp.zeros((b, n_kv, g, t, hd), jnp.float32))
    trips = jnp.minimum(last // run + 1, k.shape[2] // run)
    _, l, acc = lax.fori_loop(0, trips, trip, init)
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(o, 3, 1).reshape(b, t, h, hd)


def block_counts(positions, sizes: SparseSizes) -> dict:
    """What a sparse layer's selection reads for query rows at ``positions``
    (host-side, any array-like of ints): the engine's ``attention.sparse``
    counters, a layer counted once. ``rows_read`` is exact but for a chosen
    block the query's own position cuts short (the current one is counted
    so); ``blocks_selected`` counts the blocks that can be seen."""
    import numpy as np

    p = np.asarray(positions, np.int64).reshape(-1)
    sparse = p >= sizes.dense_len
    live_blocks = p // sizes.block + 1
    chosen = np.minimum(live_blocks, sizes.topk)
    forced = np.minimum(live_blocks, sizes.init_blocks + sizes.window // sizes.block)
    # every chosen block whole but the current one, read up to the position
    read = (chosen - 1) * sizes.block + p % sizes.block + 1
    pooled = np.maximum((p - sizes.kernel + 1) // sizes.stride + 1, 0)
    return {
        "steps_dense": int((~sparse).sum()), "steps_sparse": int(sparse.sum()),
        "blocks_live": int(live_blocks[sparse].sum()), "blocks_selected": int(chosen[sparse].sum()),
        "blocks_forced": int(forced[sparse].sum()),
        "rows_live": int((p + 1).sum()), "rows_read": int(np.where(sparse, read, p + 1).sum()),
        "pooled_rows_scored": int(pooled[sparse].sum()),
    }
