"""Pallas TPU flash-attention kernels for the serving path.

Green-field TPU component (the reference has no model/kernel code —
SURVEY.md §2: "Native components: there are none"); this is the
CUDA-kernel-equivalent tier of the new framework, written as Mosaic/Pallas
blockwise kernels.

Design notes (why this shape, not a torch translation):

- **One masking rule covers every serving phase.** The engine's KV arena is
  a static ``[B, S, KV, hd]`` buffer written at per-sequence positions
  (models/llama.py). A query row at position ``p`` may see arena slot ``j``
  iff ``j <= p`` — that single rule *is* causal attention when positions are
  ``arange(T)`` (training / no-cache prefill), *is* ragged cached prefill
  when each sequence sits at a different offset (continuous batching), and
  *is* decode when T == 1. So both kernels take ``q_positions`` and build
  the mask in-register from a 2-D iota — no ``[B, T, S]`` mask tensor ever
  touches HBM.
- **Online softmax, f32 accumulators, bf16 operands.** Scores and the
  running (m, l, acc) state live in VMEM scratch that persists across the
  innermost KV-block grid dimension; softmax rescaling follows the standard
  flash recurrence. MXU matmuls get f32 ``preferred_element_type``.
- **GQA without materializing repeated K/V.** Grid cells are (batch,
  kv-head); the G = H/KV query heads of the group are processed in an
  unrolled loop against the same K/V block already resident in VMEM —
  K/V HBM traffic is per *kv* head, the way GQA intends.
- **Causal block skipping.** KV blocks entirely in the future of every
  query row in the tile (``k_start > max(pos)``) skip their matmuls via
  ``pl.when`` predication — ~2x prefill FLOP cut at long context.

CPU CI runs the same kernels under ``interpret=True`` (tests/), matching
ops/attention.py's reference implementation bit-for-bit in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# prefill kernel: q [B, T, H, hd] vs arena k/v [B, S, KV, hd]
# ---------------------------------------------------------------------------


def _prefill_kernel(
    pos_ref,  # [1, bq, 1] int32          (VMEM)
    q_ref,  # [1, 1, G, bq, hd]          (VMEM)
    k_ref,  # [1, 1, bk, hd]             (VMEM)
    v_ref,  # [1, 1, bk, hd]             (VMEM)
    o_ref,  # [1, 1, G, bq, hd]          (VMEM)
    m_ref,  # [G, bq] f32 scratch
    l_ref,  # [G, bq] f32 scratch
    acc_ref,  # [G, bq, hd] f32 scratch
    *,
    groups: int,
    block_k: int,
    seq_len_k: int,
    scale: float,
):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[0, :, 0]  # [bq] int32
    k_start = ik * block_k
    bq = pos.shape[0]
    col = k_start + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
    mask = (col <= pos[:, None]) & (col < seq_len_k)  # [bq, bk]

    # skip KV blocks strictly in the future of every row in this q tile
    @pl.when(k_start <= jnp.max(pos))
    def _compute():
        kb = k_ref[0, 0].astype(jnp.float32)  # [bk, hd]
        vb = v_ref[0, 0].astype(jnp.float32)
        # rows past the arena end are padded garbage (can be NaN): zero them,
        # since 0 * NaN from the masked-out probabilities would poison acc
        col_valid = k_start + lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
        vb = jnp.where(col_valid < seq_len_k, vb, 0.0)
        for g in range(groups):
            qb = q_ref[0, 0, g].astype(jnp.float32)  # [bq, hd]
            s = lax.dot_general(
                qb,
                kb,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [bq, bk]
            s = jnp.where(mask, s * scale, NEG_INF)
            m_prev = m_ref[g, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            l_ref[g, :] = l_ref[g, :] * alpha + jnp.sum(p, axis=-1)
            acc_ref[g] = acc_ref[g] * alpha[:, None] + lax.dot_general(
                p,
                vb,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[g, :] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked (padding) rows
        o_ref[0, 0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret")
)
def flash_prefill(
    q: jnp.ndarray,  # [B, T, H, hd]
    k: jnp.ndarray,  # [B, S, KV, hd]
    v: jnp.ndarray,  # [B, S, KV, hd]
    q_positions: jnp.ndarray,  # [B, T] int32
    block_q: int = 128,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Blockwise flash attention; row t sees arena slot j iff j <= pos[b, t]."""
    b, t, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    bq = min(block_q, _round_up(t, 8))
    bk = min(block_k, _round_up(s, 128))

    qh = q.reshape(b, t, kv, g, hd).transpose(0, 2, 3, 1, 4)  # [B,KV,G,T,hd]
    kh = k.transpose(0, 2, 1, 3)  # [B,KV,S,hd]
    vh = v.transpose(0, 2, 1, 3)

    grid = (b, kv, pl.cdiv(t, bq), pl.cdiv(s, bk))
    kernel = functools.partial(
        _prefill_kernel,
        groups=g,
        block_k=bk,
        seq_len_k=s,
        scale=1.0 / (hd**0.5),
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # [B, T, 1] so the (sublane, lane) dims are TPU-block-legal
            pl.BlockSpec((1, bq, 1), lambda ib, ih, iq, ik: (ib, iq, 0)),
            pl.BlockSpec(
                (1, 1, g, bq, hd), lambda ib, ih, iq, ik: (ib, ih, 0, iq, 0)
            ),
            pl.BlockSpec((1, 1, bk, hd), lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, bq, hd), lambda ib, ih, iq, ik: (ib, ih, 0, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, bq), jnp.float32),
            pltpu.VMEM((g, bq), jnp.float32),
            pltpu.VMEM((g, bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q_positions.astype(jnp.int32).reshape(b, t, 1), qh, kh, vh)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, hd)


# ---------------------------------------------------------------------------
# decode kernel: q [B, H, hd] (one token per sequence) vs arena [B, S, KV, hd]
# ---------------------------------------------------------------------------


def _decode_kernel(
    pos_ref,  # [B] int32 (SMEM, unblocked)
    q_ref,  # [1, 1, G, hd]
    k_ref,  # [1, 1, bk, hd]
    v_ref,  # [1, 1, bk, hd]
    o_ref,  # [1, 1, G, hd]
    m_ref,  # [G, 1] f32
    l_ref,  # [G, 1] f32
    acc_ref,  # [G, hd] f32
    *,
    block_k: int,
    seq_len_k: int,
    scale: float,
):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[pl.program_id(0)]
    k_start = ik * block_k

    @pl.when(k_start <= pos)
    def _compute():
        col = k_start + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = (col <= pos) & (col < seq_len_k)  # [1, bk]
        qb = q_ref[0, 0].astype(jnp.float32)  # [G, hd]
        kb = k_ref[0, 0].astype(jnp.float32)  # [bk, hd]
        s = lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [G, bk]
        s = jnp.where(mask, s * scale, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        vb = v_ref[0, 0].astype(jnp.float32)
        vb = jnp.where(col.reshape(block_k, 1) < seq_len_k, vb, 0.0)
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, 0] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(
    q: jnp.ndarray,  # [B, H, hd]
    k: jnp.ndarray,  # [B, S, KV, hd]
    v: jnp.ndarray,  # [B, S, KV, hd]
    q_positions: jnp.ndarray,  # [B] int32
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-token attention over the KV arena, fused softmax — no [B,H,S]
    score tensor ever reaches HBM (the decode path is HBM-bandwidth-bound)."""
    b, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    bk = min(block_k, _round_up(s, 128))

    qh = q.reshape(b, kv, g, hd)
    kh = k.transpose(0, 2, 1, 3)  # [B,KV,S,hd]
    vh = v.transpose(0, 2, 1, 3)

    grid = (b, kv, pl.cdiv(s, bk))
    kernel = functools.partial(
        _decode_kernel, block_k=bk, seq_len_k=s, scale=1.0 / (hd**0.5)
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # whole [B] positions
            pl.BlockSpec((1, 1, g, hd), lambda ib, ih, ik: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda ib, ih, ik: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda ib, ih, ik: (ib, ih, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda ib, ih, ik: (ib, ih, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q_positions.astype(jnp.int32), qh, kh, vh)
    return out.reshape(b, h, hd)


def kernel_supported(n_heads: int, n_kv_heads: int, head_dim: int) -> bool:
    """The kernels assume lane-aligned head_dim and clean GQA grouping."""
    return head_dim % 128 == 0 and n_heads % n_kv_heads == 0


def flash_attention_tpu(q, k, v, mask=None):
    """Back-compat entry used by ops/attention.py's dispatch: causal
    self-attention (no arena). Raises for shapes the kernel can't take —
    the caller falls back to the XLA reference path."""
    if not kernel_supported(q.shape[2], k.shape[2], q.shape[3]):
        raise ValueError("unsupported attention shape for the pallas kernel")
    b, t = q.shape[0], q.shape[1]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    return flash_prefill(q, k, v, positions)


# ---------------------------------------------------------------------------
# paged (block-table) attention: pool [P, KV, page_size, hd] + block table
# ---------------------------------------------------------------------------
#
# The block table and query positions ride as scalar-prefetch operands
# (PrefetchScalarGridSpec), so each page's K/V block is DMA'd HBM→VMEM
# straight out of the pool at ``table[b, page]`` — the index_map IS the
# page walk; no gathered [B, S, KV, hd] arena copy ever materializes in
# HBM. One (page, kv-head) block is a contiguous ``[page_size, hd]`` slab
# (KV heads sit outside the page in the pool layout), which is what keeps
# the last two block dims on the (8, 128) tiling the chip's compiler
# demands. The innermost grid dimension iterates logical pages and the
# online-softmax (m, l, acc) recurrence is identical to the dense kernels
# above with block_k == page_size.
#
# The reference these kernels are A/B'd against is gather + dense flash
# (ops/attention.py: one XLA dynamic-gather into a contiguous arena view,
# then the dense kernels): bit-for-bit in f32 under interpret=True on CPU
# (tests/test_pallas_attention.py), and selectable on a chip with
# AGENTAINER_PAGED_GATHER=1. ``plan_cache_attention`` picks between them.


def _paged_prefill_kernel(
    table_ref,  # [B, n_blocks] int32 (SMEM, scalar prefetch)
    pos_ref,  # [1, bq, 1] int32           (VMEM)
    q_ref,  # [1, 1, G, bq, hd]            (VMEM)
    k_ref,  # [ps, hd] — the page at table[b, page]
    v_ref,  # [ps, hd]
    o_ref,  # [1, 1, G, bq, hd]
    m_ref,  # [G, bq] f32 scratch
    l_ref,  # [G, bq] f32 scratch
    acc_ref,  # [G, bq, hd] f32 scratch
    *,
    groups: int,
    page_size: int,
    seq_len_k: int,
    scale: float,
):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[0, :, 0]  # [bq] int32
    k_start = ik * page_size
    bq = pos.shape[0]
    col = k_start + lax.broadcasted_iota(jnp.int32, (bq, page_size), 1)
    mask = (col <= pos[:, None]) & (col < seq_len_k)  # [bq, ps]

    # pages strictly in the future of every row in this q tile are skipped
    @pl.when(k_start <= jnp.max(pos))
    def _compute():
        kb = k_ref[...].astype(jnp.float32)  # [ps, hd]
        vb = v_ref[...].astype(jnp.float32)
        col_valid = k_start + lax.broadcasted_iota(jnp.int32, (page_size, 1), 0)
        vb = jnp.where(col_valid < seq_len_k, vb, 0.0)
        for g in range(groups):
            qb = q_ref[0, 0, g].astype(jnp.float32)  # [bq, hd]
            s = lax.dot_general(
                qb,
                kb,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [bq, ps]
            s = jnp.where(mask, s * scale, NEG_INF)
            m_prev = m_ref[g, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            l_ref[g, :] = l_ref[g, :] * alpha + jnp.sum(p, axis=-1)
            acc_ref[g] = acc_ref[g] * alpha[:, None] + lax.dot_general(
                p,
                vb,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[g, :] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def fused_paged_flash_prefill(
    q: jnp.ndarray,  # [B, T, H, hd]
    pool_k: jnp.ndarray,  # [P, KV, page_size, hd]
    pool_v: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, n_blocks] int32
    q_positions: jnp.ndarray,  # [B, T] int32
    block_q: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged prefill that walks the block table in the kernel grid: the
    K/V index_map reads ``table[b, page]`` from scalar-prefetch SMEM, so
    page blocks stream pool→VMEM with no gathered arena in between."""
    b, t, h, hd = q.shape
    kv, ps = pool_k.shape[1], pool_k.shape[2]
    n_blocks = block_table.shape[1]
    g = h // kv
    bq = min(block_q, _round_up(t, 8))
    seq_len_k = n_blocks * ps

    qh = q.reshape(b, t, kv, g, hd).transpose(0, 2, 3, 1, 4)  # [B,KV,G,T,hd]

    grid = (b, kv, pl.cdiv(t, bq), n_blocks)
    kernel = functools.partial(
        _paged_prefill_kernel,
        groups=g,
        page_size=ps,
        seq_len_k=seq_len_k,
        scale=1.0 / (hd**0.5),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the block table
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1), lambda ib, ih, iq, ik, tbl: (ib, iq, 0)),
            pl.BlockSpec(
                (1, 1, g, bq, hd),
                lambda ib, ih, iq, ik, tbl: (ib, ih, 0, iq, 0),
            ),
            # the page walk: block index into the pool comes from the table
            pl.BlockSpec(
                (None, None, ps, hd),
                lambda ib, ih, iq, ik, tbl: (tbl[ib, ik], ih, 0, 0),
            ),
            pl.BlockSpec(
                (None, None, ps, hd),
                lambda ib, ih, iq, ik, tbl: (tbl[ib, ik], ih, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, bq, hd), lambda ib, ih, iq, ik, tbl: (ib, ih, 0, iq, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, bq), jnp.float32),
            pltpu.VMEM((g, bq), jnp.float32),
            pltpu.VMEM((g, bq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=interpret,
    )(
        block_table.astype(jnp.int32),
        q_positions.astype(jnp.int32).reshape(b, t, 1),
        qh,
        pool_k,
        pool_v,
    )
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, hd)


def _paged_decode_kernel(
    table_ref,  # [B, n_blocks] int32 (SMEM, scalar prefetch)
    pos_ref,  # [B] int32 (SMEM, scalar prefetch)
    q_ref,  # [G, hd]
    k_ref,  # [ps, hd] — the page at table[b, page]
    v_ref,  # [ps, hd]
    o_ref,  # [G, hd]
    m_ref,  # [G, 1] f32
    l_ref,  # [G, 1] f32
    acc_ref,  # [G, hd] f32
    *,
    page_size: int,
    seq_len_k: int,
    scale: float,
):
    ip = pl.program_id(2)
    npg = pl.num_programs(2)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[pl.program_id(0)]
    k_start = ip * page_size

    @pl.when(k_start <= pos)
    def _compute():
        col = k_start + lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        mask = (col <= pos) & (col < seq_len_k)  # [1, ps]
        qb = q_ref[...].astype(jnp.float32)  # [G, hd]
        kb = k_ref[...].astype(jnp.float32)  # [ps, hd]
        s = lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [G, ps]
        s = jnp.where(mask, s * scale, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        vb = v_ref[...].astype(jnp.float32)
        vb = jnp.where(col.reshape(page_size, 1) < seq_len_k, vb, 0.0)
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, 0] = m_new

    @pl.when(ip == npg - 1)
    def _finish():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_paged_flash_decode(
    q: jnp.ndarray,  # [B, H, hd]
    pool_k: jnp.ndarray,  # [P, KV, page_size, hd]
    pool_v: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, n_blocks] int32
    q_positions: jnp.ndarray,  # [B] int32
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-token paged attention with the block-table walk fused into
    the grid (block_k == page_size); pages past the lane's position are
    skipped entirely — decode reads exactly the live pages from HBM."""
    b, h, hd = q.shape
    kv, ps = pool_k.shape[1], pool_k.shape[2]
    n_blocks = block_table.shape[1]
    g = h // kv
    seq_len_k = n_blocks * ps

    qh = q.reshape(b, kv, g, hd)

    kernel = functools.partial(
        _paged_decode_kernel,
        page_size=ps,
        seq_len_k=seq_len_k,
        scale=1.0 / (hd**0.5),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table + positions
        grid=(b, kv, n_blocks),
        in_specs=[
            pl.BlockSpec(
                (None, None, g, hd), lambda ib, ih, ip, tbl, pos: (ib, ih, 0, 0)
            ),
            pl.BlockSpec(
                (None, None, ps, hd),
                lambda ib, ih, ip, tbl, pos: (tbl[ib, ip], ih, 0, 0),
            ),
            pl.BlockSpec(
                (None, None, ps, hd),
                lambda ib, ih, ip, tbl, pos: (tbl[ib, ip], ih, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, g, hd), lambda ib, ih, ip, tbl, pos: (ib, ih, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=interpret,
    )(
        block_table.astype(jnp.int32),
        q_positions.astype(jnp.int32),
        qh,
        pool_k,
        pool_v,
    )
    return out.reshape(b, h, hd)
