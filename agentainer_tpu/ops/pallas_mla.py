"""Pallas TPU kernels: MLA decode and prefill over the latent arena where it lies.

Absorbed latent attention (``ops/mla.py``) is multi-query attention with one
shared "head": every one of a sequence's H queries scores against the same
``R + r`` wide latent row and combines the same ``R`` wide value, which is
the first R columns of that row. So a step reads a lane's live rows ONCE for
all heads, and both kernels are the dense flash kernels of
``ops/pallas_attention`` cut to that case:

- the operand is the STACK ``[n_mla, B, S, W]`` as the layer scan carries it
  (W: the ``R + r`` values padded to whole lane tiles, ``hybrid.latent_width``);
  layer and slot are prefetched scalars and the index map addresses
  ``(layer, slot + lane, position block)``: a block is a run of whole rows,
  contiguous in HBM as stored. Nothing the size of a lane's row is sliced or
  copied on the way in;
- blocks past the last position a query can see are not fetched (their index
  repeats a live block's, so no DMA is issued for them);
- the row serves as key and as value, so the arena is read once.

:func:`mla_decode` is one query token a lane: ``[H, W]`` against the lane's
blocks, the accumulator ``[H, W]``, the caller keeps its first R columns.

:func:`mla_prefill` is a chunk of T tokens of each sequence: the absorbed
queries ``[T, H, W]`` viewed as ``T * H`` rows and tiled by tokens, a tile of
``tq * H`` rows against a block of ``bk`` latent rows, so the two matmuls
are about ``[512, 640] x [640, 512]`` at Kimi-Linear's widths. Scores and the
online softmax's state are float32 in VMEM and never reach HBM; the
probabilities are rounded to the rows' dtype before the value matmul, as
``ops/mla.attend`` rounds them. A tile's key blocks stop at the tile's last
position (a prefetched scalar) and are the tile's LAST grid steps
(``pallas_attention.kv_block_index``), so the next tile's first block is
fetched under this tile's matmuls; the mask is built only in the blocks that
straddle the tile's positions or the arena's end. A token that is not valid
(a bucket's padding) is given position -1: it sees nothing, its output is
nobody's, and a tile of such tokens fetches and computes nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import NEG_INF, _round_up, _scalar, kv_block_index

_ROWS_VMEM = 8 << 20  # decode: the row blocks, two buffers
# prefill: the row blocks (two buffers), and a query tile with everything that
# has its rows (q and output in two buffers, accumulator, m, l, positions, the
# float32 scores with their exponentials); the call asks Mosaic for what its
# plan needs and no more (``vmem_limit_bytes``)
_PREFILL_ROWS_VMEM = 4 << 20
_PREFILL_Q_VMEM = 24 << 20


def _accumulate(s, values, m_ref, l_ref, acc_ref):
    """One block of the online softmax: the masked float32 scores ``s [rows,
    bk]`` and the block's values ``[bk, V]`` into the running max, sum and
    accumulator; the probabilities are rounded to the values' dtype."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
        p.astype(values.dtype), values, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new


def _mla_decode_kernel(
    layer_ref, slot_ref, pos_ref,  # prefetched scalars
    q_ref,  # [H, W]
    rows_ref,  # [bk, W]
    o_ref,  # [H, W]
    m_ref, l_ref, acc_ref,  # [H, 1], [H, 1], [H, W] f32
    *, block_k: int, seq_len: int, scale: float,
):
    ik = pl.program_id(1)

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
        seen = (col <= pos) & (col < seq_len)
        rows = rows_ref[...]
        # rows past the arena's end are padding (can be NaN): zero them, since
        # 0 · NaN from the masked-out probabilities would poison the sum
        rows = jnp.where(col.reshape(block_k, 1) < seq_len, rows, jnp.zeros_like(rows))
        s = lax.dot_general(
            q_ref[...], rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [H, bk]
        _accumulate(jnp.where(seen, s * scale, NEG_INF), rows, m_ref, l_ref, acc_ref)

    @pl.when(ik == pl.num_programs(1) - 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_block_rows(width: int, itemsize: int, seq_len: int, block_k: int = 512) -> int:
    """Latent rows of one block of :func:`mla_decode` over an arena of
    ``seq_len`` rows of ``width`` columns (what the engine's count of fetched
    blocks divides a lane's position by)."""
    fit = max(128, _ROWS_VMEM // (2 * width * itemsize) // 128 * 128)
    return min(block_k, _round_up(seq_len, 128), fit)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "block_k", "interpret"))
def mla_decode(
    q_full: jnp.ndarray,  # [B, H, R + r] the absorbed queries
    latent: jnp.ndarray,  # [n_mla, Bc, S, R + r] the stacked arena
    positions: jnp.ndarray,  # [B] int32: lane b sees slots 0 .. positions[b]
    layer,  # int32 scalar: the layer of the stack to read
    slot=0,  # int32 scalar: lane b reads arena row slot + b
    *,
    scale: float,
    rank: int,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """The combined latent ``[B, H, R]`` float32 of one token a lane."""
    b, h, w = q_full.shape
    s = latent.shape[2]
    bk = decode_block_rows(w, latent.dtype.itemsize, s, block_k)
    n_blocks = pl.cdiv(s, bk)

    def rows_map(ib, ik, lay, slt, pos):
        # blocks past the lane's position repeat its last live block: no DMA
        return lay[0], slt[0] + ib, jnp.minimum(ik, pos[ib] // bk), 0

    q_spec = pl.BlockSpec((None, h, w), lambda ib, ik, lay, slt, pos: (ib, 0, 0))
    kernel = functools.partial(_mla_decode_kernel, block_k=bk, seq_len=s, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer, slot, positions
            grid=(b, n_blocks),
            in_specs=[q_spec, pl.BlockSpec((None, None, bk, w), rows_map)],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, w), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_decode",
    )(_scalar(layer), _scalar(slot), positions.astype(jnp.int32), q_full.astype(latent.dtype), latent)
    return out[..., :rank]


def _mla_prefill_kernel(
    layer_ref, slot_ref,  # prefetched scalars (used by the index maps)
    first_ref, last_ref,  # prefetched [B * tiles]: the least and largest position of a tile's tokens
    pos_ref,  # [rows, 1] int32: the position of each query row's token
    q_ref,  # [rows, W]: rows = tq * H, token t's head h is row t * H + h
    rows_ref,  # [bk, W]
    o_ref,  # [rows, V]: V the value's columns (R, or W where R is not whole lane tiles)
    m_ref, l_ref, acc_ref,  # [rows, 1], [rows, 1], [rows, V] f32
    *, block_k: int, seq_len: int, scale: float,
):
    ik, nk = pl.program_id(2), pl.num_programs(2)
    tile = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = kv_block_index(ik, nk, last_ref[tile], block_k)  # negative: a step before the tile's first block
    k_start = blk * block_k
    # a block every row of the tile sees whole, inside the arena, needs no mask
    ragged = (k_start + block_k - 1 > first_ref[tile]) | (k_start + block_k > seq_len)

    def step(masked: bool):
        rows = rows_ref[...]
        if masked:
            col = k_start + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            # rows past the arena's end are padding (can be NaN): zero them, since
            # 0 · NaN from the masked-out probabilities would poison the sum
            rows = jnp.where(col.reshape(block_k, 1) < seq_len, rows, jnp.zeros_like(rows))
        s = lax.dot_general(
            q_ref[...], rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [rows, bk]
        if masked:
            s = jnp.where((col <= pos_ref[...]) & (col < seq_len), s, NEG_INF)
        _accumulate(s, rows[:, : acc_ref.shape[-1]], m_ref, l_ref, acc_ref)

    pl.when((blk >= 0) & ragged)(lambda: step(True))
    pl.when((blk >= 0) & ~ragged)(lambda: step(False))

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "block_q", "block_k", "interpret"))
def mla_prefill(
    q_full: jnp.ndarray,  # [B, T, H, W] the absorbed queries
    latent: jnp.ndarray,  # [n_mla, Bc, S, W] the stacked arena
    positions: jnp.ndarray,  # [B, T] int32: token t sees slots 0 .. positions[b, t]; negative: nothing
    layer,  # int32 scalar: the layer of the stack to read
    slot=0,  # int32 scalar: sequence b reads arena row slot + b
    *,
    scale: float,
    rank: int,
    block_q: int = 16,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """The combined latent ``[B, T, H, R]`` float32 of a chunk of T tokens a
    sequence (``ops/mla.attend`` over the stack where it lies). ``block_q``
    is in tokens: a query tile is ``block_q * H`` rows."""
    b, t, h, w = q_full.shape
    s, item = latent.shape[2], latent.dtype.itemsize
    value_w = rank if rank % 128 == 0 else w
    bk = min(block_k, _round_up(s, 128), max(128, _PREFILL_ROWS_VMEM // (2 * w * item) // 128 * 128))
    # a token's H rows: q in two buffers; output in two and the accumulator;
    # m, l and the positions' two buffers, each a lane tile a row; the scores,
    # their exponentials and those rounded for the value matmul
    per_token = h * (2 * w * item + 3 * value_w * 4 + 4 * 128 * 4 + bk * (8 + item))
    tq = min(block_q, _round_up(t, 8), max(8, _PREFILL_Q_VMEM // per_token // 8 * 8))
    n_tiles, n_blocks = pl.cdiv(t, tq), pl.cdiv(s, bk)

    pos = positions.astype(jnp.int32)
    edge = n_tiles * tq - t  # the last tile's rows past T: nobody's, and they see nothing
    by_tile = lambda fill: jnp.pad(pos, ((0, 0), (0, edge)), constant_values=fill).reshape(b * n_tiles, tq)  # noqa: E731
    first = by_tile(jnp.iinfo(jnp.int32).max).min(axis=1)
    last = by_tile(-1).max(axis=1)

    def rows_map(ib, iq, ik, lay, slt, first, last):
        # the tile's live blocks are its last steps; the steps before them hold
        # block 0 and fetch nothing new
        return lay[0], slt[0] + ib, jnp.maximum(kv_block_index(ik, n_blocks, last[ib * n_tiles + iq], bk), 0), 0

    def tile_spec(width):
        return pl.BlockSpec((None, tq * h, width), lambda ib, iq, ik, *scalars: (ib, iq, 0))

    kernel = functools.partial(_mla_prefill_kernel, block_k=bk, seq_len=s, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # layer, slot, each tile's first and last position
            grid=(b, n_tiles, n_blocks),
            in_specs=[tile_spec(1), tile_spec(w), pl.BlockSpec((None, None, bk, w), rows_map)],
            out_specs=tile_spec(value_w),
            scratch_shapes=[
                pltpu.VMEM((tq * h, 1), jnp.float32),
                pltpu.VMEM((tq * h, 1), jnp.float32),
                pltpu.VMEM((tq * h, value_w), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t * h, value_w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=2 * bk * w * item + tq * per_token + (8 << 20),
        ),
        interpret=interpret,
        name="mla_prefill",
    )(
        _scalar(layer), _scalar(slot), first, last,
        jnp.repeat(pos, h, axis=1)[..., None], q_full.astype(latent.dtype).reshape(b, t * h, w), latent,
    )
    return out.reshape(b, t, h, value_w)[..., :rank]
