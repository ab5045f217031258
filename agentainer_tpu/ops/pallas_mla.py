"""Pallas TPU kernel: MLA decode over the latent arena where it lies.

Absorbed latent attention (``ops/mla.py``) is multi-query attention with one
shared "head": every one of a lane's H queries scores against the same
``R + r`` wide latent row and combines the same ``R`` wide value, which is
the first R columns of that row. So a decode step reads a lane's live rows
ONCE for all heads, and this kernel is ``ops/pallas_attention.flash_decode``
cut to that case:

- the operand is the STACK ``[n_mla, B, S, R + r]`` as the layer scan carries
  it; layer and slot are prefetched scalars and the index map addresses
  ``(layer, slot + lane, position block)``: a block is a run of whole rows,
  contiguous in HBM as stored (``R + r`` = 576 is the array's full last
  dimension, so the block is legal though 576 is no multiple of 128);
- blocks past a lane's position are skipped (their index repeats the last
  live block's, so no DMA is issued for them);
- the row serves as key and as value: the accumulator is ``[H, R + r]`` and
  the caller keeps its first R columns, which costs a ninth more MXU work on
  the value side and saves a second read of the arena.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import NEG_INF, _round_up, _scalar

_ROWS_VMEM = 8 << 20  # the row blocks, two buffers


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
        s = jnp.where(seen, s * scale, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p.astype(rows.dtype), rows, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == pl.num_programs(1) - 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


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
    fit = max(128, _ROWS_VMEM // (2 * w * latent.dtype.itemsize) // 128 * 128)
    bk = min(block_k, _round_up(s, 128), fit)
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
