"""Pallas TPU flash-attention kernels for the serving path.

Green-field TPU component (the reference has no model/kernel code —
SURVEY.md §2: "Native components: there are none"); this is the
CUDA-kernel-equivalent tier of the new framework, written as Mosaic/Pallas
blockwise kernels.

Design notes (why this shape, not a torch translation):

- **One masking rule covers every serving phase.** The engine's KV arena is
  a static ``[L, B, S, KV, hd]`` buffer written at per-sequence positions
  (models/llama.py); the kernels read layer ``l`` of it where it lies. A
  query row at position ``p`` may see arena slot ``j`` iff ``j <= p`` —
  that single rule *is* causal attention when positions are
  ``arange(T)`` (training / no-cache prefill), *is* ragged cached prefill
  when each sequence sits at a different offset (continuous batching), and
  *is* decode when T == 1. So both kernels take ``q_positions`` and build
  the mask in-register from a 2-D iota — no ``[B, T, S]`` mask tensor ever
  touches HBM.
- **Online softmax, f32 state, operands as stored.** Scores and the running
  (m, l, acc) state are float32 in VMEM scratch that persists across the
  innermost KV-block grid dimension; softmax rescaling follows the standard
  flash recurrence. ``flash_prefill`` hands the MXU q, K, V and the
  probabilities in the arena's dtype (bfloat16 as served; float32 arrays
  run float32, which is what the interpret-mode parity tests use) with a
  float32 ``preferred_element_type``, keeps m and l a row to a sublane
  (``[rows, 1]``, no move across lanes in the loop) and tiles its rows and
  keys by a plan of the call's shapes (``prefill_plan``). ``flash_decode``
  and the page pool's kernels convert to float32 first; at Mosaic's default
  precision the MXU rounds those operands to bfloat16 in one pass all the
  same (measured: PERF.md section 5, PR 49).
- **GQA without materializing repeated K/V.** A K/V block holds a block of
  kv heads of a run of positions; the kernel loops over those heads and the
  G = H/KV query heads of each group run against the same head of the
  block already resident in VMEM — K/V HBM traffic is per *kv* head, the
  way GQA intends.
- **Causal block skipping.** KV blocks entirely in the future of every
  query row in the tile (``k_start > max(pos)``) skip their matmuls via
  ``pl.when`` predication — ~2x prefill FLOP cut at long context. The dense
  kernels skip their DMA too: the K/V index maps name no block past the
  one that holds the rows' last position (``kv_block_index``), so a decode
  lane reads the blocks it has written and not the arena row.

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
# dense arena kernels: q vs layer ``layer`` of the stacked arena
# k/v [L, B, S, KV, hd], read in that layout
# ---------------------------------------------------------------------------
#
# The layer scan carries the whole stack (models/llama.py) and a Pallas
# operand has to be a materialised array, so the kernels take the STACK and
# find their block themselves: ``layer`` and ``slot`` ride as scalar-prefetch
# operands and the K/V index maps address ``(layer, slot + b, position
# block, head block)``. A block is ``[block_k, heads, hd]`` — a run of
# positions with a block of KV heads (all of them up to 16), contiguous in
# HBM as stored — and the kernel loops over the heads inside, taking head h
# out of the block in VMEM. Nothing the size of a layer is sliced,
# transposed or copied on the way in. (The ``[…, S, KV·hd]`` view with a
# head as a 128-lane column block is NOT free on the chip: HBM tiles the
# last two dims, so that reshape is a relayout of the whole arena.)
#
# A block with every head grows with the model's head count where the old
# per-head blocks did not, so the blocks are SIZED, not fixed. K and V blocks
# (double-buffered) get 8 MiB in decode, where they are all the traffic and the
# 16 MiB a Mosaic kernel gets by default hold them, and 4 MiB in prefill. A
# prefill q tile holds every query head of the block's KV heads × ``block_q``
# rows, and everything that has its rows: q and the output in two buffers
# each, the float32 accumulator, the softmax's m and l (a float32 a row, each
# padded to a lane tile: on the row's sublane, so no step moves them across
# lanes; kept along the lanes they cost a relayout a head and a block, two
# thirds of the call at GQA-7: PERF.md section 5, PR 49), the positions, and
# one head's float32 scores with their exponentials. The tile is the largest
# ``block_q`` whose rows fit ``_PREFILL_Q_VMEM`` (256 rows of 28 / 4 heads: 28
# MiB; a chunk's K/V blocks are then read once, not once a 128-row tile), and
# the call asks Mosaic for what the two plans need, no more
# (``prefill_plan``). The MXU's operands are what the caller stores: q, K, V
# and the probabilities in the arena's dtype (bfloat16 as served, which is
# what a float32 matmul at Mosaic's default precision rounds them to anyway;
# float32 arrays run float32), scores and state in float32.

_DECODE_KV_VMEM = 8 << 20
_PREFILL_KV_VMEM = 4 << 20
_PREFILL_Q_VMEM = 32 << 20


def _kv_block(kv: int, hd: int, dtype, s: int, block_k: int, vmem: int) -> tuple[int, int]:
    """``(heads, positions)`` of a K/V block ``[positions, heads, hd]``.

    Heads: all of them, or 16 at a time where that divides (16 rows are
    whole sublane tiles at every dtype of 2 bytes or more; a head count
    with no such divisor stays whole and its block gets shorter instead).
    Positions: ``block_k``, or as many 128s as ``vmem`` holds of K and V
    blocks, two buffers each, the heads padded to whole tiles."""
    heads = 16 if kv % 16 == 0 else kv
    fit = max(128, vmem // _kv_position_bytes(heads, hd, dtype) // 128 * 128)
    return heads, min(block_k, _round_up(s, 128), fit)


def _kv_position_bytes(heads: int, hd: int, dtype) -> int:
    """VMEM bytes a position of a K/V block costs: K and V, two buffers each,
    the heads padded to whole sublane tiles."""
    itemsize = jnp.dtype(dtype).itemsize
    return 4 * _round_up(heads, 32 // itemsize) * hd * itemsize


def decode_kv_block(kv: int, hd: int, dtype, s: int, block_k: int = 512) -> tuple[int, int]:
    """``flash_decode``'s K/V block over an arena of ``s`` positions:
    ``(heads, positions)`` (the engine counts fetched blocks with it)."""
    return _kv_block(kv, hd, dtype, s, block_k, _DECODE_KV_VMEM)


def ring_block(kv: int, hd: int, dtype) -> int:
    """Rows a window layer's ring has to be whole multiples of: the larger of
    the two kernels' K/V blocks (each a power-of-two count of 128s)."""
    long = 1 << 20
    return max(_kv_block(kv, hd, dtype, long, 256, _PREFILL_KV_VMEM)[1], decode_kv_block(kv, hd, dtype, long)[1])


def _scalar(x) -> jnp.ndarray:
    """A prefetched scalar operand: int32 ``[1]``."""
    return jnp.asarray(x, jnp.int32).reshape(1)


def kv_block_index(ik, n_steps, last_pos, block_k: int):
    """The position block that step ``ik`` of the ``n_steps`` a grid gives a
    K/V row holds, for query rows that see the arena up to ``last_pos``; the
    steps before the first block read negative. A K/V index map never names a
    block past the last position its rows can attend to: the ``live =
    last_pos // block_k + 1`` blocks are the row's LAST steps, the steps
    before them hold block 0 and do nothing, and a step whose block index did
    not change fetches nothing, so a lane costs the DMA of what it has
    written and not of the arena row. Idle steps first, not last: the
    pipeline fetches one step ahead, so a row's first block is then fetched
    under the previous row's last matmuls; behind idle steps it would wait
    for its DMA with nothing to hide it (measured: section 6 of PERF.md, PR
    33). A position past the arena's end sees every block, as it always did."""
    return ik - (n_steps - jnp.minimum(last_pos // block_k + 1, n_steps))


def ring_block_index(ik, n_steps, lo, last, block_k: int):
    """``kv_block_index`` for a window layer's ring of ``n_steps`` blocks (row
    of position p: ``p mod R``, ``R = n_steps * block_k``): ``(ring block,
    live)`` of step ``ik`` for query rows that see positions ``lo .. last``.
    The blocks that hold those positions are the row's LAST steps, in the
    order of their positions; the steps before them hold the first of them
    and do nothing, so blocks under ``lo`` are not fetched, as blocks over
    ``last`` are not. A span that laps the ring (its first and last position
    share a block) visits every block once: ``ring_positions`` gives each row
    of a block the position it holds."""
    first = lo // block_k
    live = jnp.minimum(last // block_k - first + 1, n_steps)
    step = ik - (n_steps - live)
    return (first + jnp.maximum(step, 0)) % n_steps, step >= 0


def ring_positions(block, last, block_k: int, ring: int) -> jnp.ndarray:
    """``[1, block_k]``: the position each row of ring block ``block`` holds
    once a launch has written up to position ``last``: the newest position at
    or before ``last`` that lands on the row (rows past ``last mod ring`` are
    a lap behind; negative: never written). What ``models/llama.ring_rows``
    guarantees is that every position a query of the launch sees is still the
    newest on its row."""
    row = block * block_k + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    return row + ring * (last // ring - (row > last % ring).astype(jnp.int32))


def _head(ref, h: int) -> jnp.ndarray:
    """Head ``h`` of a K/V block ref ``[1, 1, bk, heads, hd]`` as f32 ``[bk, hd]``.

    The block holds a run of KV heads of its positions (what is contiguous in
    the stored layout), so a head is one sublane row out of each position's
    ``[KV, hd]`` tile. bf16 packs two heads into each 32-bit sublane word:
    there the block is viewed as uint32 ``[bk · KV/2, hd]``, one
    sublane-strided load fetches the word pair (h, h ^ 1) for all ``bk``
    positions, and the half that is head ``h`` moved to the top of the word
    IS its f32 value (bf16 → f32 is a 16-bit shift) — the convert the
    kernels need anyway. Measured on a v5e at 16 × 2,048 × 16 × 128 this
    reads the arena at 89 % of the HBM roofline (0.369 ms a call against
    0.529 for the plain index and 0.45 for the old pre-transposed kernel).
    Other dtypes, and an odd head count, index the head out directly."""
    _, _, bk, kv, hd = ref.shape
    if ref.dtype == jnp.bfloat16 and kv % 2 == 0:
        words = ref.bitcast(jnp.uint32).reshape(bk * (kv // 2), hd)
        pair = words[pl.ds(h // 2, bk, stride=kv // 2), :]
        bits = (pair << 16) if h % 2 == 0 else (pair & jnp.uint32(0xFFFF0000))
        return pltpu.bitcast(bits, jnp.float32)
    return ref[0, 0, :, h, :].astype(jnp.float32)


def _prefill_kernel(
    layer_ref,  # [1] int32 (SMEM, scalar prefetch; used by the index maps)
    slot_ref,  # [1] int32 (SMEM, scalar prefetch; used by the index maps)
    last_ref,  # [B] int32 (SMEM, scalar prefetch) each sequence's last position
    *refs,  # with ``window``: lo_ref [B] int32 (SMEM, scalar prefetch) first; then
    # pos_ref [G, bq, 1] int32 (VMEM) the q tile's positions, per group
    # q_ref [heads, G, bq, hd] (VMEM) heads: this block of KV heads
    # k_ref, v_ref [1, 1, bk, heads, hd] (VMEM)
    # o_ref [heads, G, bq, hd] (VMEM)
    # m_ref, l_ref [heads, G * bq, 1] f32 scratch; acc_ref [heads, G * bq, hd] f32 scratch
    block_k: int,
    seq_len_k: int,
    scale: float,
    window: int = 0,
):
    if window:
        lo_ref, *refs = refs
    pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    kv_heads, groups, bq, hd = q_ref.shape
    rows = groups * bq  # a kv head's G query heads run as ONE matmul

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[...].reshape(rows, 1)  # row g * bq + i is query i of group g
    if window:
        # a ring (``seq_len_k`` rows, whole blocks): the block of this step and
        # the position each of its rows holds; a row sees the last ``window``
        last = last_ref[pl.program_id(0)]
        blk, live = ring_block_index(ik, nk, lo_ref[pl.program_id(0)], last, block_k)
        held = ring_positions(blk, last, block_k, seq_len_k)  # [1, bk]
        # skip the steps before the first block, and blocks wholly in the
        # future or wholly behind the window of every row in this q tile
        run = live & (jnp.min(held) <= jnp.max(pos)) & (jnp.max(held) + window > jnp.min(pos))
        k_start = 0  # every row of a ring block is inside the ring
    else:
        blk = kv_block_index(ik, nk, last_ref[pl.program_id(0)], block_k)
        k_start = blk * block_k
        # skip the steps before the sequence's first block, and KV blocks
        # strictly in the future of every row in this q tile
        run = (blk >= 0) & (k_start <= jnp.max(pos))

    @pl.when(run)
    def _compute():
        # the mask is built HERE, inside the step: built beside ``run`` it is a
        # [G * bq, bk] value that crosses into this body through VMEM, written
        # once and read back by every head (+ 38 % of a deep GQA-7 call)
        if window:
            # (``held + window``, never ``pos - window``: a q tile's padding
            # rows carry whatever positions the block's padding holds)
            mask = (held <= pos) & (held + window > pos) & (held >= 0)  # [G * bq, bk]
        else:
            col = k_start + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            mask = (col <= pos) & (col < seq_len_k)  # [G * bq, bk]
        # rows past the arena end are padded garbage (can be NaN): zero them,
        # since 0 * NaN from the masked-out probabilities would poison acc
        col_valid = k_start + lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
        operand = jnp.promote_types(q_ref.dtype, k_ref.dtype)
        for h in range(kv_heads):
            # the MXU takes q, K, V and the probabilities in the arena's dtype
            # (``_head``'s float32 of a bfloat16 head rounds back exactly);
            # scores, the softmax's state and the accumulator are float32
            qb = q_ref[h].reshape(rows, hd).astype(operand)
            kb = _head(k_ref, h).astype(operand)  # [bk, hd]
            vb = jnp.where(col_valid < seq_len_k, _head(v_ref, h), 0.0).astype(v_ref.dtype)
            s = lax.dot_general(
                qb,
                kb,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G * bq, bk]
            s = jnp.where(mask, s * scale, NEG_INF)
            # m, l ``[G * bq, 1]``: a row's state stays on the row's sublane
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + lax.dot_general(
                p.astype(vb.dtype),
                vb,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked (padding) rows
        out = acc_ref[...] / l
        o_ref[...] = out.reshape(kv_heads, groups, bq, hd).astype(o_ref.dtype)


def prefill_plan(
    t: int, h: int, kv: int, hd: int, s: int, q_dtype, kv_dtype, block_q: int = 256, block_k: int = 256
) -> tuple[int, int, int, int]:
    """``flash_prefill``'s tiles for ``t`` rows of ``h`` query heads over an
    arena of ``s`` positions of ``kv`` heads: ``(heads, bq, bk, vmem_bytes)``,
    a function of the call's shapes and dtypes alone. ``heads, bk``: the K/V
    block (``_kv_block``). ``bq``: ``block_q`` rows, halved until a tile's
    rows fit ``_PREFILL_Q_VMEM``. ``vmem_bytes``: both plans, what the call
    asks Mosaic for (less its margin)."""
    g = h // kv
    heads, bk = _kv_block(kv, hd, kv_dtype, s, block_k, _PREFILL_KV_VMEM)
    q_item, kv_item = jnp.dtype(q_dtype).itemsize, jnp.dtype(kv_dtype).itemsize
    # a q row across the block's heads and their G query heads: q and the
    # output in two buffers each and the f32 accumulator; m and l, a lane tile
    # each; the positions (an int32 a row and group, a lane tile, two
    # buffers); the scores, their exponentials and those rounded for the
    # value matmul, of two heads in flight
    per_row = g * (heads * (hd * (4 * q_item + 4) + 2 * 128 * 4) + 2 * 128 * 4 + 2 * bk * (8 + kv_item))
    bq = min(block_q, _round_up(t, 8))
    while bq > 8 and bq * per_row > _PREFILL_Q_VMEM:
        bq = _round_up(bq // 2, 8)
    return heads, bq, bk, bq * per_row + bk * _kv_position_bytes(heads, hd, kv_dtype)


def prefill_tile(t: int, h: int, kv: int, hd: int, s: int, q_dtype, kv_dtype) -> dict:
    """The plan in words, for an engine's ``/metrics``: the q tile's rows, the
    K/V block's positions and the dtype of the MXU's operands."""
    _, bq, bk, _ = prefill_plan(t, h, kv, hd, s, q_dtype, kv_dtype)
    return {"bq": bq, "bk": bk, "operands": str(jnp.promote_types(q_dtype, kv_dtype))}


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret", "window")
)
def flash_prefill(
    q: jnp.ndarray,  # [B, T, H, hd]
    k: jnp.ndarray,  # [L, Bc, S, KV, hd] the stacked arena
    v: jnp.ndarray,
    q_positions: jnp.ndarray,  # [B, T] int32
    layer,  # int32 scalar: the layer of the stack to read
    slot=0,  # int32 scalar: sequence b reads arena row slot + b
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    window: int = 0,
) -> jnp.ndarray:
    """Blockwise flash attention; row t sees arena slot j iff j <= pos[b, t].
    ``window``: the arena is a window layer's ring (row of position p: ``p mod
    S``, S whole K/V blocks) and row t sees the positions ``pos - window <
    j <= pos`` in it; blocks that hold none of a sequence's are not fetched."""
    b, t, h, hd = q.shape
    s, kv = k.shape[2], k.shape[3]
    g = h // kv
    heads, bq, bk, vmem_bytes = prefill_plan(t, h, kv, hd, s, q.dtype, k.dtype, block_q, block_k)

    qh = q.reshape(b, t, kv, g, hd).transpose(0, 2, 3, 1, 4)  # [B,KV,G,T,hd]
    # positions once per group, so a q tile's [G, bq] rows carry their own
    # ([…, 1]: the (sublane, lane) dims stay TPU-block-legal)
    pos = jnp.broadcast_to(q_positions.astype(jnp.int32)[:, None, :, None], (b, g, t, 1))
    # the last position any row of a sequence sees: the K/V index map stops
    # there (a bucket's padding rows carry positions that run on past the real
    # tokens and may pass the arena's end, where ``kv_block_index`` stops)
    last = q_positions.astype(jnp.int32).max(axis=1)
    scalars = (_scalar(layer), _scalar(slot), last)
    window_kw = {}
    if window:
        if s % bk:
            raise ValueError(f"a ring of {s} rows is not whole K/V blocks of {bk}")
        # the first position any row of a sequence sees: the lower bound
        scalars += (jnp.maximum(q_positions.astype(jnp.int32).min(axis=1) - (window - 1), 0),)
        window_kw = {"window": window}

    kernel = functools.partial(
        _prefill_kernel, block_k=bk, seq_len_k=s, scale=1.0 / (hd**0.5), **window_kw
    )
    q_spec = pl.BlockSpec(
        (None, heads, g, bq, hd), lambda ib, ih, iq, ik, *scalars: (ib, ih, 0, iq, 0)
    )

    n_blocks = pl.cdiv(s, bk)

    if window:
        def kv_map(ib, ih, iq, ik, lay, slt, last, lo):
            return lay[0], slt[0] + ib, ring_block_index(ik, n_blocks, lo[ib], last[ib], bk)[0], ih, 0
    else:
        def kv_map(ib, ih, iq, ik, lay, slt, last):
            return lay[0], slt[0] + ib, jnp.maximum(kv_block_index(ik, n_blocks, last[ib], bk), 0), ih, 0

    kv_spec = pl.BlockSpec((1, 1, bk, heads, hd), kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer, slot, each sequence's last position (and, windowed, its first)
        num_scalar_prefetch=len(scalars),
        grid=(b, kv // heads, pl.cdiv(t, bq), n_blocks),
        in_specs=[
            pl.BlockSpec(
                (None, g, bq, 1), lambda ib, ih, iq, ik, *scalars: (ib, 0, iq, 0)
            ),
            q_spec,
            kv_spec,
            kv_spec,
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((heads, g * bq, 1), jnp.float32),
            pltpu.VMEM((heads, g * bq, 1), jnp.float32),
            pltpu.VMEM((heads, g * bq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_bytes + (8 << 20)),
        interpret=interpret,
    )(*scalars, pos, qh, k, v)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, hd)


def _decode_kernel(
    layer_ref,  # [1] int32 (SMEM, scalar prefetch; used by the index maps)
    slot_ref,  # [1] int32 (SMEM, scalar prefetch; used by the index maps)
    pos_ref,  # [B] int32 (SMEM, scalar prefetch)
    q_ref,  # [heads, G, hd]   heads: this block of KV heads
    k_ref,  # [1, 1, bk, heads, hd]
    v_ref,  # [1, 1, bk, heads, hd]
    o_ref,  # [heads, G, hd]
    m_ref,  # [heads, G, 1] f32
    l_ref,  # [heads, G, 1] f32
    acc_ref,  # [heads, G, hd] f32
    *,
    block_k: int,
    seq_len_k: int,
    scale: float,
    window: int = 0,
):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    kv_heads = q_ref.shape[0]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[pl.program_id(0)]
    if window:
        # a ring: the lane's last ``window`` positions, wherever they lie in it
        blk, run = ring_block_index(ik, nk, jnp.maximum(pos - (window - 1), 0), pos, block_k)
    else:
        blk = kv_block_index(ik, nk, pos, block_k)
        run = blk >= 0
    k_start = blk * block_k

    @pl.when(run)  # the steps before the lane's first block do nothing
    def _compute():
        col = k_start + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        if window:
            held = ring_positions(blk, pos, block_k, seq_len_k)
            mask = (held <= pos) & (held + window > pos) & (held >= 0)  # [1, bk]
        else:
            mask = (col <= pos) & (col < seq_len_k)  # [1, bk]
        row_valid = col.reshape(block_k, 1) < seq_len_k
        for h in range(kv_heads):
            qb = q_ref[h].astype(jnp.float32)  # [G, hd]
            kb = _head(k_ref, h)  # [bk, hd]
            s = lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # [G, bk]
            s = jnp.where(mask, s * scale, NEG_INF)
            m_prev = m_ref[h, :, 0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            vb = jnp.where(row_valid, _head(v_ref, h), 0.0)
            l_ref[h, :, 0] = l_ref[h, :, 0] * alpha + jnp.sum(p, axis=-1)
            acc_ref[h] = acc_ref[h] * alpha[:, None] + lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[h, :, 0] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret", "window"))
def flash_decode(
    q: jnp.ndarray,  # [B, H, hd]
    k: jnp.ndarray,  # [L, Bc, S, KV, hd] the stacked arena
    v: jnp.ndarray,
    q_positions: jnp.ndarray,  # [B] int32
    layer,  # int32 scalar: the layer of the stack to read
    slot=0,  # int32 scalar: sequence b reads arena row slot + b
    block_k: int = 512,
    interpret: bool = False,
    window: int = 0,
) -> jnp.ndarray:
    """Single-token attention over the KV arena, fused softmax — no [B,H,S]
    score tensor ever reaches HBM (the decode path is HBM-bandwidth-bound),
    and a lane fetches the ``positions // block + 1`` blocks it can see.
    ``window``: the arena is a window layer's ring and a lane fetches the
    blocks that hold its last ``window`` positions (``flash_prefill``)."""
    b, h, hd = q.shape
    s, kv = k.shape[2], k.shape[3]
    g = h // kv
    heads, bk = decode_kv_block(kv, hd, k.dtype, s, block_k)
    window_kw = {}
    if window:
        if s % bk:
            raise ValueError(f"a ring of {s} rows is not whole K/V blocks of {bk}")
        window_kw = {"window": window}

    qh = q.reshape(b, kv, g, hd)

    kernel = functools.partial(
        _decode_kernel, block_k=bk, seq_len_k=s, scale=1.0 / (hd**0.5), **window_kw
    )
    q_spec = pl.BlockSpec(
        (None, heads, g, hd), lambda ib, ih, ik, lay, slt, pos: (ib, ih, 0, 0)
    )

    n_blocks = pl.cdiv(s, bk)

    if window:
        def kv_map(ib, ih, ik, lay, slt, pos):
            lo = jnp.maximum(pos[ib] - (window - 1), 0)
            return lay[0], slt[0] + ib, ring_block_index(ik, n_blocks, lo, pos[ib], bk)[0], ih, 0
    else:
        def kv_map(ib, ih, ik, lay, slt, pos):
            return lay[0], slt[0] + ib, jnp.maximum(kv_block_index(ik, n_blocks, pos[ib], bk), 0), ih, 0

    kv_spec = pl.BlockSpec((1, 1, bk, heads, hd), kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, slot, positions
        grid=(b, kv // heads, n_blocks),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((heads, g, 1), jnp.float32),
            pltpu.VMEM((heads, g, 1), jnp.float32),
            pltpu.VMEM((heads, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=interpret,
    )(_scalar(layer), _scalar(slot), q_positions.astype(jnp.int32), qh, k, v)
    return out.reshape(b, h, hd)


# ---------------------------------------------------------------------------
# a lane's step over its chosen key blocks (ops/sparse_attention.py)
# ---------------------------------------------------------------------------
#
# A block-sparse layer's step reads ``topk`` listed blocks of ``block`` rows a
# K/V head, anywhere in the lane's rows. A block with every stored head is one
# contiguous run of the arena (64 rows x 2 heads x 128 bf16: 32 KB), far too
# small for one block a grid step, and its address comes from a list, not from
# the grid. So the grid is the lanes, K and V stay in HBM (``pl.ANY``), and the
# kernel copies a CHUNK of listed blocks at a time into one half of a
# double-buffered VMEM scratch with its own async copies, the next chunk's
# (or the next lane's first) in flight under this one's matmuls. A block that
# a later head lists at the place where the first head lists it is copied
# once and read by both: the selection puts its forced blocks, the same for
# every head, first and in order (``select_blocks``: +inf scores, ``top_k``
# breaks ties by index), which is half the list.

_SPARSE_KV_VMEM = 4 << 20


def sparse_chunk(kv: int, stored: int, hd: int, dtype, block: int, topk: int) -> int:
    """Listed blocks ``sparse_decode`` copies a chunk: a divisor of ``topk``,
    as many as reach 512 rows a head (``flash_decode``'s block) while K and V
    chunks of every head, two buffers each, fit ``_SPARSE_KV_VMEM``."""
    rows = _SPARSE_KV_VMEM // (4 * kv * stored * hd * jnp.dtype(dtype).itemsize)
    most = max(1, min(512, rows) // block)
    return next(n for n in range(min(most, topk), 0, -1) if topk % n == 0)


def _sparse_decode_kernel(
    layer_ref,  # [1] int32 (SMEM, scalar prefetch)
    slot_ref,  # [1] int32 (SMEM, scalar prefetch)
    pos_ref,  # [B] int32 (SMEM, scalar prefetch)
    blocks_ref,  # [B * KV * topk] int32 (SMEM, scalar prefetch) the lists, -1: none
    q_ref,  # [KV, G, hd]
    k_hbm,  # [L, Bc, S, KVs, hd] where it lies
    v_hbm,
    o_ref,  # [KV, G, hd] f32
    k_buf,  # [2 * KV * per, 1, block, KVs, hd]: buffer, head that listed it, place in the chunk
    v_buf,
    sems,  # DMA [2, 2]: K or V, buffer
    m_ref,  # [KV, G, 1] f32
    l_ref,  # [KV, G, 1] f32
    acc_ref,  # [KV, G, hd] f32
    *,
    block: int,
    topk: int,
    per: int,
    scale: float,
):
    ib, nb = pl.program_id(0), pl.num_programs(0)
    kv = q_ref.shape[0]
    n_chunks = topk // per
    layer, first_lane = layer_ref[0], slot_ref[0]

    def listed(lane, h, i):
        """Place ``i`` of head ``h``'s list, and whether the head holds a copy
        of its own there: head 0 does, a later head where it lists another
        block than head 0."""
        e = blocks_ref[(lane * kv + h) * topk + i]
        return e, (e != blocks_ref[lane * kv * topk + i]) if h else True

    def copies(lane, c, buf, go):
        """Start (or wait for) the copies of chunk ``c`` of ``lane``'s lists
        into buffer ``buf``: the blocks that are there, once each."""
        for h in range(kv):
            for j in range(per):
                e, own = listed(lane, h, c * per + j)

                @pl.when((e >= 0) & own)
                def _():
                    # (a list made for a position past the arena's end may name a block past it)
                    rows = pl.ds(pl.multiple_of(jnp.minimum(e, k_hbm.shape[2] // block - 1) * block, block), block)
                    at = (buf * kv + h) * per + j
                    for hbm, into, sem in ((k_hbm, k_buf, sems.at[0, buf]), (v_hbm, v_buf, sems.at[1, buf])):
                        go(pltpu.make_async_copy(hbm.at[layer, first_lane + lane, rows], into.at[at, 0], sem))

    start, wait = (lambda cp: cp.start()), (lambda cp: cp.wait())

    @pl.when(ib == 0)
    def _first():
        # a place whose entry is -1 is never copied into: what the scratch
        # held before the call may be NaN, and 0 * NaN would reach acc
        v_buf[...] = jnp.zeros_like(v_buf)
        copies(0, 0, 0, start)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    pos = pos_ref[ib]
    col = lax.broadcasted_iota(jnp.int32, (1, per * block), 1)

    def chunk(c, carry):
        buf = (ib * n_chunks + c) % 2
        wraps = c + 1 == n_chunks
        nxt_lane, nxt = jnp.where(wraps, ib + 1, ib), jnp.where(wraps, 0, c + 1)

        @pl.when(nxt_lane < nb)
        def _ahead():
            copies(nxt_lane, nxt, 1 - buf, start)

        copies(ib, c, buf, wait)
        for h in range(kv):
            # where each block's rows lie in the scratch, and the position
            # each row holds: past ``pos`` for a block that is not there
            at, held = [], None
            for j in range(per):
                e, own = listed(ib, h, c * per + j)
                at.append((buf * kv + jnp.where(own, h, 0)) * per + j)
                first = jnp.where(e >= 0, e * block, pos + 1) - j * block
                held = first + col if j == 0 else jnp.where(col >= j * block, first + col, held)
            seen = held <= pos  # [1, per * block]
            qb = q_ref[h].astype(k_hbm.dtype)  # [G, hd]
            kb = jnp.concatenate([_head(k_buf.at[pl.ds(a, 1)], h).astype(k_hbm.dtype) for a in at], axis=0)
            vb = jnp.concatenate([_head(v_buf.at[pl.ds(a, 1)], h).astype(v_hbm.dtype) for a in at], axis=0)
            s = lax.dot_general(qb, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            s = jnp.where(seen, s * scale, NEG_INF)  # [G, per * block]
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    lax.fori_loop(0, n_chunks, chunk, 0)
    o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sparse_decode(
    q: jnp.ndarray,  # [B, H, hd]
    k: jnp.ndarray,  # [L, Bc, S, KVs, hd] the stacked arena
    v: jnp.ndarray,
    blocks: jnp.ndarray,  # [B, KV, topk] int32: each K/V head's blocks, -1 for none
    q_positions: jnp.ndarray,  # [B] int32
    layer,  # int32 scalar: the layer of the stack to read
    slot=0,  # int32 scalar: sequence b reads arena row slot + b
    *,
    block: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-token attention over listed key blocks of ``block`` rows
    (``ops/sparse_attention.attend_blocks``, which is its reference): head
    group ``g`` of lane ``b`` sees row ``r`` iff ``r // block`` is in
    ``blocks[b, g]`` and ``r <= q_positions[b]``. The listed blocks are copied
    from the arena where it lies, and nothing else of it is read; the heads
    past ``KV`` that the arena stores as padding are not attended. float32
    out, whatever ``q`` is."""
    b, h, hd = q.shape
    kv, topk = blocks.shape[1:]
    stored = k.shape[3]
    g = h // kv
    if k.shape[2] % block:
        raise ValueError(f"an arena of {k.shape[2]} rows is not whole key blocks of {block}")
    per = sparse_chunk(kv, stored, hd, k.dtype, block, topk)
    kernel = functools.partial(_sparse_decode_kernel, block=block, topk=topk, per=per, scale=1.0 / (hd**0.5))
    q_spec = pl.BlockSpec((None, kv, g, hd), lambda ib, *scalars: (ib, 0, 0, 0))
    where_it_lies = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # layer, slot, positions, the lists
        grid=(b,),
        in_specs=[q_spec, where_it_lies, where_it_lies],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2 * kv * per, 1, block, stored, hd), k.dtype),
            pltpu.VMEM((2 * kv * per, 1, block, stored, hd), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((kv, g, 1), jnp.float32),
            pltpu.VMEM((kv, g, 1), jnp.float32),
            pltpu.VMEM((kv, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), jnp.float32),
        # a lane's last chunk starts the next lane's first copies
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="sparse_decode",
    )(_scalar(layer), _scalar(slot), q_positions.astype(jnp.int32), blocks.astype(jnp.int32).reshape(-1),
      q.reshape(b, kv, g, hd), k, v)
    return out.reshape(b, h, hd)


def kernel_supported(n_heads: int, n_kv_heads: int, head_dim: int) -> bool:
    """The kernels assume lane-aligned head_dim, clean GQA grouping, and a
    KV-head count whose ``[KV, hd]`` rows are stored unpadded (1, 2, 4 or a
    multiple of 8): any other makes XLA pad the whole arena into a temporary
    before every call (compiled for a described v5e: 537 MB at 12 heads)."""
    return (
        head_dim % 128 == 0
        and n_heads % n_kv_heads == 0
        and (n_kv_heads in (1, 2, 4) or n_kv_heads % 8 == 0)
    )


def flash_attention_tpu(q, k, v, mask=None):
    """Back-compat entry used by ops/attention.py's dispatch: causal
    self-attention (no arena). Raises for shapes the kernel can't take —
    the caller falls back to the XLA reference path."""
    if not kernel_supported(q.shape[2], k.shape[2], q.shape[3]):
        raise ValueError("unsupported attention shape for the pallas kernel")
    b, t = q.shape[0], q.shape[1]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    return flash_prefill(q, k[None], v[None], positions, layer=0)


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
