"""Pallas TPU grouped SwiGLU FFN over the stacked expert weights.

The MoE FFN of a call with many rows (ops/moe.py decides which) sorts its
(token, chosen expert) assignments by expert into tile-aligned rows and runs
every expert over its own rows only. This kernel is that grouped matmul, all
three matrices of it, and the rows' trip to their expert's tile and back:

- **One row tile, one expert.** ``tile_expert[i]`` (a prefetched scalar
  array) names the expert of row tile ``i``; an expert with more rows than a
  tile holds has several tiles in a row, one with none has none. Group sizes
  are therefore free: any skew, all rows on one expert included.
- **The rows never leave VMEM.** The call's ``x [N, d]`` is one resident
  operand and the result one resident float32 ``[N, d]`` accumulator. The
  tile plan's rows exist only a STAGE at a time (``_STAGE_ROWS`` rows, whole
  tiles): at a stage's first tile the kernel builds the stage's 0/1 block
  ``[stage, N]`` from ``row_of`` (row r holds token n) and takes the rows
  from the MXU, ``block @ x``; a tile's result, weighted by its rows' gates
  and rounded to the activations' dtype, is written into the stage, and the
  stage's last tile that holds rows adds ``blockᵀ @ y`` to the accumulator:
  a token's k terms, summed in float32. A stage and not a tile, because the
  0/1 matmuls and the accumulator's read-modify-write cost ``N · d`` whatever
  the rows, and a tile of 32 is a quarter of what the MXU takes for that.
  No array anywhere is sized by the worst routing.
- **The weights stay int8 and stay where they lie.** The operands are the
  stacked ``[L, E, d, F]`` tensors as the engine stores them; the layer is a
  prefetched scalar and the index maps address ``(layer, expert, :, f
  block)``, so nothing of expert size is sliced, copied, relaid or
  dequantised in HBM. An int8 block is converted in VMEM; its
  per-output-channel scale, taken in the dtype the pytree stores and
  converted in VMEM too, multiplies the float32 result columns (gate, up) or
  the finished accumulator (down), which is exact where scaling the weights
  first rounds them to bf16.
- **One pass.** Grid ``(row tiles, F blocks)``: for an F block the kernel
  computes ``silu(x Wg) * (x Wu)`` and adds its product with the matching
  rows of ``Wd`` to a float32 ``[tile, d]`` accumulator, so the ``[rows, F]``
  activation never exists in HBM and a tile's expert is streamed once.
- **Tiles nobody uses cost nothing.** The grid is sized for the worst
  routing; tiles past ``n_active`` do nothing: their index maps repeat the
  last fetched blocks, so no DMA is issued for them, and they read and write
  no row.

CPU CI runs the kernel under ``interpret=True`` against the plain grouped
FFN of ops/moe.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _scalar

# VMEM the weight blocks of one grid step may take: three blocks
# ``[d, f_block]``, two buffers each as stored plus one converted copy in
# the activations' dtype. The chip has 128 MiB; the kernel asks for what its
# plan needs and no more (``vmem_limit_bytes``).
_WEIGHT_VMEM = 40 << 20
# ... and what a call's resident rows may take beside them: ``x [N, d]`` and
# the float32 accumulator ``[N, d]``, one buffer each (1,365 rows at
# d = 4,096 in bfloat16; every launch the engine makes has at most 1,024 +
# its lanes). ``ops/moe.sorted_moe_ffn`` cuts a longer call into such pieces.
_ROWS_VMEM = 32 << 20
# Rows of the tile plan in VMEM at a time, whole tiles of 32 to 128: what one
# gather and one combine serve. Measured on a v5e at the six served shapes
# (PERF.md section 6, PR 53): 128 and 256 within 3 % of each other, 512 behind
# (a stage's gather is computed whole, whatever part of it holds rows).
_STAGE_ROWS = 128


def ffn_block(d: int, f: int, w_itemsize: int, x_itemsize: int) -> int:
    """Columns of F a grid step takes: all of F, or the largest multiple of
    128 dividing F whose three weight blocks fit ``_WEIGHT_VMEM``."""
    per_col = 3 * d * (2 * w_itemsize + x_itemsize)
    fit = _WEIGHT_VMEM // per_col
    if f <= fit:
        return f
    for cols in range(fit // 128 * 128, 0, -128):
        if f % cols == 0:
            return cols
    return f  # no aligned divisor: one block, the compiler says if it fits


def resident_rows(d: int, x_itemsize: int) -> int:
    """The most rows ``N`` of a call the kernel keeps in VMEM (``x`` and the
    float32 accumulator within ``_ROWS_VMEM``), a multiple of 8."""
    return _ROWS_VMEM // (d * (x_itemsize + 4)) // 8 * 8


def _ffn_kernel(
    layer_ref, tile_expert_ref, n_active_ref,  # prefetched scalars
    x_ref, row_ref, gate_ref, wg_ref, sg_ref, wu_ref, su_ref, wd_ref, sd_ref,
    o_ref,
    block_ref, rg_ref, xs_ref, ys_ref, acc_ref,
    *,
    tile: int,
    act: str,
):
    i, j = pl.program_id(0), pl.program_id(1)
    n_active = n_active_ref[0]
    active = i < n_active
    last = j == pl.num_programs(1) - 1
    stage, n = block_ref.shape
    per_stage = stage // tile
    at = pl.multiple_of((i % per_stage) * tile, tile)  # the tile's rows in its stage
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    dtype = x_ref.dtype

    def scale(ref):
        # the tile's expert's row of a block of experts' scales, in float32
        s = ref[...].astype(jnp.float32)
        mine = lax.broadcasted_iota(jnp.int32, s.shape, 0) == tile_expert_ref[i] % s.shape[0]
        return jnp.sum(jnp.where(mine, s, 0.0), axis=0, keepdims=True)

    @pl.when((i == 0) & (j == 0))
    def _start():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
        # the combine multiplies a stage's rows no tile has written by 0:
        # they must be finite (and later are an earlier stage's)
        ys_ref[...] = jnp.zeros(ys_ref.shape, ys_ref.dtype)

    @pl.when(active & (j == 0) & (i % per_stage == 0))
    def _gather():
        # block[r, n]: row r of this stage is one of token n's k choices; its
        # gate is that choice's. k compares of [stage, N]
        rows = (i * tile) + lax.broadcasted_iota(jnp.int32, (stage, n), 0)
        rows = jnp.where(rows < pl.num_programs(0) * tile, rows, -1)  # past the plan: nobody's (not a dropped choice's)
        hit = jnp.zeros((stage, n), bool)
        gate = jnp.zeros((stage, n), jnp.float32)
        for c in range(row_ref.shape[0]):
            here = row_ref[c : c + 1, :] == rows
            hit |= here
            gate = jnp.where(here, gate_ref[c : c + 1, :], gate)
        block = jnp.where(hit, 1.0, 0.0).astype(dtype)
        block_ref[...] = block
        rg_ref[...] = jnp.sum(gate, axis=1, keepdims=True)  # 0 for a group's padding rows
        xs_ref[...] = dot(block, x_ref[...]).astype(dtype)

    @pl.when(active & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    @pl.when(active)
    def _compute():
        x = xs_ref[pl.ds(at, tile), :]
        gate = dot(x, wg_ref[...].astype(dtype)) * scale(sg_ref)
        up = dot(x, wu_ref[...].astype(dtype)) * scale(su_ref)
        h = ((jax.nn.silu(gate) if act == "silu" else jnp.maximum(gate, 0.0)) * up).astype(dtype)
        acc_ref[...] += dot(h, wd_ref[...].astype(dtype))

    @pl.when(active & last)
    def _finish():
        y = acc_ref[...] * scale(sd_ref) * rg_ref[pl.ds(at, tile), :]
        ys_ref[pl.ds(at, tile), :] = y.astype(dtype)

    @pl.when(active & last & ((i % per_stage == per_stage - 1) | (i == n_active - 1)))
    def _combine():
        o_ref[...] += lax.dot_general(
            block_ref[...], ys_ref[...], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )


@functools.partial(jax.jit, static_argnames=("tile", "interpret", "act"))
def grouped_ffn(
    x: jnp.ndarray,  # [N, d]: the call's rows, as they come
    gates: jnp.ndarray,  # [N, k]: a token's gate for each of its choices
    row_of: jnp.ndarray,  # [N·k] int32: the tile plan's row of each assignment, ``tiles · tile`` for none
    tile_expert: jnp.ndarray,  # [tiles] int32
    n_active: jnp.ndarray,  # int32: tiles that hold rows
    w_gate: jnp.ndarray,  # [L, E, d, F] as stored (int8 or float)
    s_gate: jnp.ndarray,  # [L, E, 1, F] as stored (bfloat16 or float32)
    w_up: jnp.ndarray,
    s_up: jnp.ndarray,
    w_down: jnp.ndarray,  # [L, E, F, d]
    s_down: jnp.ndarray,  # [L, E, 1, d]
    layer: jnp.ndarray,  # int32
    *,
    tile: int,
    interpret: bool = False,
    act: str = "silu",
) -> jnp.ndarray:
    """``[N, d]`` float32: ``Σ_j gates[n, j] · FFN_e(x[n])`` over token n's
    choices that have a row, ``e`` the expert of the row's tile; each term is
    rounded to ``x``'s dtype before the float32 sum. ``act``: ``silu``
    (SwiGLU) or ``relu`` (ReGLU: ``relu(x Wg) * (x Wu)``)."""
    n, d = x.shape
    k = gates.shape[1]
    f = w_gate.shape[-1]
    n_tiles = tile_expert.shape[0]
    stage = max(tile, _STAGE_ROWS)
    fb = ffn_block(d, f, w_gate.dtype.itemsize, x.dtype.itemsize)
    nf = f // fb

    # tiles past the active ones repeat the last active tile's blocks (the
    # pipeline fetches a block only when its index changes)
    def row(i, na):
        return jnp.minimum(i, na[0] - 1)

    def col(i, j, na):
        return jnp.where(i < na[0], j, nf - 1)

    def up_map(i, j, layer, te, na):  # gate and up: [L, E, d, F]
        return layer[0], te[row(i, na)], 0, col(i, j, na)

    def down_map(i, j, layer, te, na):  # [L, E, F, d]
        return layer[0], te[row(i, na)], col(i, j, na), 0

    # the layer's scales ``[E, ·]``, sliced out here (a few KB; as the custom
    # call's operand a stack ``[L, E, 1, ·]`` is relaid or fetched whole, in
    # every layer): a block is ``e_block`` experts' rows, whole sublane tiles,
    # and the kernel picks its expert's
    n_experts = w_gate.shape[1]
    e_block = n_experts if n_experts % 16 else 16

    def layer_scales(s):
        return lax.dynamic_index_in_dim(s.squeeze(2), layer, 0, keepdims=False)

    def up_scale_map(i, j, layer, te, na):  # [E, F]
        return te[row(i, na)] // e_block, col(i, j, na)

    def down_scale_map(i, j, layer, te, na):  # [E, d]
        return te[row(i, na)] // e_block, 0

    resident = pl.BlockSpec(memory_space=pltpu.VMEM)  # whole, one buffer, for all the grid
    w_up_spec = pl.BlockSpec((None, None, d, fb), up_map)
    s_up_spec = pl.BlockSpec((e_block, fb), up_scale_map)
    weight_bytes = 3 * d * fb * (2 * w_gate.dtype.itemsize + x.dtype.itemsize)
    tile_bytes = tile * d * (2 * x.dtype.itemsize + 4) + 6 * tile * fb * 4
    rows_bytes = n * d * (x.dtype.itemsize + 4 + 4)  # x, the accumulator, a combine's product
    # a stage: its rows of x and of results and a gather's float32 product; its 0/1
    # block and the compares' row numbers, hits and gates; a gate a row, lane-padded
    stage_bytes = stage * (d * (2 * x.dtype.itemsize + 4) + n * (x.dtype.itemsize + 12) + 512)
    return pl.pallas_call(
        functools.partial(_ffn_kernel, tile=tile, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles, nf),
            in_specs=[
                resident, resident, resident,
                w_up_spec, s_up_spec, w_up_spec, s_up_spec,
                pl.BlockSpec((None, None, fb, d), down_map),
                pl.BlockSpec((e_block, d), down_scale_map),
            ],
            out_specs=resident,
            scratch_shapes=[
                pltpu.VMEM((stage, n), x.dtype),  # the stage's 0/1 block
                pltpu.VMEM((stage, 1), jnp.float32),  # its rows' gates
                pltpu.VMEM((stage, d), x.dtype),  # its rows of x
                pltpu.VMEM((stage, d), x.dtype),  # its rows' results
                pltpu.VMEM((tile, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=weight_bytes + tile_bytes + rows_bytes + stage_bytes + (8 << 20),
        ),
        interpret=interpret,
        name="moe_grouped_ffn",
    )(
        _scalar(layer), tile_expert.astype(jnp.int32), _scalar(n_active),
        x, row_of.reshape(n, k).T.astype(jnp.int32), gates.T.astype(jnp.float32),
        w_gate, layer_scales(s_gate), w_up, layer_scales(s_up), w_down, layer_scales(s_down),
    )
