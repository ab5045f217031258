"""Pallas TPU grouped SwiGLU FFN over the stacked expert weights.

The MoE FFN of a call with many rows (ops/moe.py decides which) sorts its
(token, chosen expert) assignments by expert into a tile-aligned row buffer
and runs every expert over its own rows only. This kernel is that grouped
matmul, all three matrices of it:

- **One row tile, one expert.** ``tile_expert[i]`` (a prefetched scalar
  array) names the expert of row tile ``i``; an expert with more rows than a
  tile holds has several tiles in a row, one with none has none. Group sizes
  are therefore free: any skew, all rows on one expert included.
- **The weights stay int8 and stay where they lie.** The operands are the
  stacked ``[L, E, d, F]`` tensors as the engine stores them; the layer is a
  prefetched scalar and the index maps address ``(layer, expert, :, f
  block)``, so nothing of expert size is sliced, copied, relaid or
  dequantised in HBM. An int8 block is converted in VMEM; its
  per-output-channel scale multiplies the float32 result columns (gate, up)
  or the finished accumulator (down), which is exact where scaling the
  weights first rounds them to bf16.
- **One pass.** Grid ``(row tiles, F blocks)``: for an F block the kernel
  computes ``silu(x Wg) * (x Wu)`` and adds its product with the matching
  rows of ``Wd`` to a float32 ``[tile, d]`` accumulator, so the ``[rows, F]``
  activation never exists in HBM and a tile's expert is streamed once.
- **Tiles nobody uses cost nothing.** The buffer is sized for the worst
  routing; tiles past ``n_active`` skip their matmuls, their index maps
  repeat the last fetched blocks, so no DMA is issued for them, and they
  write zeros.

CPU CI runs the kernel under ``interpret=True`` against the plain grouped
FFN of ops/moe.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _scalar

# VMEM the weight blocks of one grid step may take: three blocks
# ``[d, f_block]``, two buffers each as stored plus one converted copy in
# the activations' dtype. The chip has 128 MiB; the kernel asks for what its
# plan needs and no more (``vmem_limit_bytes``).
_WEIGHT_VMEM = 40 << 20


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


def _ffn_kernel(
    layer_ref, tile_expert_ref, n_active_ref,  # prefetched scalars
    x_ref, rg_ref, wg_ref, sg_ref, wu_ref, su_ref, wd_ref, sd_ref,
    o_ref,
    acc_ref,
    *,
    act: str = "silu",
):
    i, j = pl.program_id(0), pl.program_id(1)
    active = i < n_active_ref[0]

    @pl.when(active & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    @pl.when(active)
    def _compute():
        x = x_ref[...]
        dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
        gate = dot(x, wg_ref[...].astype(x.dtype)) * sg_ref[...]
        up = dot(x, wu_ref[...].astype(x.dtype)) * su_ref[...]
        h = ((jax.nn.silu(gate) if act == "silu" else jnp.maximum(gate, 0.0)) * up).astype(x.dtype)
        acc_ref[...] += dot(h, wd_ref[...].astype(x.dtype))

    last = j == pl.num_programs(1) - 1

    @pl.when(active & last)
    def _finish():
        o_ref[...] = (acc_ref[...] * sd_ref[...] * rg_ref[...]).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(active) & last)
    def _no_rows():  # the combine multiplies these rows by 0: they must be finite
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret", "act"))
def grouped_ffn(
    x_rows: jnp.ndarray,  # [M, d]: rows sorted by expert, groups tile-aligned
    row_gate: jnp.ndarray,  # [M, 1] float32: a row's gate, 0 for padding
    tile_expert: jnp.ndarray,  # [M // tile] int32
    n_active: jnp.ndarray,  # int32: tiles that hold rows
    w_gate: jnp.ndarray,  # [L, E, d, F] as stored (int8 or float)
    s_gate: jnp.ndarray,  # [L, E, 1, F] float32
    w_up: jnp.ndarray,
    s_up: jnp.ndarray,
    w_down: jnp.ndarray,  # [L, E, F, d]
    s_down: jnp.ndarray,  # [L, E, 1, d] float32
    layer: jnp.ndarray,  # int32
    *,
    tile: int,
    interpret: bool = False,
    act: str = "silu",
) -> jnp.ndarray:
    """``[M, d]``: row r is ``gate_r · FFN_e(x_r)`` for the expert ``e`` of
    r's tile; rows of tiles past ``n_active`` are 0. ``act``: ``silu``
    (SwiGLU) or ``relu`` (ReGLU: ``relu(x Wg) * (x Wu)``)."""
    m, d = x_rows.shape
    f = w_gate.shape[-1]
    n_tiles = m // tile
    fb = ffn_block(d, f, w_gate.dtype.itemsize, x_rows.dtype.itemsize)
    nf = f // fb

    # tiles past the active ones repeat the last active tile's blocks (the
    # pipeline fetches a block only when its index changes)
    def row(i, na):
        return jnp.minimum(i, na[0] - 1)

    def col(i, j, na):
        return jnp.where(i < na[0], j, nf - 1)

    def x_map(i, j, layer, te, na):
        return row(i, na), 0

    def up_map(i, j, layer, te, na):  # gate and up: [L, E, d, F], scales [L, E, 1, F]
        return layer[0], te[row(i, na)], 0, col(i, j, na)

    def down_map(i, j, layer, te, na):  # [L, E, F, d]
        return layer[0], te[row(i, na)], col(i, j, na), 0

    def down_scale_map(i, j, layer, te, na):
        return layer[0], te[row(i, na)], 0, 0

    w_up_spec = pl.BlockSpec((None, None, d, fb), up_map)
    s_up_spec = pl.BlockSpec((None, None, 1, fb), up_map)
    weight_bytes = 3 * d * fb * (2 * w_gate.dtype.itemsize + x_rows.dtype.itemsize)
    tile_bytes = tile * d * (4 * x_rows.dtype.itemsize + 4) + 6 * tile * fb * 4
    return pl.pallas_call(
        _ffn_kernel if act == "silu" else functools.partial(_ffn_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles, nf),
            in_specs=[
                pl.BlockSpec((tile, d), x_map),
                pl.BlockSpec((tile, 1), x_map),
                w_up_spec, s_up_spec, w_up_spec, s_up_spec,
                pl.BlockSpec((None, None, fb, d), down_map),
                pl.BlockSpec((None, None, 1, d), down_scale_map),
            ],
            out_specs=pl.BlockSpec((tile, d), lambda i, j, layer, te, na: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), x_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=weight_bytes + tile_bytes + (8 << 20),
        ),
        interpret=interpret,
        name="moe_grouped_ffn",
    )(
        _scalar(layer), tile_expert.astype(jnp.int32), _scalar(n_active),
        x_rows, row_gate, w_gate, s_gate, w_up, s_up, w_down, s_down,
    )
