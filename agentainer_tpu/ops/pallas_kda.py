"""Pallas TPU kernel: the KDA decode step, one fused read-update-write of
every lane's recurrent state where it lies.

A decode step of a KDA layer moves nothing but state: per lane and head a
float32 ``[dk, dv]`` tile (64 KB at 128 × 128) is read, decayed, corrected by
the delta rule's rank-1 update, read out against the query and written back
(``ops/kda.py`` has the mathematics and :func:`ops.kda.kda_step`, the ``jnp``
twin this kernel is tested against). At 64 lanes × 32 heads that is 268 MB a
layer both ways and there is no arithmetic to hide it behind, so the kernel
does exactly one pass:

- **The state stays where it lies.** The operand is the STACK ``[n_kda, B,
  H, dk, dv]`` the layer scan carries; the layer's index is a prefetched
  scalar, the index maps address ``(layer, lane, head block)`` and the
  output aliases the input, so only the visited tiles are rewritten and
  nothing of a layer's size is sliced out, copied or written back.
- **A masked lane costs a pass and changes nothing**: with ``g = 0`` and
  ``β = 0`` the tile is written back as read (``S·1 + 0``).
- Row vectors (q, k, β·k, the decay) are needed down the sublanes of the
  tile; a ``[1, dk]`` row becomes a ``[dk, 1]`` column by a masked lane
  reduction over the ``dk × dk`` identity, which every Mosaic version lowers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _scalar

HEAD_BLOCK = 8  # heads a grid step takes: whole sublane tiles of the row operands


def _kda_decode_kernel(idx_ref, q_ref, k_ref, kb_ref, v_ref, g_ref, s_ref, o_ref, s_out_ref):
    hb, dk = q_ref.shape
    eye = lax.broadcasted_iota(jnp.int32, (dk, dk), 0) == lax.broadcasted_iota(jnp.int32, (dk, dk), 1)

    def col(row):  # [1, dk] -> [dk, 1]
        return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (dk, dk)), 0.0), axis=1, keepdims=True)

    for h in range(hb):
        a = jnp.exp(g_ref[h : h + 1, :])
        k = k_ref[h : h + 1, :]
        s = s_ref[h] * col(a)  # diag(a) S
        kept = jnp.sum(s_ref[h] * col(k * a), axis=0, keepdims=True)  # kᵀ diag(a) S  [1, dv]
        s = s + col(kb_ref[h : h + 1, :]) * (v_ref[h : h + 1, :] - kept)
        s_out_ref[h] = s
        o_ref[h : h + 1, :] = jnp.sum(s * col(q_ref[h : h + 1, :]), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode(
    q: jnp.ndarray,  # [B, H, dk]
    k: jnp.ndarray,  # [B, H, dk]
    v: jnp.ndarray,  # [B, H, dv]
    g: jnp.ndarray,  # [B, H, dk] log decay (0 for a lane that does not step)
    beta: jnp.ndarray,  # [B, H]   (0 for a lane that does not step)
    state: jnp.ndarray,  # [n_kda, B, H, dk, dv] float32: the stacked state
    layer,  # int32 scalar: which layer of the stack
    *,
    interpret: bool = False,
):
    """``(o [B, H, dv] float32, state)`` with layer ``layer`` of the stack
    stepped by one token for every lane, in place."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    q, k, v, g = (x.astype(f32) for x in (q, k, v, g))
    kb = k * beta.astype(f32)[..., None]
    hb = HEAD_BLOCK if h % HEAD_BLOCK == 0 else h
    row = pl.BlockSpec((None, hb, dk), lambda ib, ih, idx: (ib, ih, 0))
    row_v = pl.BlockSpec((None, hb, dv), lambda ib, ih, idx: (ib, ih, 0))
    tile = pl.BlockSpec((None, None, hb, dk, dv), lambda ib, ih, idx: (idx[0], ib, ih, 0, 0))
    o, state = pl.pallas_call(
        _kda_decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // hb),
            in_specs=[row, row, row, row_v, row, tile],
            out_specs=[row_v, tile],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), f32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},  # the state stack (operand 6, the scalar counted) is output 1
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="kda_decode",
    )(_scalar(layer), q, k, kb, v, g, state)
    return o, state
