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
import math

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


# -- one decay a head, keys narrower than values (Gated DeltaNet) ---------------
#
# The state of such a layer is stored ``[n, B, dk, H·dv]``: a lane's tile is
# keys down the sublanes and every head's values side by side along the lanes
# (96 × 5760 float32 at 30 heads of 96 × 192: whole (8, 128) tiles, where
# ``[H, 96, 192]`` would pad each head's 192 lanes to 256). A head's columns
# start at ``h · dv``, which is a lane-tile boundary only every ``pack`` heads
# (2 at 192), so the kernel works on windows of ``pack`` heads: the window's
# per-head column vectors (k, β·k, q) are laid over its lanes by a select on
# the lane index, and the per-column operands (decay, v, the output) are rows
# ``[1, H·dv]`` as they come.

GDN_BLOCK_BYTES = 1 << 20  # a state block's float32 bytes, at most (in and out, two buffers each)


def gdn_supported(h: int, dk: int, dv: int) -> bool:
    """Whether a lane's ``[dk, H·dv]`` tile is whole (8, 128) tiles and the
    heads fill lane-aligned windows: what the kernel's blocks need."""
    return dk % 8 == 0 and (h * dv) % 128 == 0 and h % (128 // math.gcd(dv, 128)) == 0


def gdn_blocking(h: int, dk: int, dv: int) -> tuple[int, int]:
    """``(heads a grid step takes, heads a lane-aligned window holds)``."""
    if not gdn_supported(h, dk, dv):
        raise ValueError(f"a [{dk}, {h}x{dv}] state tile is not whole (8, 128) tiles of lane-aligned windows")
    pack = 128 // math.gcd(dv, 128)
    windows = h // pack
    per_window = dk * pack * dv * 4
    n = max(d for d in range(1, windows + 1) if windows % d == 0 and (d == 1 or d * per_window <= GDN_BLOCK_BYTES))
    return n * pack, pack


def _gdn_decode_kernel(idx_ref, q_ref, k_ref, kb_ref, v_ref, a_ref, s_ref, o_ref, s_out_ref, *, dv, pack):
    hb, dk = q_ref.shape
    width = pack * dv
    eye = lax.broadcasted_iota(jnp.int32, (dk, dk), 0) == lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    lane = lax.broadcasted_iota(jnp.int32, (dk, width), 1)

    def col(row):  # [1, dk] -> [dk, 1]
        return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (dk, dk)), 0.0), axis=1, keepdims=True)

    def cols(ref, h0):  # heads h0 .. h0 + pack as columns over their own value lanes
        out = jnp.broadcast_to(col(ref[h0 : h0 + 1, :]), (dk, width))
        for j in range(1, pack):
            out = jnp.where(lane >= j * dv, col(ref[h0 + j : h0 + j + 1, :]), out)
        return out

    for w in range(hb // pack):
        sl = slice(w * width, (w + 1) * width)
        s = s_ref[:, sl] * a_ref[:, sl]  # a S
        kept = jnp.sum(s * cols(k_ref, w * pack), axis=0, keepdims=True)  # kᵀ a S  [1, width]
        s = s + cols(kb_ref, w * pack) * (v_ref[:, sl] - kept)
        s_out_ref[:, sl] = s
        o_ref[:, sl] = jnp.sum(s * cols(q_ref, w * pack), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_decode(
    q: jnp.ndarray,  # [B, H, dk]
    k: jnp.ndarray,  # [B, H, dk]
    v: jnp.ndarray,  # [B, H, dv]
    g: jnp.ndarray,  # [B, H] log decay, one a head (0 for a lane that does not step)
    beta: jnp.ndarray,  # [B, H]   (0 for a lane that does not step)
    state: jnp.ndarray,  # [n, B, dk, H·dv] float32: the stacked state
    layer,  # int32 scalar: which layer of the stack
    *,
    interpret: bool = False,
):
    """``(o [B, H, dv] float32, state)`` with layer ``layer`` of the stack
    stepped by one token for every lane, in place (:func:`ops.kda.kda_step`
    on ``state`` viewed ``[B, H, dk, dv]`` is the ``jnp`` twin)."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    hb, pack = gdn_blocking(h, dk, dv)
    q, k, v = (x.astype(f32) for x in (q, k, v))
    kb = k * beta.astype(f32)[..., None]
    heads = lambda x: x.reshape(b, h // hb, hb, dk)  # noqa: E731  (a block is then whole in its last two dims)
    lanes = lambda x: x.reshape(b, 1, h * dv)  # noqa: E731
    a = jnp.repeat(jnp.exp(g.astype(f32)), dv, axis=-1)
    row = pl.BlockSpec((None, None, hb, dk), lambda ib, ih, idx: (ib, ih, 0, 0))
    row_v = pl.BlockSpec((None, 1, hb * dv), lambda ib, ih, idx: (ib, 0, ih))
    tile = pl.BlockSpec((None, None, dk, hb * dv), lambda ib, ih, idx: (idx[0], ib, 0, ih))
    o, state = pl.pallas_call(
        functools.partial(_gdn_decode_kernel, dv=dv, pack=pack),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // hb),
            in_specs=[row, row, row, row_v, row_v, tile],
            out_specs=[row_v, tile],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, 1, h * dv), f32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},  # the state stack (operand 6, the scalar counted) is output 1
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="gdn_decode",
    )(_scalar(layer), heads(q), heads(k), heads(kb), lanes(v), lanes(a), state)
    return o.reshape(b, h, dv), state
