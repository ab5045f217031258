"""Ring attention — sequence/context parallelism over the ICI ring.

Green-field (SURVEY.md §5.7): each device holds a sequence shard of Q/K/V;
K/V blocks rotate around the mesh axis with ``ppermute`` while every device
accumulates its queries' attention over each visiting block with an online
(flash-style) softmax — full attention over sequences ``sp``× longer than
one device could hold, with communication overlapping compute on the ring.

Causality is handled at block granularity with global positions derived from
``axis_index``: a KV block entirely in the future is skipped numerically by
the mask (uniform -inf rows are renormalized away by the online softmax).

All math accumulates in float32; inputs may be bf16.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import pcast
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _ring_local(q, k, v, *, axis_name: str, causal: bool, extra_vary: tuple = ()):
    """Per-device body. q/k/v: [B, T_loc, H|KV, hd] (already sharded)."""
    ax = lax.axis_index(axis_name)
    n = lax.psum(1, axis_name)
    b, t_loc, h, hd = q.shape
    kv_heads = k.shape[2]
    group = h // kv_heads
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    qf = q.astype(jnp.float32).reshape(b, t_loc, kv_heads, group, hd)
    q_pos = ax * t_loc + jnp.arange(t_loc)  # global positions of my queries

    # accumulators must carry the same varying-over-axis type as the data
    # they merge with inside the scan (new shard_map vma typing); with a
    # sharded batch axis the data varies over it too
    vary = (axis_name, *extra_vary)
    m0 = pcast(jnp.full((b, kv_heads, group, t_loc), NEG_INF, jnp.float32), vary, to='varying')
    l0 = pcast(jnp.zeros((b, kv_heads, group, t_loc), jnp.float32), vary, to='varying')
    o0 = pcast(jnp.zeros((b, t_loc, kv_heads, group, hd), jnp.float32), vary, to='varying')
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, carry):
        k_blk, v_blk, m, l, o = carry
        src = (ax - i) % n  # who this block originally belonged to
        kv_pos = src * t_loc + jnp.arange(t_loc)
        scores = (
            jnp.einsum("btkgd,bskd->bkgts", qf, k_blk.astype(jnp.float32)) * scale
        )  # [B,KV,G,T,S]
        if causal:
            mask = kv_pos[None, :] <= q_pos[:, None]  # [T, S]
            scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        blk_max = jnp.max(scores, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(scores - new_m[..., None])
        new_l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgts,bskd->btkgd", p, v_blk.astype(jnp.float32))
        new_o = o * corr.transpose(0, 3, 1, 2)[..., None] + pv
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return k_nxt, v_nxt, new_m, new_l, new_o

    _, _, m, l, o = lax.fori_loop(0, n, body, (k, v, m0, l0, o0))
    l = jnp.maximum(l, 1e-30)  # fully-masked rows (never for causal self-attn)
    out = o / l.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(b, t_loc, h, hd).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
    batch_axis: str | None = None,
) -> jnp.ndarray:
    """Full-sequence attention with inputs/outputs sequence-sharded over
    ``axis``. Shapes: q [B, T, H, hd], k/v [B, T, KV, hd] (global view).
    ``batch_axis`` additionally shards the batch dim (dp training meshes) —
    the ring then runs independently per batch shard."""
    spec = P(batch_axis, axis, None, None)
    extra = (batch_axis,) if batch_axis else ()
    fn = partial(_ring_local, axis_name=axis, causal=causal, extra_vary=extra)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
