"""Ulysses-style sequence parallelism: all-to-all head scattering.

The complement to ring attention (SURVEY.md §5.7): instead of rotating KV
blocks, two ``all_to_all`` collectives re-shard activations from
sequence-sharded ``[B, T/s, H, hd]`` to head-sharded ``[B, T, H/s, hd]``,
each device runs ordinary full attention over the whole sequence for its
own heads, and a reverse all-to-all restores sequence sharding. Cheaper
than a ring when ``s ≤ heads`` and the full sequence fits per device;
requires ``s`` to divide the KV-head count.
"""

from __future__ import annotations

from functools import partial

import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import attention_reference, causal_mask


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool):
    # local shapes: q [B, T/s, H, hd]; k/v [B, T/s, KV, hd]
    # all-to-all: gather sequence, scatter heads → [B, T, H/s, hd]
    q = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    k = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    v = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    mask = None
    if causal:
        t = q.shape[1]
        mask = jnp.broadcast_to(causal_mask(t), (q.shape[0], t, t))
    out = attention_reference(q, k, v, mask=mask)  # [B, T, H/s, hd]
    # reverse: gather heads, scatter sequence → [B, T/s, H, hd]
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
    batch_axis: str | None = None,
) -> jnp.ndarray:
    sp = mesh.shape[axis]
    if k.shape[2] % sp != 0:
        raise ValueError(f"sp={sp} must divide n_kv_heads={k.shape[2]} for Ulysses")
    spec = P(batch_axis, axis, None, None)
    fn = partial(_ulysses_local, axis_name=axis, causal=causal)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
