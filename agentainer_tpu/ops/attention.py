"""Attention ops — XLA reference implementations + TPU kernel dispatch.

Green-field TPU-first design (the reference has no model code). The XLA
path is einsum-shaped so the compiler tiles it onto the MXU; softmax runs in
float32. GQA is handled by grouping query heads over shared KV heads rather
than materializing repeated K/V (saves HBM bandwidth, the usual bottleneck).

Which implementation serves a call is decided in ONE place,
``plan_cache_attention`` (arena attention, the serving hot path) and
``pallas_available`` (the predicate it shares with ``flash_attention``):
the Pallas blockwise kernels (ops/pallas_attention.py) on a TPU backend
when shapes allow, the XLA reference elsewhere — and every decision carries
its reason, which the engine logs and exports, so a step that runs the
reference on a chip says so instead of hiding it.
"""

from __future__ import annotations

import os
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _group_query_heads(q: jnp.ndarray, n_kv_heads: int) -> jnp.ndarray:
    """[B, T, H, hd] → [B, T, KV, G, hd] where H = KV * G."""
    b, t, h, hd = q.shape
    assert h % n_kv_heads == 0, (h, n_kv_heads)
    return q.reshape(b, t, n_kv_heads, h // n_kv_heads, hd)


def attention_reference(
    q: jnp.ndarray,  # [B, Tq, H, hd]
    k: jnp.ndarray,  # [B, Tk, KV, hd]
    v: jnp.ndarray,  # [B, Tk, KV, hd]
    mask: jnp.ndarray | None = None,  # broadcastable to [B, Tq, Tk]
) -> jnp.ndarray:
    """Pure-XLA scaled dot-product attention with GQA. Returns [B, Tq, H, hd]."""
    n_kv = k.shape[2]
    qg = _group_query_heads(q, n_kv)  # [B,Tq,KV,G,hd]
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], dtype=jnp.float32))
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * scale  # [B,KV,G,Tq,Tk]
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    b, tq, kv, g, hd = out.shape
    return out.reshape(b, tq, kv * g, hd).astype(q.dtype)


def causal_mask(t: int) -> jnp.ndarray:
    """[1, T, T] lower-triangular mask."""
    return jnp.tril(jnp.ones((t, t), dtype=bool))[None]


def cache_mask(q_positions: jnp.ndarray, cache_len: int) -> jnp.ndarray:
    """Mask for attending over a KV cache of static size ``cache_len``.

    A query at position p may see cache slot j iff j <= p — unwritten slots
    have higher indices than any live position, so padding never leaks.
    q_positions: [B, Tq] → mask [B, Tq, cache_len].
    """
    slots = jnp.arange(cache_len)[None, None, :]
    return slots <= q_positions[:, :, None]


def ring_mask(q_positions: jnp.ndarray, ring: int, window: int) -> jnp.ndarray:
    """Mask for attending over a window layer's RING of ``ring`` rows, where
    the row of position p is ``p mod ring`` (models/llama.WindowKVCache). Row
    r holds, for a query at position i, the position ``i - ((i - r) mod
    ring)``: the newest at or before i that lands there (nothing newer was
    written to it: ``models/llama.ring_rows``). The query sees it iff that
    position is within the last ``window`` and not before position 0.
    q_positions ``[B, Tq]`` → mask ``[B, Tq, ring]``."""
    back = (q_positions[:, :, None] - jnp.arange(ring)[None, None, :]) % ring
    return (back < window) & (back <= q_positions[:, :, None])


def pallas_available(n_heads: int, n_kv_heads: int, head_dim: int) -> tuple[bool, str]:
    """Whether the compiled Pallas kernels can serve these head shapes in
    this process, and the reason either way."""
    if os.environ.get("AGENTAINER_NO_PALLAS"):
        return False, "AGENTAINER_NO_PALLAS is set"
    backend = jax.default_backend()
    if backend != "tpu":
        return False, f"backend is {backend}; the Mosaic kernels need tpu"
    from .pallas_attention import kernel_supported

    if not kernel_supported(n_heads, n_kv_heads, head_dim):
        return False, (
            f"heads {n_heads}/{n_kv_heads} x {head_dim}: the kernels need "
            "head_dim % 128 == 0, whole GQA groups and 1, 2, 4 or 8k KV heads"
        )
    return True, "tpu backend, lane-aligned heads"


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray | None = None,
    causal: bool = False,
) -> jnp.ndarray:
    """Dispatch: Pallas blockwise kernel on TPU (prefill-shaped inputs),
    XLA reference elsewhere."""
    if causal and mask is None and pallas_available(q.shape[2], k.shape[2], q.shape[3])[0]:
        from .pallas_attention import flash_attention_tpu

        return flash_attention_tpu(q, k, v)
    if causal and mask is None:
        mask = causal_mask(q.shape[1])
    return attention_reference(q, k, v, mask=mask)


# -- paged KV (block-table) variants ------------------------------------
#
# The paged arena replaces per-sequence arena rows with a global pool of
# fixed-size pages ``[P, KV, page_size, hd]`` plus a per-lane block table
# ``[B, n_blocks]`` of physical page ids (vLLM idiom). KV heads sit OUTSIDE
# the page so one (page, kv-head) block is a contiguous, tile-aligned
# ``[page_size, hd]`` slab — the shape Mosaic can DMA pool→VMEM (the last
# two block dims must be multiples of (8, 128); with the head axis inside
# the page the squeezed axis was second-to-last and the chip's compiler
# refused the fused kernels). The ops below are the single definition of
# the page addressing scheme: logical position ``p`` of lane ``b``, head
# ``h`` lives at ``(block_table[b, p // page_size], h, p % page_size)``.
# Attention gathers a lane's pages into a contiguous arena VIEW and then
# runs the exact same math as the dense path — which is what makes greedy
# decode bit-exact across the two layouts, and lets CPU CI run the
# identical code (the gather lowers to plain XLA).


def pages_to_rows(pages: jnp.ndarray) -> jnp.ndarray:
    """``[..., n, KV, page_size, hd]`` pages → ``[..., n * page_size, KV,
    hd]`` rows, the dense arena's (and the snapshot blob's) layout."""
    *lead, n, kv, ps, hd = pages.shape
    nd = len(lead)
    perm = (*range(nd), nd, nd + 2, nd + 1, nd + 3)
    return pages.transpose(perm).reshape(*lead, n * ps, kv, hd)


def rows_to_pages(rows: jnp.ndarray, page_size: int) -> jnp.ndarray:
    """Inverse of :func:`pages_to_rows`: ``[..., n * page_size, KV, hd]``
    → ``[..., n, KV, page_size, hd]``."""
    *lead, s, kv, hd = rows.shape
    nd = len(lead)
    perm = (*range(nd), nd, nd + 2, nd + 1, nd + 3)
    return rows.reshape(*lead, s // page_size, page_size, kv, hd).transpose(perm)


def gather_pages(
    pool_k: jnp.ndarray,  # [P, KV, page_size, hd]
    pool_v: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, n_blocks] int32 physical page ids
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Materialize each lane's logical KV arena from its pages:
    ``[B, n_blocks * page_size, KV, hd]`` — laid out exactly like a dense
    arena row, so every downstream attention path applies unchanged.
    Under a tp mesh (pool sharded on the KV-head axis) the gather is
    local per shard: the page index never crosses the head split, so no
    collective is needed (pinned by tests/test_paged_hlo.py)."""
    return pages_to_rows(pool_k[block_table]), pages_to_rows(pool_v[block_table])


def scatter_paged_kv(
    pool_k: jnp.ndarray,  # [P, KV, page_size, hd], or [L, P, ...] with ``layer``
    pool_v: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, T, KV, hd]
    v_new: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, n_blocks]
    positions: jnp.ndarray,  # [B, T] int32
    layer=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write this step's K/V through the block table into pool pages — of
    layer ``layer`` of the stacked pool when one is given.

    Positions past the logical arena (bucket padding that the dense path's
    out-of-range scatter silently DROPS) clamp to the last logical slot —
    the per-lane scratch row — so they land somewhere no live query ever
    attends instead of wrapping into a live page."""
    ps = pool_k.shape[-2]
    s = block_table.shape[1] * ps
    cpos = jnp.minimum(positions, s - 1)
    b_idx = jnp.arange(positions.shape[0])[:, None]
    pages = block_table[b_idx, cpos // ps]
    offs = cpos % ps
    # advanced indices split by the head slice: the indexed dims lead, so
    # the update shape is [B, T, KV, hd] — exactly k_new's
    idx = (pages, slice(None), offs) if layer is None else (layer, pages, slice(None), offs)
    return pool_k.at[idx].set(k_new), pool_v.at[idx].set(v_new)


class CacheAttention(NamedTuple):
    """Arena attention as an engine's compiled steps trace it, chosen once
    at build. ``fn(q, ck, cv, positions, block_table, layer, slot)`` is the
    only attention those steps call — there is no second dispatch behind
    it — so ``prefill`` (what a T > 1 call runs) and ``decode`` (T == 1)
    name what is in the compiled programs, and ``reason`` says why.

    ``ck``/``cv`` are the STACKED arena ``[L, B, S, KV, hd]`` (or page pool
    ``[L, P, KV, page_size, hd]``) as the layer scan carries it, ``layer``
    the index of the layer to read and ``slot`` (or None) the arena row the
    batch starts at. ``arena`` says what the implementation does with
    them: ``"stack+layer"`` reads layer ``layer`` where it lies (the
    kernels address it in their index maps), ``"layer_slice"`` takes
    ``stack[layer]`` out first — a copy of the layer in every layer-step."""

    fn: Callable
    prefill: str
    decode: str
    reason: str
    arena: str = "layer_slice"

    def describe(self) -> dict:
        return {
            "prefill": self.prefill,
            "decode": self.decode,
            "reason": self.reason,
            "arena": self.arena,
        }


def layer_slice(ck, cv, layer, slot=None, rows: int = 0):
    """``stack[layer]`` of both stacks (and of that, the ``rows`` arena rows
    from ``slot`` on): what an implementation that cannot address the stack
    by layer reads instead."""
    ck = lax.dynamic_index_in_dim(ck, layer, 0, keepdims=False)
    cv = lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False)
    if slot is not None:
        ck = lax.dynamic_slice_in_dim(ck, slot, rows, 0)
        cv = lax.dynamic_slice_in_dim(cv, slot, rows, 0)
    return ck, cv


def _reference_dense(q, ck, cv, positions, block_table, layer, slot, window: int = 0):
    ck, cv = layer_slice(ck, cv, layer, slot, q.shape[0])
    mask = ring_mask(positions, ck.shape[1], window) if window else cache_mask(positions, ck.shape[1])
    return attention_reference(q, ck, cv, mask=mask)


def _reference_paged(q, pool_k, pool_v, positions, block_table, layer, slot):
    ck, cv = gather_pages(*layer_slice(pool_k, pool_v, layer), block_table)
    return attention_reference(q, ck, cv, mask=cache_mask(positions, ck.shape[1]))


def pallas_dense(q, ck, cv, positions, block_table, layer, slot, interpret: bool = False, window: int = 0):
    """The dense flash kernels by call shape: one token per sequence runs
    ``flash_decode``, anything longer ``flash_prefill``. Both read layer
    ``layer`` (rows from ``slot``) of the stacks in the stored layout.
    ``window``: the stack is a window layer's ring and a row sees its last
    ``window`` positions."""
    from .pallas_attention import flash_decode, flash_prefill

    slot = 0 if slot is None else slot
    kw = {"window": window} if window else {}
    if q.shape[1] == 1:
        out = flash_decode(
            q[:, 0], ck, cv, positions[:, 0], layer, slot, interpret=interpret, **kw
        )
        return out[:, None]
    return flash_prefill(q, ck, cv, positions, layer, slot, interpret=interpret, **kw)


def pallas_dense_layer(q, ck, cv, positions, interpret: bool = False):
    """The same kernels over ONE layer's own ``[B, S, KV, hd]`` arrays (a
    gathered pool, a shard under ``shard_map``): a one-layer stack."""
    return pallas_dense(q, ck[None], cv[None], positions, None, 0, None, interpret)


def _pallas_paged_gather(q, pool_k, pool_v, positions, block_table, layer, slot):
    ck, cv = gather_pages(*layer_slice(pool_k, pool_v, layer), block_table)
    return pallas_dense_layer(q, ck, cv, positions)


def _pallas_paged_fused(q, pool_k, pool_v, positions, block_table, layer, slot):
    from .pallas_attention import fused_paged_flash_decode, fused_paged_flash_prefill

    pool_k, pool_v = layer_slice(pool_k, pool_v, layer)
    if q.shape[1] == 1:
        out = fused_paged_flash_decode(
            q[:, 0], pool_k, pool_v, block_table, positions[:, 0]
        )
        return out[:, None]
    return fused_paged_flash_prefill(q, pool_k, pool_v, block_table, positions)


def plan_cache_attention(
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    *,
    page_size: int = 0,
    use_pallas: bool = True,
) -> CacheAttention:
    """Choose the arena attention for these head shapes in this process:
    row t sees slot j iff ``j <= positions[b, t]`` (ragged cached prefill
    and T == 1 decode alike). ``page_size > 0`` means the cache is a page
    pool ``[L, P, KV, page_size, hd]`` read through a block table.

    On a TPU backend the Pallas flash kernels build the mask in-register —
    dense (reading layer ``layer`` of the stacked arena where it lies:
    ``arena: stack+layer``), or fused with the block-table walk for a pool
    (the gather + dense-kernel path stays as the reference the fused
    kernels are A/B'd against; ``AGENTAINER_PAGED_GATHER=1`` forces it;
    both take the layer's pool out of the stack first:
    ``arena: layer_slice``). Elsewhere, and for
    shapes the kernels cannot take, the XLA reference materializes
    ``cache_mask``. Callers running under GSPMD sharding pass
    ``use_pallas=False`` — XLA cannot auto-partition a pallas_call, while
    it shards the einsum path along the head axis for free."""
    paged = page_size > 0
    if use_pallas:
        ok, why = pallas_available(n_heads, n_kv_heads, head_dim)
    else:
        ok, why = False, "caller runs under GSPMD sharding (pallas_call cannot be partitioned)"
    if not ok:
        if paged:
            name = "xla:gather_pages+attention_reference"
            return CacheAttention(_reference_paged, name, name, why)
        name = "xla:attention_reference"
        return CacheAttention(_reference_dense, name, name, why)
    if not paged:
        return CacheAttention(
            pallas_dense, "pallas:flash_prefill", "pallas:flash_decode", why, "stack+layer"
        )
    if os.environ.get("AGENTAINER_PAGED_GATHER"):
        gather_why = "AGENTAINER_PAGED_GATHER is set"
    elif page_size % 8:
        gather_why = f"page_size {page_size} is not sublane-aligned (multiple of 8)"
    else:
        return CacheAttention(
            _pallas_paged_fused,
            "pallas:fused_paged_flash_prefill",
            "pallas:fused_paged_flash_decode",
            why,
        )
    return CacheAttention(
        _pallas_paged_gather,
        "pallas:gather_pages+flash_prefill",
        "pallas:gather_pages+flash_decode",
        gather_why,
    )
