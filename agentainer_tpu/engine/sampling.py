"""Token sampling: greedy / temperature / top-k / top-p.

Static-shape, jit-safe (no data-dependent branches): filters are applied as
masks over the full vocab so the same compiled sampler serves every request
in a continuous batch with per-request settings.

Cost model: the exact path pays ONE descending [B, V] sort shared by the
top-k threshold and the top-p cumulative (the two filters used to sort
twice; masking the already-sorted row with the top-k threshold produces
exactly ``jnp.sort(filtered)[::-1]``, so the second sort was pure waste).
The opt-in ``approx_topk`` path replaces the sort entirely with
``jax.lax.approx_max_k`` over a fixed ``APPROX_SEG``-wide segment.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# Segment width for the opt-in `approx_topk` sampler path: both the top-k
# threshold and the top-p cumulative operate over the approx_max_k segment
# instead of the full vocab. 128 covers every practical top_k setting; lanes
# asking for top_k > APPROX_SEG are clamped to the segment (a strictly
# stronger filter), and top-p renormalizes over the segment's mass (tail
# mass outside the segment counts as zero, so the cutoff lands at or above
# the exact one — again strictly stronger). Divergence is bounded by the
# probability mass outside the top APPROX_SEG candidates, which for peaked
# LLM logits is negligible; the parity tests pin this.
APPROX_SEG = 128


class SamplingParams(NamedTuple):
    temperature: float = 0.0  # 0 → greedy
    top_k: int = 0  # 0 → disabled
    top_p: float = 1.0  # 1 → disabled


def sample(
    logits: jnp.ndarray,  # [B, V] float32
    key: jax.Array,
    temperature: jnp.ndarray | float = 0.0,  # scalar or [B]
    top_k: int = 0,
    top_p: float = 1.0,
) -> jnp.ndarray:
    """Returns token ids [B]. temperature may be per-request ([B]) so one
    batch can mix greedy and sampled requests."""
    temperature = jnp.asarray(temperature, dtype=jnp.float32)
    if temperature.ndim == 0:
        temperature = jnp.broadcast_to(temperature, (logits.shape[0],))

    greedy = jnp.argmax(logits, axis=-1)

    filtered = logits
    desc = None
    if top_k > 0 or top_p < 1.0:
        # one shared descending sort serves both filters
        desc = jnp.sort(logits, axis=-1)[:, ::-1]
    if top_k > 0:
        # clamp to the vocab: top_k >= V must mean "disabled" (every token
        # kept) — an unclamped k would index out of the row
        k = min(int(top_k), logits.shape[-1])
        kth = desc[:, k - 1][:, None]
        filtered = jnp.where(filtered < kth, NEG_INF, filtered)
        # masking the SORTED row below kth is elementwise identical to
        # jnp.sort(filtered)[::-1]: the kept prefix is untouched and the
        # dropped suffix becomes NEG_INF, in place
        desc = jnp.where(desc < kth, NEG_INF, desc)
    if top_p < 1.0:
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)  # [B]
        cutoff_logit = jnp.take_along_axis(desc, cutoff_idx[:, None], axis=-1)
        filtered = jnp.where(filtered < cutoff_logit, NEG_INF, filtered)

    scaled = filtered / jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def sample_step(
    logits: jnp.ndarray,  # [B, V] float32
    key: jax.Array,
    temperature: jnp.ndarray,  # [B] float32, 0 → greedy
    top_k: jnp.ndarray,  # [B] int32, <= 0 → disabled
    top_p: jnp.ndarray,  # [B] float32, >= 1 → disabled
    *,
    greedy_cond: bool = True,
    approx_topk: bool = False,
) -> jnp.ndarray:
    """The fused-loop sampler: every filter is a per-lane ARRAY so a single
    compiled while_loop body serves a batch mixing greedy, temperature,
    top-k, and top-p lanes.

    Bit-exact with :func:`sample`: when a lane's filter is disabled the
    ``where`` keeps the original logit row untouched (not a recomputed
    copy), and when a filter is active the threshold math is the same
    sort-based mask — so `sample(logits, key, t, k, p)` and
    `sample_step(logits, key, [t]*B, [k]*B, [p]*B)` draw identical tokens
    from identical keys.

    The all-greedy batch (the dominant agentic case, and every batch whose
    sampled lanes are parked) takes a ``lax.cond`` fast path: per-lane
    filters as ARRAYS mean the sort/softmax/threefry below can't be
    constant-folded away like scalar ``sample``'s can, and paying a full
    [B, V] sort plus a categorical draw per decode step to then discard
    them lane-by-lane roughly doubles the per-step wall. Greedy ignores
    the filters anyway (argmax is invariant under top-k/top-p masks), so
    the branch is exact, not approximate.

    ``greedy_cond=False`` (static) drops the ``lax.cond`` and always runs
    the where-merged pipeline — bit-identical output, just no fast path.
    MESHED engines must pass it: this jaxlib's XLA:CPU partitioner
    segfaults compiling a batch-wide conditional over sharded operands
    (a tp engine's warmup died inside the cond), and on a real mesh the sort
    pipeline is cheap relative to the sharded forward anyway.

    ``approx_topk=True`` (static) swaps the full-vocab sort for a
    ``jax.lax.approx_max_k`` segment of width :data:`APPROX_SEG`: the
    top-k threshold and the top-p cumulative both come from the segment.
    NOT bit-exact for sampled lanes (see APPROX_SEG notes) — greedy lanes
    are unaffected (argmax never touches the filters). Opt-in via the
    engine's `approx_topk` flag; exact remains the default.
    """
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _finish(filtered):
        scaled = filtered / jnp.maximum(temperature, 1e-6)[:, None]
        sampled = jax.random.categorical(key, scaled, axis=-1)
        return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)

    def _exact(_):
        # ONE shared descending sort: the top-k threshold reads it at
        # [k_eff - 1], and masking it below kth reproduces
        # jnp.sort(filtered)[::-1] for the top-p cumulative (the kept
        # prefix is untouched, the dropped suffix becomes NEG_INF).
        desc = jnp.sort(logits, axis=-1)[:, ::-1]
        k_eff = jnp.clip(top_k.astype(jnp.int32), 1, V)
        kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=-1)  # [B, 1]
        k_on = (top_k > 0)[:, None]
        filtered = jnp.where(k_on & (logits < kth), NEG_INF, logits)
        sorted_logits = jnp.where(k_on & (desc < kth), NEG_INF, desc)

        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1)  # [B]
        cutoff_logit = jnp.take_along_axis(
            sorted_logits, cutoff_idx[:, None], axis=-1
        )
        p_on = (top_p < 1.0)[:, None]
        filtered = jnp.where(p_on & (filtered < cutoff_logit), NEG_INF, filtered)
        return _finish(filtered)

    def _approx(_):
        seg = min(V, APPROX_SEG)
        # values arrive sorted descending (aggregate_to_topk=True default);
        # on non-TPU backends approx_max_k lowers to exact top_k, so the
        # only divergence source is the segment truncation itself.
        vals, _ = jax.lax.approx_max_k(logits, k=seg)
        k_eff = jnp.clip(top_k.astype(jnp.int32), 1, seg)
        kth = jnp.take_along_axis(vals, (k_eff - 1)[:, None], axis=-1)  # [B, 1]
        k_on = (top_k > 0)[:, None]
        filtered = jnp.where(k_on & (logits < kth), NEG_INF, logits)
        seg_sorted = jnp.where(k_on & (vals < kth), NEG_INF, vals)

        # top-p over the segment's renormalized mass; the cutoff index is
        # clamped into the segment so a flat distribution (cum never
        # reaching top_p inside the segment) degrades to keep-the-segment
        # rather than reading past it
        probs = jax.nn.softmax(seg_sorted, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.minimum(
            jnp.sum(cum < top_p[:, None], axis=-1), seg - 1
        )  # [B]
        cutoff_logit = jnp.take_along_axis(
            seg_sorted, cutoff_idx[:, None], axis=-1
        )
        p_on = (top_p < 1.0)[:, None]
        filtered = jnp.where(p_on & (filtered < cutoff_logit), NEG_INF, filtered)
        return _finish(filtered)

    _sampled = _approx if approx_topk else _exact

    if not greedy_cond:
        return _sampled(None)
    return jax.lax.cond(jnp.all(temperature <= 0.0), lambda _: greedy, _sampled, None)
