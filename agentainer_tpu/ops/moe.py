"""The MoE FFN of a call with many rows: sort by expert, grouped matmul.

``models/llama._moe_mlp`` sends every row through every expert and masks at
the combine. That is free while the call is weight-bound — the experts have
to be streamed once whatever the rows do — and E/k times the work once the
rows' FLOPs outlast the stream. :func:`sorted_from_rows` computes where
that happens on the device at hand; from there on the FFN computes only the
(token, chosen expert) pairs:

1. the call's ``N·k`` assignments are sorted by expert;
2. each expert's rows get places in a plan of row tiles whose groups start
   on a tile, so a tile belongs to one expert (:func:`route`); the plan is
   sized for the worst routing, and is a table of ``N·k`` row numbers and
   one of a tile's expert — no array has the plan's rows;
3. one grouped SwiGLU FFN (``ops/pallas_moe.grouped_ffn`` on a TPU) takes
   ``x [N, d]`` as it comes, gathers a stage of the plan's rows at a time
   inside its VMEM, runs every tile that holds rows against its expert's
   three matrices, read from the stacked weights as stored (int8 and their
   scales included), weights each row by its gate and
4. sums a token's k rows in float32 into one ``[N, d]`` accumulator, still
   in VMEM (since PR 53; before, rows went to an HBM buffer of the plan's
   size and came back through a 0/1 matrix, and everything XLA did around
   the kernel was sized by the worst routing: 3,104 rows for the 264 real
   ones of a Laguna launch).

Off the TPU the plain form serves and is what the kernel is tested against:
the row buffer, the 0/1 spread matrix on both sides and ``lax.ragged_dot``
(:func:`_grouped_ffn_xla`).

Nothing is dropped and there is no capacity: the plan holds every tile
count a group can need, an expert with all N rows included, and tiles no
group needs are skipped by the kernel.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.hw import chip_spec
from .quant import QTensor

EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")

_MIN_TILE, _MAX_TILE = 32, 128


def sorted_from_rows(
    n_experts: int, k: int, weight_dtype, device_kind: str | None = None
) -> int | None:
    """The smallest row count ``N = B·T`` of a call whose MoE FFN is sorted
    and grouped; smaller calls keep the all-experts einsum. A function of the
    experts' shape, their stored dtype and the device's peaks, evaluated
    when a step is traced; ``None`` where sorting can win nothing (every
    expert is chosen).

    The cut is the chip's ridge: the all-experts einsum is free while N rows
    of FLOPs through an expert take no longer than streaming it, i.e. up to
    N = peak · bytes a weight ÷ (2 · bandwidth) rows — 120 for int8 weights
    on a v5e, the same for every expert shape. Measured there at both
    published shapes (8 × 4096 × 14336 and 64 × 2048 × 1024, ms a layer,
    einsum against sorted): 64 rows 1.89 / 1.91 and 0.55 / 0.57, 128 rows
    2.39 / 1.92 and 0.74 / 0.60, 256 rows 4.35 / 2.01 and 1.28 / 0.73 — the
    two cross where the arithmetic says, so no margin is added to it. A
    device that is not in ``utils/hw.py``'s table (the CPU of a test run) is
    read as the chip the engine is built for."""
    if k >= n_experts:
        return None
    kind = device_kind if device_kind is not None else jax.devices()[0].device_kind
    spec = chip_spec(kind) or chip_spec("v5 lite")
    ridge = spec.bf16_flops * jnp.dtype(weight_dtype).itemsize / (2 * spec.hbm_gbps)
    return int(ridge) + 1


def kernel_by_default() -> bool:
    """Whether a sorted call takes the Pallas kernel where nobody says: on a
    TPU backend (what the engine's ``moe.rows_gathered_in_kernel`` counts by)."""
    return jax.default_backend() == "tpu"


def row_tile(n_rows: int, n_experts: int, k: int) -> int:
    """Rows of a tile: the power of two at or over ONE AND A HALF times an
    expert's fair share of the assignments, between 32 and 128. An expert
    whose rows outgrow a tile is streamed once a tile, so a tile should hold
    what routing's scatter gives nearly every expert (at the fair share
    itself half of them overflow: 2.96 against 2.05 ms a Mixtral layer at
    256 rows, measured); past 128 rows a tile the MXU is full and the
    padding of half-filled tiles is all a larger one adds. A prefill bucket
    is a power of two and so is its share: the tile is then twice the share.
    A launch that carries the decode lanes beside a chunk (256 + 16 rows)
    has a share just over a power of two and keeps that bucket's tile — at
    twice the share it took the next one, and every expert's padding, the
    row buffer and the spread matrices doubled for a sixteenth more rows
    (19.0 ms an OLMoE launch, measured)."""
    share = -(-n_rows * k // n_experts)
    return min(_MAX_TILE, max(_MIN_TILE, 1 << (3 * share // 2 - 1).bit_length()))


def sorted_rows(n_rows: int, n_experts: int, k: int, tile: int | None = None) -> int:
    """Rows of the tile plan of a call of ``n_rows`` (the kernel's grid; off
    the TPU the plain path's row buffer): every tile the worst routing can
    need. A group of c rows takes ``ceil(c / tile)`` tiles; the groups hold
    ``n_rows·k`` rows between them and none more than ``n_rows`` (a token's
    choices are distinct).
    ``n_experts`` is what the stack holds (a token has at most that many of
    its choices here)."""
    tile = tile if tile is not None else row_tile(n_rows, n_experts, k)
    a = n_rows * min(k, n_experts)
    tiles = min(n_experts * -(-n_rows // tile), (a + n_experts * (tile - 1)) // tile)
    return tiles * tile


class Routing(NamedTuple):
    tile_expert: jnp.ndarray  # [tiles] the expert of each row tile
    n_active: jnp.ndarray  # tiles that hold rows; they come first
    row_of: jnp.ndarray  # [N·k] the plan's row of each assignment (token·k + choice)


def route(chosen: jnp.ndarray, n_experts: int, tile: int, n_rows_out: int) -> Routing:
    """Lay ``chosen`` ``[N, k]`` out as ``n_rows_out`` rows sorted by expert,
    each expert's group starting on a tile. Two sorts of ``N·k`` keys and
    arithmetic on tables of E entries, looked up through the assignments'
    one-hot: an XLA gather of a few thousand scalars costs the chip more
    than all of this (30–50 µs each, measured)."""
    a = chosen.size
    flat = chosen.reshape(a)
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)  # [A, E]
    counts = jnp.sum(onehot, axis=0)
    tiles = -(-counts // tile)
    tile_end = jnp.cumsum(tiles)
    first_row = (tile_end - tiles) * tile  # where an expert's group starts
    first_place = jnp.cumsum(counts) - counts  # ... and in the sorted order
    place = jnp.argsort(jnp.argsort(flat, stable=True))  # assignment -> sorted place
    row_of = place + jnp.sum(onehot * (first_row - first_place)[None, :], axis=1)
    n_active = tile_end[-1]
    # a tile's expert: the first whose tiles end after it; the unused tiles
    # at the end repeat the last used one's, so they fetch nothing new
    t = jnp.minimum(jnp.arange(n_rows_out // tile), n_active - 1)
    tile_expert = jnp.searchsorted(tile_end, t, side="right", method="compare_all")
    return Routing(tile_expert.astype(jnp.int32), n_active, row_of)


def stacked_experts(layers: dict) -> dict:
    """The expert weights of a stacked ``layers`` pytree as the grouped FFN
    takes them: ``name -> (weights [L, E, ·, ·], scales [L, E, 1, ·])``, both
    as stored: nothing the size of a stack is converted in a traced step (the
    kernel converts a block's scales in VMEM); float weights get scales of
    one."""
    out = {}
    for name in EXPERT_WEIGHTS:
        w = layers[name]
        if isinstance(w, QTensor):
            out[name] = (w.q, w.scale)
        else:
            out[name] = (w, jnp.ones(w.shape[:2] + (1, w.shape[3]), jnp.float32))
    return out


def gate_act(name: str):
    """The FFN's gate activation by ``cfg.ffn_act``: SwiGLU's or ReGLU's."""
    return {"silu": jax.nn.silu, "relu": jax.nn.relu}[name]


def _grouped_ffn_xla(x_rows, row_gate, routing, experts, layer, tile, act: str = "silu"):
    """The grouped FFN without the kernel (any backend): the layer's experts
    taken out of the stack and dequantised, ``lax.ragged_dot`` over the
    tile-aligned groups."""
    n_experts = experts["w_gate"][0].shape[1]
    used = jnp.arange(routing.tile_expert.size) < routing.n_active
    sizes = tile * jnp.sum(
        jax.nn.one_hot(routing.tile_expert, n_experts, dtype=jnp.int32) * used[:, None], axis=0
    )

    def dense(name):
        w, s = (lax.dynamic_index_in_dim(t, layer, 0, keepdims=False) for t in experts[name])
        return (w.astype(jnp.float32) * s.astype(jnp.float32)).astype(x_rows.dtype)

    gate = gate_act(act)(lax.ragged_dot(x_rows, dense("w_gate"), sizes))
    up = lax.ragged_dot(x_rows, dense("w_up"), sizes)
    out = lax.ragged_dot(gate * up, dense("w_down"), sizes)
    return (out * row_gate).astype(x_rows.dtype)


def sorted_moe_ffn(
    x: jnp.ndarray,  # [N, d]
    gates: jnp.ndarray,  # [N, k]
    chosen: jnp.ndarray,  # [N, k] int
    experts: dict,  # stacked_experts(...)
    layer,  # int32 scalar: which layer of the stack
    *,
    kernel: bool | None = None,
    interpret: bool = False,
    held: tuple[int, int] | None = None,
    act: str = "silu",
) -> jnp.ndarray:
    """``[N, d]``: ``Σ_j gates[n, j] · FFN_{chosen[n, j]}(x[n])``, computing
    only those pairs. ``act``: the gate's activation, ``silu`` (SwiGLU) or
    ``relu`` (ReGLU). ``kernel``: the Pallas grouped FFN (default: on a TPU
    backend). ``held = (offset, n_total)``: ``experts`` is the chip's share
    ``[offset, offset + E)`` of ``n_total`` experts and ``chosen`` indexes
    all of them; assignments to absent experts (a negative choice, a row
    routed nowhere, among them) are dropped before the sort (they sort last,
    get no row and add nothing), and the row tile follows the share of a
    token's choices that lands here.

    With the kernel the rows' trip to their tiles and back happens in its
    VMEM (ops/pallas_moe.py): what is traced here is the routing's tables of
    ``N·k`` and ``tiles`` integers, and nothing has ``sorted_rows`` rows.
    Without it rows go to a buffer of that many and come back through one 0/1
    matrix ``[M, N]`` (row r holds token n): exact — a row is one token's
    values, a token's output the float32 sum of its k rows — and the plain
    form the kernel is tested against."""
    n, k = chosen.shape
    n_experts = experts["w_gate"][0].shape[1]
    in_kernel = interpret or (kernel_by_default() if kernel is None else kernel)
    if in_kernel:
        from .pallas_moe import grouped_ffn, resident_rows

        most = resident_rows(x.shape[1], x.dtype.itemsize)
        if n > most:  # more rows than the kernel keeps in VMEM: a piece at a time
            return jnp.concatenate([
                sorted_moe_ffn(x[at : at + most], gates[at : at + most], chosen[at : at + most], experts, layer,
                               kernel=kernel, interpret=interpret, held=held, act=act)
                for at in range(0, n, most)
            ])
    if held is None:
        tile = row_tile(n, n_experts, k)
        m = sorted_rows(n, n_experts, k)
        routing = route(chosen, n_experts, tile, m)
    else:
        offset, n_total = held
        tile = row_tile(n, n_total, k)
        m = sorted_rows(n, n_experts, k, tile)
        local = chosen - offset
        here = (local >= 0) & (local < n_experts)
        routing = route(jnp.where(here, local, n_experts), n_experts, tile, m)
        # a call none of whose choices land here still runs tile 0 (rows of
        # gate 0): the kernel's index maps address tile ``n_active - 1``
        routing = routing._replace(
            row_of=jnp.where(here.reshape(-1), routing.row_of, m),
            n_active=jnp.maximum(routing.n_active, 1),
        )
    if in_kernel:
        (wg, sg), (wu, su), (wd, sd) = (experts[name] for name in EXPERT_WEIGHTS)
        out = grouped_ffn(
            x, gates, routing.row_of, routing.tile_expert, routing.n_active,
            wg, sg, wu, su, wd, sd, layer, tile=tile, interpret=interpret,
            **({"act": act} if act != "silu" else {}),
        )
        return out.astype(x.dtype)
    # holds[r, a]: buffer row r is assignment a (token a // k)
    holds = routing.row_of[None, :] == jnp.arange(m)[:, None]
    row_gate = jnp.sum(
        jnp.where(holds, gates.reshape(1, n * k).astype(jnp.float32), 0.0), axis=1, keepdims=True
    )  # 0 for a group's padding rows
    spread = jnp.any(holds.reshape(m, n, k), axis=2).astype(x.dtype)  # [M, N]
    x_rows = jnp.dot(spread, x, preferred_element_type=jnp.float32).astype(x.dtype)
    y = _grouped_ffn_xla(x_rows, row_gate, routing, experts, layer, tile, act)
    return jnp.einsum("mn,md->nd", spread, y, preferred_element_type=jnp.float32).astype(x.dtype)
