"""The hybrid block (Kimi-Linear, Olmo-Hybrid, Mistral-Small-4, Laguna,
MiniCPM-SALA, Solar-Open2): a linear mixer beside a positional one, a
positional one alone, or full attention beside sliding-window attention, and
two FFNs, through the one forward.

``models/llama.forward`` hands a config with ``layer_kinds`` to
:func:`forward` here; the engine calls one ``forward`` and never learns a
layer's kind. A layer is ``x += mixer(rmsnorm(x)); x += ffn(rmsnorm(x))``
with

- the mixer **KDA** (``ops/kda.py``: gated delta-rule linear attention; q, k,
  v through a short causal depthwise conv and SiLU, l2-normalised q and k, a
  per-channel decay from a low-rank pair, a sigmoid β, a head-wise RMSNorm
  and a low-rank sigmoid gate on the output) or **MLA** (``ops/mla.py``:
  latent attention; the cache row is the normalised latent and the shared
  key dimensions, the up-projection absorbed into query and output. With no
  rotary embedding anywhere, a full-rank query: Kimi-Linear. Under
  ``cfg.mla_q_rank`` the query has its own low-rank pair and norm; under
  ``cfg.mla_rotary`` the shared key dimensions and the query's matching part
  are rotated (``ops/rope.py``: YaRN's frequencies, adjacent pairs) BEFORE
  the row is written and the query absorbed, so the cached row holds a
  rotated ``k_s`` and absorbs as before; the softmax scale takes YaRN's
  ``m²`` and the query Llama-4's scale by position: Mistral-Small-4, every
  layer of which is such an MLA layer, with no linear mixer and no per-lane
  state at all);
- or the mixer **GDN** (Gated DeltaNet: the same delta rule with ONE decay a
  head, ``β = 2 · sigmoid`` where the model allows negative eigenvalues, keys
  narrower than values, so a rectangular state, and a full-rank SiLU output
  gate) or **full** (softmax attention over K/V rows read by the dense flash
  kernels, QK-norm over the whole projections, no rotary embedding where the
  model has none). A model has one linear kind and one positional kind, and
  any linear kind may stand beside any positional one: Solar-Open2 is **KDA**
  (``β = 2 · sigmoid`` under ``cfg.delta_neg_eigval``, as GDN) beside **full**
  (64 query heads over 8 K/V heads, no rotary embedding, no QK-norm, the
  output gated by a sigmoid as wide as itself: ``cfg.attn_gate == "full"``);
- or the mixer **lightning** (``ops/lightning.py``: linear attention with a
  constant decay a head, no conv, no β, no erase term; a norm a head on q and
  k, rotate-half RoPE, one RMSNorm over the concatenated output and a
  full-width sigmoid gate) or **sparse** (``ops/sparse_attention.py``: softmax
  attention whose rows past ``cfg.sparse_dense_len`` choose ``cfg.sparse_topk``
  key blocks from pooled keys and read nothing else; the pooled keys are a
  leaf of their own, ``ck [n_sparse, B, S / stride, KV, hd]``, beside ``k``
  and ``v``; a norm a head, no rotary embedding, a full-width sigmoid gate):
  MiniCPM-SALA, whose every sublayer's output is scaled before the residual
  adds it (``cfg.residual_scale``; ``cfg.embed_scale``, ``cfg.logit_divisor``);
- or, with no linear mixer, **full** beside **swa** (Laguna: the same softmax
  attention over the last ``cfg.window`` positions, its K/V rows a RING,
  ``wk``, ``wv`` ``[n_swa, B, R, KV, hd]``, whose arithmetic is
  ``models/llama.py``'s: ``ring_rows``, ``_ring_index``, the kernels'
  ``window=``). The two kinds have their own ``wq`` / ``wo`` / ``wg`` stacks,
  so their own count of query heads (48 and 64 over 8 K/V heads), and their
  own rotary embedding (:func:`_attn_rotate`: YaRN over the first half of a
  head, cos and sin scaled, against plain RoPE over the whole head); every
  head's output is gated by a sigmoid of the layer's normed input
  (``cfg.attn_gate``);
- the FFN a dense SwiGLU (the first ``n_dense_layers``) or the MoE of
  ``models/llama.py`` with the sigmoid router rule (or the softmax rules
  of ``llama.moe_gates``), a shared expert and the experts this chip holds;
- the norms before each sublayer, or (``cfg.post_norm``, the OLMo-2 family)
  on each sublayer's OUTPUT: ``x += rmsnorm(mixer(x)); x += rmsnorm(ffn(x))``.

**Weights are stacked per kind** (``params["kda"]`` ``[n_kda, …]``,
``["mla"]``, ``["dense"]``, ``["moe"]``; the two pre-norm vectors of every
layer in ``["layers"]``) and **each kind of block is traced once**: one
``lax.scan`` over all layers whose body switches mixer and FFN by the
layer's kind (``lax.cond``) and indexes the per-kind stacks, and the caches,
by the layer's index within its kind.

**The cache is a pytree this module builds** (:class:`HybridCache`,
``llama.init_cache``): positional rows — ``latent [n_mla, B, S, 640]`` (576
values and padding to whole lane tiles) or ``k``, ``v`` ``[n_full, B, S, KV
stored, hd]`` (:func:`stored_kv_heads`), read up to a lane's position like a
K/V arena — and per-lane state ``state`` float32 (``[n_kda, B, H, dk, dv]``,
or for GDN ``[n_gdn, B, dk, H·dv]``: whole tiles at 96 × 192 a head; for
lightning ``[n, B, H, dk, dv]`` with NO conv: ``conv`` is ``None``) and
``conv [n, B, (W − 1)·channels]`` — which cannot be truncated, rewound or
overwritten harmlessly. A leaf the model has no layer for is ``None`` (a
model with no linear kind has no ``state`` and no ``conv``: its cache is
positional rows and the two control leaves). All ride in the scan's carry
and are updated in place.

**Masking is part of the mathematics.** A token that is not valid leaves
state and conv untouched (β = 0, g = 0, conv not shifted). Prefill says
which rows of its bucket are real (``valid``). A decode step (``T = 1``,
no ``valid``) reads it from two per-lane control leaves the cache carries:
a lane steps its state while ``position < stop[lane]`` and the token fed is
not ``eos[lane]`` (a fed EOS closes the lane: ``stop = 0``). The engine sets
both when it admits a request (``admit_lane``): the last token a request
generates is never fed, so a parked or idle lane, and the steps a pipelined
chunk runs past a request's end, cannot touch a session's state.

**Two groups of rows in one launch** (``forward``'s ``lanes``, the engine's
``jit_prefill_with_decode``): a lane's chunk ``[1, T]`` and one decode step
of every lane ``[B, 1]`` go through the layer scan's body together, ``[1, T
+ B]`` rows through everything that reads weights. A positional mixer writes
both groups' rows, then attends each group as its own launch would
(``_groups``, ``_put_groups``). A linear mixer runs its projections and
everything elementwise over all the rows at once, and what reads or writes a
per-lane leaf a group at a time (``_short_conv``, ``_stack_by_group``): the
chunk's rows through the conv window and the chunked rule on lane ``slot``'s
state, the lanes' rows as a ``T = 1`` step of every lane on its own, so a
lane's window never slides over the chunk's rows nor the chunk's over a
lane's. The lanes' rows follow the controls as a ``T = 1`` step does; the
chunk's own lane is among them, parked at or past its
``stop``, so that step leaves what the chunk wrote as it is.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import kda as kda_ops
from ..ops import lightning as lightning_ops
from ..ops import mla as mla_ops
from ..ops import sparse_attention as sparse_ops
from ..ops.moe import EXPERT_WEIGHTS, stacked_experts
from ..ops.norms import rms_norm
from ..ops.quant import QTensor, dequant, embed_lookup
from ..ops.rope import apply_rope, yarn_frequencies
from .configs import LINEAR_KINDS, POSITIONAL_KINDS, WINDOW_KIND, ModelConfig

NO_STOP = np.iinfo(np.int32).max
L2_EPS = 1e-6


class HybridCache(NamedTuple):
    """``latent``, ``k`` and ``v`` are positional (rows up to a lane's
    position; ``None`` where the model has no such layer), ``ck`` follows
    ``k`` a row every ``stride`` positions; ``state`` and ``conv`` are
    per-lane (``conv`` is ``None`` for a linear kind without one); ``stop``
    and ``eos`` are the per-lane decode controls (module docstring)."""

    latent: jnp.ndarray | None  # [n_mla, B, S, latent_width]: R + r values, zero padding
    state: jnp.ndarray | None  # [n_kda | n_lightning, B, H, dk, dv] or [n_gdn, B, dk, H·dv], float32
    # [n, B, (W - 1)·channels]: the last W − 1 conv inputs, row after row (None: no conv, "lightning")
    conv: jnp.ndarray | None
    stop: jnp.ndarray  # [B] int32
    eos: jnp.ndarray  # [B] int32 (-1: no token closes the lane)
    k: jnp.ndarray | None = None  # [n_full, B, S, stored_kv_heads, hd]
    v: jnp.ndarray | None = None
    # the "swa" layers' rows as a ring: the row of position p is ``p mod R``
    # (``models/llama.ring_rows``); a lane's R rows are its last R positions
    wk: jnp.ndarray | None = None  # [n_swa, B, R, stored_kv_heads, hd]
    wv: jnp.ndarray | None = None
    # the "sparse" layers' pooled keys: row j is the mean of the lane's ``k``
    # rows ``stride·j .. stride·j + kernel − 1`` (``ops/sparse_attention.py``)
    ck: jnp.ndarray | None = None  # [n_sparse, B, S / stride, KV, hd]

    POSITIONAL = ("latent", "k", "v")
    RING = ("wk", "wv")
    # one row every ``stride`` positions: shipped up to a snapshot's bucket
    # like a positional leaf, not trimmed to the position (rows past it are
    # never read and are rewritten as the lane goes on)
    POOLED = ("ck",)

    def rows(self) -> tuple:
        """The positional leaves this cache has, in ``POSITIONAL``'s order."""
        return tuple(a for a in (self.latent, self.k, self.v) if a is not None)

    def carried(self) -> dict:
        """The leaves a positional mixer reads and writes, by name: the
        positional rows and, where the model has them, the pooled keys."""
        named = {n: getattr(self, n) for n in self.POSITIONAL + self.POOLED}
        return {n: a for n, a in named.items() if a is not None}

    def ring(self) -> tuple:
        """The ring leaves this cache has (both or neither)."""
        return tuple(a for a in (self.wk, self.wv) if a is not None)

    def leaves(self) -> dict:
        """The leaves a slot is made of, by name (the controls left out)."""
        named = {"latent": self.latent, "k": self.k, "v": self.v, "ck": self.ck, "wk": self.wk, "wv": self.wv,
                 "state": self.state, "conv": self.conv}
        return {n: a for n, a in named.items() if a is not None}


def stored_kv_heads(n_kv_heads: int) -> int:
    """K/V heads a row of the ``k`` and ``v`` leaves holds. The dense flash
    kernels read a block of whole ``[KV, hd]`` tiles, so a count that is not
    1, 2, 4 or a multiple of 8 is stored rounded up to one (30 as 32): the
    heads beyond the model's are never written, and a query padded with
    zeros reads them and is dropped. HBM tiles the last two dims, so the
    unpadded arena would occupy the same bytes, and XLA would pad it into a
    temporary before every kernel call besides."""
    return n_kv_heads if n_kv_heads in (1, 2, 4) else -(-n_kv_heads // 8) * 8


def conv_channels(cfg: ModelConfig) -> int:
    """Channels of the short conv: q, k and v side by side."""
    return cfg.kda_heads * (2 * cfg.kda_head_dim + cfg.delta_v_dim)


def latent_width(cfg: ModelConfig) -> int:
    """Columns of a stored latent row: the ``R + r`` values (Kimi-Linear's
    576, Mistral-Small-4's 320) and zeros up to whole 128-lane tiles (640,
    384). Unpadded, the TPU keeps the
    arena position-minor to save the padding itself, and every step program
    that hands it to a kernel relayouts it on the way in and out (compiled
    for a described v5e: two 2.1 GB copies a launch)."""
    return -(-(cfg.mla_kv_rank + cfg.mla_rope_dim) // 128) * 128


def init_cache(
    cfg: ModelConfig, lanes: int, max_seq: int, dtype=jnp.bfloat16, live: bool = True,
    launch_rows: int | None = None, block: int = 1,
) -> HybridCache:
    """A zeroed cache. ``live``: every lane steps (direct callers, tests);
    an engine starts its lanes closed (``stop = 0``) and opens one when it
    admits a request. ``launch_rows``, ``block``: the ring's size, as
    ``models/llama.ring_rows`` takes them (``llama.ring_plan`` gives an
    engine's)."""
    from .llama import ring_rows

    h, dk, dv, nl = cfg.kda_heads, cfg.kda_head_dim, cfg.delta_v_dim, cfg.n_linear
    for kinds in (LINEAR_KINDS, POSITIONAL_KINDS):
        have = [k for k in kinds if k in cfg.layer_kinds]
        if len(have) > 1:
            raise ValueError(
                f"the hybrid block has at most one of {kinds} in a model and this one has {have}: a cache holds one "
                'linear kind\'s state and one positional kind\'s rows ("swa" may stand beside "full")'
            )
    if cfg.n_window and (nl or cfg.positional_kind != "full"):
        raise ValueError('the hybrid block runs "swa" layers beside "full" layers and no other kind')
    stored = stored_kv_heads(cfg.n_kv_heads)
    arena = (cfg.n_positional, lanes, max_seq, stored, cfg.head_dim)
    ring = (cfg.n_window, lanes, ring_rows(cfg.window, max_seq, launch_rows, block), stored, cfg.head_dim)
    full = cfg.positional_kind in ("full", "sparse")
    state_shape = (nl, lanes, dk, h * dv) if cfg.linear_kind == "gdn" else (nl, lanes, h, dk, dv)
    has_conv = nl and cfg.linear_kind != "lightning"
    if cfg.n_sparse and max_seq % cfg.sparse_block:
        raise ValueError(f"a sparse layer's arena is whole key blocks of {cfg.sparse_block} rows, not {max_seq}")
    pooled = (cfg.n_sparse, lanes, max_seq // max(cfg.sparse_stride, 1), cfg.n_kv_heads, cfg.head_dim)
    return HybridCache(
        latent=jnp.zeros((cfg.n_mla, lanes, max_seq, latent_width(cfg)), dtype) if cfg.n_mla else None,
        state=jnp.zeros(state_shape, jnp.float32) if nl else None,
        # the W − 1 rows of a lane side by side: a dimension of 3 next to the
        # channels would be padded to a whole sublane tile (or, minor-most,
        # to 128 lanes: compiled for a described v5e, 94 MB became 3.75 GB)
        conv=jnp.zeros((nl, lanes, (cfg.kda_conv - 1) * conv_channels(cfg)), dtype) if has_conv else None,
        stop=jnp.full((lanes,), NO_STOP if live else 0, jnp.int32),
        eos=jnp.full((lanes,), -1, jnp.int32),
        k=jnp.zeros(arena, dtype) if full else None,
        v=jnp.zeros(arena, dtype) if full else None,
        wk=jnp.zeros(ring, dtype) if cfg.n_window else None,
        wv=jnp.zeros(ring, dtype) if cfg.n_window else None,
        ck=jnp.zeros(pooled, dtype) if cfg.n_sparse else None,
    )


# -- the cache manager's three moves on a lane --------------------------------


def admit_lane(cache: HybridCache, lane, fresh, stop, eos) -> HybridCache:
    """Open ``lane`` for a request: its decode may step the state up to
    position ``stop`` (exclusive) and closes on ``eos``. ``fresh``: a new
    context starts from zero state (the latent rows need no reset: they are
    read only up to the position)."""
    cache = cache._replace(stop=cache.stop.at[lane].set(stop), eos=cache.eos.at[lane].set(eos))
    if cache.state is None:  # no linear kind: nothing of a lane outlives its rows
        return cache
    keep = jnp.where(fresh, 0.0, 1.0)
    lane_of = lambda a: lax.dynamic_slice_in_dim(a, lane, 1, axis=1)  # noqa: E731
    put = lambda a, v: lax.dynamic_update_slice_in_dim(a, v, lane, axis=1)  # noqa: E731
    cache = cache._replace(state=put(cache.state, lane_of(cache.state) * keep))
    if cache.conv is None:  # a linear kind without a conv
        return cache
    return cache._replace(conv=put(cache.conv, lane_of(cache.conv) * keep.astype(cache.conv.dtype)))


def snapshot_lane(cache: HybridCache, lane, bucket: int, n_kv_heads: int | None = None) -> dict:
    """A lane's leaves by name: the positional rows ``[:, :bucket]`` (of
    ``k`` and ``v`` the model's ``n_kv_heads`` heads, not the padding they
    are stored with), the pooled keys of those rows (``[:, :bucket / stride]``),
    the per-lane state whole, and the ring whole (its R rows are the last R
    positions wherever the lane stands, as ``models/llama.snapshot_lane``
    ships it)."""
    lane_of = lambda a: lax.dynamic_index_in_dim(a, lane, axis=1, keepdims=False)  # noqa: E731
    out = {}
    for name, a in cache.leaves().items():
        a = lane_of(a)
        if name in cache.POSITIONAL:
            a = a[:, :bucket]
        if name in cache.POOLED:
            a = a[:, : -(-bucket * a.shape[1] // cache.k.shape[2])]
        if name in ("k", "v") + cache.RING:
            a = a[:, :, :n_kv_heads]
        out[name] = a
    return out


def restore_lane(cache: HybridCache, lane, leaves: dict) -> HybridCache:
    """Write a snapshot's leaves into ``lane`` (positional rows from 0) and
    close the lane until a request is admitted."""

    def put(arena, value):
        start = (0, lane) + (0,) * (arena.ndim - 2)
        return lax.dynamic_update_slice(arena, value[:, None].astype(arena.dtype), start)

    return cache._replace(
        **{name: put(a, leaves[name]) for name, a in cache.leaves().items()},
        stop=cache.stop.at[lane].set(0),
    )


# -- the kernels a step traces -------------------------------------------------


class HybridPlan(NamedTuple):
    """Which implementation each kind of layer's calls trace, chosen once
    and a kind at a time (:func:`plan_hybrid`; an engine chooses at build and
    reports it). "": the model has no such layer."""

    kda_decode: str = ""
    kda_prefill: str = ""
    mla_decode: str = ""
    mla_prefill: str = ""
    reason: str = ""
    gdn_decode: str = ""
    gdn_prefill: str = ""
    full_decode: str = ""
    full_prefill: str = ""
    # the rotary embedding of the MLA layers' shared key dims ("": none)
    mla_rotary: str = ""
    # the window layers beside the full ones (the same kernels, ``window=``)
    swa_decode: str = ""
    swa_prefill: str = ""
    # block-sparse attention beside lightning attention: a sparse layer's
    # rows under ``cfg.sparse_dense_len`` take the dense kernels named first
    sparse_decode: str = ""
    sparse_prefill: str = ""
    lightning_decode: str = ""
    lightning_prefill: str = ""

    def describe(self) -> dict:
        mine = {k: v for k, v in self._asdict().items() if v}
        prefill, decode = next(
            (pair for pair in ((self.full_prefill, self.full_decode), (self.sparse_prefill, self.sparse_decode)) if pair[1]),
            (self.mla_prefill, self.mla_decode),
        )
        arena = "layer_slice" if decode.startswith("xla:") else "stack+layer"
        return {**mine, "prefill": prefill, "decode": decode, "arena": arena}

    def kinds(self) -> dict:
        """``kind -> (prefill, decode)`` of the kinds the model has."""
        pairs = {
            k: (getattr(self, k + "_prefill"), getattr(self, k + "_decode"))
            for k in ("kda", "gdn", "lightning", "mla", "full", "sparse", WINDOW_KIND)
        }
        return {k: v for k, v in pairs.items() if v[0]}


def plan_hybrid(cfg: ModelConfig, use_pallas: bool | None = None) -> HybridPlan:
    """The plan, chosen A KIND AT A TIME: each kind of layer the model has
    answers for itself (:data:`_KIND_PLANS`: its prefill and decode forms, and
    why it takes no kernel where it takes none), so any pair the block can
    hold gets a kernel and a rule for both of its kinds (KDA beside "full":
    Solar-Open2)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    forms, missed = {}, []
    for kind in (cfg.linear_kind, cfg.positional_kind, WINDOW_KIND if cfg.n_window else None):
        if kind is None:
            continue
        prefill, decode, miss = _KIND_PLANS[kind](cfg, use_pallas)
        forms.update({kind + "_prefill": prefill, kind + "_decode": decode})
        if miss and miss not in missed:  # "full" and "swa" give one answer
            missed.append(miss)
    # a kind's ``miss`` says why a TPU's kernel was not taken: off a TPU there is one reason for all
    why = "no tpu backend" if not use_pallas else "; ".join(missed) or f"tpu backend; {_read_in_place(cfg)}"
    return HybridPlan(**forms, reason=why, mla_rotary=rotary_kind(cfg) if cfg.n_mla else "")


def rotary_kind(cfg: ModelConfig) -> str:
    """The MLA layers' rotary embedding in words, for the plan's description."""
    if not cfg.mla_rotary:
        return ""
    freqs = f"yarn x{cfg.rope_factor:g} past {cfg.rope_original_max}" if cfg.rope_factor > 1.0 else "rope"
    return f"{freqs}, theta {cfg.rope_theta:g}, {'adjacent pairs' if cfg.rope_interleave else 'split halves'}"


def _read_in_place(cfg: ModelConfig) -> str:
    """What a plan whose every kind took its kernels says of the model's leaves."""
    if cfg.n_sparse or cfg.linear_kind == "lightning":
        return "a lane's K/V rows read where they lie, a dense row's and a sparse row's listed blocks"
    if cfg.linear_kind is None and cfg.positional_kind == "mla":
        return "the latent stack read where it lies"
    return f"state and {'latent' if cfg.positional_kind == 'mla' else 'K/V'} stacks read where they lie"


def _kda_aligned(cfg: ModelConfig) -> bool:
    return cfg.kda_head_dim % 128 == 0 and cfg.kda_heads % 8 == 0


def _plan_kda(cfg: ModelConfig, use_pallas: bool):
    """The state kernel where a head's ``[dk, dv]`` tile is whole (8, 128)
    tiles in blocks of 8 heads; the chunked rule is XLA's on every backend."""
    if use_pallas and _kda_aligned(cfg):
        return "xla_chunked", "pallas_kda_decode", ""
    return "xla_chunked", "xla_step", "KDA heads not (8, 128)-aligned"


def _plan_gdn(cfg: ModelConfig, use_pallas: bool):
    """The state kernel where a lane's ``[dk, H·dv]`` tile is whole (8, 128)
    tiles and the heads pack into lane-aligned windows."""
    from ..ops.pallas_kda import gdn_supported

    h, dk, dv = cfg.kda_heads, cfg.kda_head_dim, cfg.delta_v_dim
    if use_pallas and gdn_supported(h, dk, dv):
        return "xla_chunked", "pallas_gdn_decode", ""
    return "xla_chunked", "xla_step", f"GDN state tile [{dk}, {h}x{dv}] is not whole (8, 128) tiles"


def _plan_lightning(cfg: ModelConfig, use_pallas: bool):
    """The state's rule is XLA's on every backend."""
    return "xla_chunked", "xla_step", ""


def _plan_mla(cfg: ModelConfig, use_pallas: bool):
    """The latent kernels take any width that is whole lane tiles
    (``latent_width``). Beside KDA layers whose heads miss the state kernel's
    tiles the latent rows stay XLA's too (a tiny preset on a TPU: the answer
    it had when the plan was chosen by pair)."""
    if use_pallas and (not cfg.n_kda or _kda_aligned(cfg)):
        return "pallas_mla_prefill", "pallas_mla_decode", ""
    return "xla_absorbed", "xla_absorbed", ""


def _plan_full(cfg: ModelConfig, use_pallas: bool):
    """The dense flash kernels where they take the STORED head count, of the
    "full" layers and of the "swa" layers beside them (the same kernels,
    ``window=``): one answer for both kinds."""
    from ..ops.pallas_attention import kernel_supported

    kv = stored_kv_heads(cfg.n_kv_heads)
    counts = {cfg.n_heads, cfg.window_heads} if cfg.n_window else {cfg.n_heads}
    if use_pallas and all(kernel_supported(kv * (n // cfg.n_kv_heads), kv, cfg.head_dim) for n in counts):
        return "pallas:flash_prefill", "pallas:flash_decode", ""
    miss = f"heads {sorted(counts)}/{kv} stored x {cfg.head_dim}: not the flash kernels' shapes"
    return "xla:attention_reference", "xla:attention_reference", miss


def _plan_sparse(cfg: ModelConfig, use_pallas: bool):
    """A chunk's sparse rows (a row-by-block mask over runs of the lane's
    rows) are XLA's on every backend. A lane's step takes two kernels where
    they take the head counts: the dense flash kernel under
    ``cfg.sparse_dense_len`` and, past it, ``sparse_decode``, which copies the
    listed blocks from the arena where it lies; elsewhere XLA gathers them."""
    from ..ops.pallas_attention import kernel_supported

    if use_pallas and kernel_supported(cfg.n_heads, stored_kv_heads(cfg.n_kv_heads), cfg.head_dim):
        return "xla:block_mask", "pallas:flash_decode+pallas:sparse_decode", ""
    miss = f"heads {cfg.n_heads}/{cfg.n_kv_heads} x {cfg.head_dim}: not the flash kernels' shapes"
    return "xla:block_mask", "xla:attention_reference+xla:block_gather", miss


# kind -> (cfg, use_pallas) -> (prefill, decode, why a TPU's kernel is not taken or "")
_KIND_PLANS = {
    "kda": _plan_kda, "gdn": _plan_gdn, "lightning": _plan_lightning,
    "mla": _plan_mla, "full": _plan_full, "sparse": _plan_sparse, WINDOW_KIND: _plan_full,
}


def attention_by_kind(cfg: ModelConfig) -> dict:
    """What differs by kind of attention layer in a model with "full" beside
    "swa" layers, in words, for an engine's ``/metrics``: the query heads, the
    output gate and each kind's rotary embedding."""
    freqs = f"yarn x{cfg.rope_factor:g} past {cfg.rope_original_max}" if cfg.rope_factor > 1.0 else "rope"
    full = (
        f"{freqs}, theta {cfg.rope_theta:g}, first {cfg.rotary_dim} of {cfg.head_dim} dims, "
        f"cos and sin x{cfg.rope_attention_factor:.7g}" if cfg.rope_theta else "none"
    )
    swa = f"rope, theta {cfg.swa_rope_theta:g}, all {cfg.head_dim} dims" if cfg.swa_rope_theta else "none"
    return {
        "heads": {"full": cfg.n_heads, WINDOW_KIND: cfg.window_heads},
        "gate": cfg.gate_form,
        "rotary": {"full": full, WINDOW_KIND: swa},
    }


# -- parameters ----------------------------------------------------------------


def param_shapes(cfg: ModelConfig) -> dict:
    """``group -> name -> (shape, is_matrix)``: matrices are what a checkpoint
    quantises and the synthetic generator draws as int8; vectors stay dense."""
    d, h, dk = cfg.dim, cfg.kda_heads, cfg.kda_head_dim
    c = h * dk
    nk, nm = cfg.n_kda, cfg.n_mla
    nd, ne = cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers
    qk = cfg.mla_nope_dim + cfg.mla_rope_dim
    fs = cfg.n_shared_experts * cfg.ffn_dim
    ng, nf, nlt = (cfg.layer_kinds.count(k) for k in ("gdn", "full", "lightning"))
    cc, cv, hd = conv_channels(cfg), h * cfg.delta_v_dim, cfg.head_dim

    def attention(n: int, heads: int) -> dict:  # a kind's own stacks: its count of query heads
        out = {
            "wq": ((n, d, heads * hd), True),
            "wk": ((n, d, cfg.n_kv_heads * hd), True),
            "wv": ((n, d, cfg.n_kv_heads * hd), True),
            "wo": ((n, heads * hd, d), True),
        }
        if cfg.attn_gate:  # a gate a head, or as wide as the output
            out["wg"] = ((n, d, cfg.gate_width(heads)), True)
        if cfg.qk_norm:
            out.update(q_norm=((n, heads * hd), False), k_norm=((n, cfg.n_kv_heads * hd), False))
        return out

    shapes = {
        "layers": {"attn_norm": ((cfg.n_layers, d), False), "mlp_norm": ((cfg.n_layers, d), False)},
        "kda": {
            "wqkv": ((nk, d, 3 * c), True),
            "conv": ((nk, cfg.kda_conv, 3 * c), False),
            "w_fa": ((nk, d, dk), True),
            "w_fb": ((nk, dk, c), True),
            "dt_bias": ((nk, c), False),
            "a_log": ((nk, h), False),
            "w_beta": ((nk, d, h), True),
            "w_ga": ((nk, d, dk), True),
            "w_gb": ((nk, dk, c), True),
            "o_norm": ((nk, dk), False),
            "wo": ((nk, c, d), True),
        },
        "gdn": {
            "wqkv": ((ng, d, cc), True),  # q | k | v: 2·H·dk + H·dv columns
            "conv": ((ng, cfg.kda_conv, cc), False),
            "w_a": ((ng, d, h), True),
            "dt_bias": ((ng, h), False),
            "a_log": ((ng, h), False),
            "w_beta": ((ng, d, h), True),
            "w_g": ((ng, d, cv), True),
            "o_norm": ((ng, cfg.delta_v_dim), False),
            "wo": ((ng, cv, d), True),
        },
        "full": attention(nf, cfg.n_heads),
        WINDOW_KIND: attention(cfg.n_window, cfg.window_heads),
        # a norm a head on q and k, a sigmoid gate as wide as the output
        "sparse": {
            "wq": ((cfg.n_sparse, d, cfg.n_heads * hd), True),
            "wk": ((cfg.n_sparse, d, cfg.n_kv_heads * hd), True),
            "wv": ((cfg.n_sparse, d, cfg.n_kv_heads * hd), True),
            "wg": ((cfg.n_sparse, d, cfg.n_heads * hd), True),
            "wo": ((cfg.n_sparse, cfg.n_heads * hd, d), True),
            "q_norm": ((cfg.n_sparse, hd), False),
            "k_norm": ((cfg.n_sparse, hd), False),
        },
        "lightning": {
            "wq": ((nlt, d, c), True),
            "wk": ((nlt, d, c), True),
            "wv": ((nlt, d, c), True),
            "wg": ((nlt, d, c), True),
            "wo": ((nlt, c, d), True),
            "q_norm": ((nlt, dk), False),
            "k_norm": ((nlt, dk), False),
            "o_norm": ((nlt, c), False),
            # the decay a head as its slope, λ = exp(−slope): a vector a
            # layer, so that a checkpoint's own drops in
            "slope": ((nlt, h), False),
        },
        "mla": {
            **(
                {
                    "wq_a": ((nm, d, cfg.mla_q_rank), True),
                    "q_norm": ((nm, cfg.mla_q_rank), False),
                    "wq_b": ((nm, cfg.mla_q_rank, cfg.n_heads * qk), True),
                }
                if cfg.mla_q_rank
                else {"wq": ((nm, d, cfg.n_heads * qk), True)}
            ),
            "wkva": ((nm, d, cfg.mla_kv_rank + cfg.mla_rope_dim), True),
            "kv_norm": ((nm, cfg.mla_kv_rank), False),
            "wkvb": ((nm, cfg.mla_kv_rank, cfg.n_heads * (cfg.mla_nope_dim + cfg.mla_v_dim)), True),
            "wo": ((nm, cfg.n_heads * cfg.mla_v_dim, d), True),
        },
        "dense": {
            "w_gate": ((nd, d, cfg.dense_ffn_dim), True),
            "w_up": ((nd, d, cfg.dense_ffn_dim), True),
            "w_down": ((nd, cfg.dense_ffn_dim, d), True),
        },
        "moe": {
            "router": ((ne, d, cfg.n_experts), True),
            # the selection bias is the sigmoid rule's (``llama.moe_gates``)
            **({"router_bias": ((ne, cfg.n_experts), False)} if cfg.moe_router == "sigmoid" else {}),
            "w_gate": ((ne, cfg.n_held, d, cfg.ffn_dim), True),
            "w_up": ((ne, cfg.n_held, d, cfg.ffn_dim), True),
            "w_down": ((ne, cfg.n_held, cfg.ffn_dim, d), True),
        },
    }
    if fs:
        shapes["moe"].update(
            ws_gate=((ne, d, fs), True), ws_up=((ne, d, fs), True), ws_down=((ne, fs, d), True)
        )
    return {g: v for g, v in shapes.items() if all(s[0][0] > 0 for s in v.values())}


def vector_values(name: str, shape: tuple, key, dtype, group: str = "kda"):
    """The dense vectors of a hybrid model, seeded: norms at one; ``a_log``
    and ``dt_bias`` drawn so that a token's decays spread over about (0.5,
    0.999) (``−g = exp(a_log) · softplus(dt_bias + small)``: a reference that
    drops the gate is far off); conv filters of order ½; the selection bias
    non-zero, so that choosing by ``s + b`` and weighing by ``s`` differ.
    ``group == "gdn"``: Gated DeltaNet's published initialisation (Mamba2's:
    ``A ~ U(0, 16)``, ``dt`` log-uniform over (0.001, 0.1), ``dt_bias`` its
    inverse softplus), so a head's decay ``exp(−A · softplus(w_a x + dt_bias))``
    spreads from about 0.2 to 0.9999 with a median near 0.9: heads that forget
    in a few tokens beside heads that remember hundreds. (A first draw kept
    EVERY head's memory longer than the 200 tokens of the numerics check: the
    rounding of each token's key then adds up through the state's
    ``I − β k kᵀ`` transitions like the square root of the length, and the
    reference with bfloat16 matmul inputs itself read 2.07 % at 200 tokens,
    my chip run, PR 32.)"""
    if name == "slope":
        # Lightning Attention-2's slopes, the same in every layer: 2^(−8 (h + 1) / H),
        # so a head's memory runs from a token or two to a few hundred. float32:
        # a bfloat16 slope is another decay
        h = shape[-1]
        return jnp.broadcast_to(2.0 ** (-8.0 * (jnp.arange(h, dtype=jnp.float32) + 1.0) / h), shape)
    if group == "gdn" and name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0 / 16.0, 16.0)).astype(dtype)
    if group == "gdn" and name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, np.log(0.001), np.log(0.1)))
        return jnp.log(jnp.expm1(dt)).astype(jnp.float32)  # softplus⁻¹: kept f32
    if name == "a_log":
        return jax.random.uniform(key, shape, jnp.float32, -0.3, 0.3).astype(dtype)
    if name == "dt_bias":
        y = jnp.exp(jax.random.uniform(key, shape, jnp.float32, np.log(0.0015), np.log(0.5)))
        return jnp.log(jnp.expm1(y)).astype(jnp.float32)  # softplus⁻¹: kept f32
    if name == "conv":
        return (jax.random.normal(key, shape, jnp.float32) * 0.5).astype(dtype)
    if name == "router_bias":
        return (jax.random.normal(key, shape, jnp.float32) * 0.1).astype(jnp.float32)
    return jnp.ones(shape, dtype)


def embed_rms(cfg: ModelConfig) -> float | None:
    """RMS of a synthetic embedding's rows, where it is not the weights' own
    scale (``None``): 1 for a block without a pre-norm (``cfg.post_norm``).
    Such a block's first sublayers read the embedding itself, and at the
    weights' 0.02 their outputs' mean squares lie under the norms' eps (3e-9
    against 1e-6 in the first mixer, 2e-8 in the first FFN: read on the CPU at
    published head sizes), so the norms that should set the stream's scale do
    not, every sublayer is a product of small numbers, and a rounding of its
    input is doubled by each (the reference with bfloat16 matmul inputs read
    1.84 % at 4 layers that way, my chip run, PR 32). A served model's stream
    is of order one from its first layer.

    1 also where the FIRST mixer is softmax attention with no positional
    embedding ("full" first, ``rope_theta`` 0: Solar-Open2's layer 0). Under
    random weights such a layer's late rows all come out as nearly the same
    average of the values, three times the 0.02-scale embedding beside it; the
    first FFN reads that sum, and after one layer every late row of the stream
    is nearly ONE vector: the token is lost. The linear layers that follow
    then step their state by near-identical keys, and a rounding of that
    shared vector is the same error in every row, which a state's memory adds
    up coherently while the tokens' own signal adds like a random walk: the
    reference with bfloat16 matmul inputs itself read 2.2-5.9 % at 5 layers (G
    K K K G, the error 0.5 % after the attention layer and 1.8 % after the
    first KDA layer: my chip run and CPU readings at published widths, PR 57),
    and 0.36 % with rows of RMS 1, which keep the token the larger part of
    the stream, as a trained embedding does."""
    if cfg.post_norm or (cfg.layer_kinds[:1] == ("full",) and not cfg.rope_theta):
        return 1.0
    return None


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Random init of the hybrid pytree (0.02-scale matrices, the vectors of
    :func:`vector_values`)."""
    shapes = param_shapes(cfg)
    n = sum(len(v) for v in shapes.values()) + 2
    keys = iter(jax.random.split(key, n))

    def w(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dtype)

    out = {
        g: {
            name: w(next(keys), shape) if matrix else vector_values(name, shape, next(keys), dtype, g)
            for name, (shape, matrix) in group.items()
        }
        for g, group in shapes.items()
    }
    out["embed"] = w(next(keys), (cfg.vocab_size, cfg.dim))
    if embed_rms(cfg) is not None:
        out["embed"] = (out["embed"].astype(jnp.float32) * (embed_rms(cfg) / 0.02)).astype(dtype)
    out["lm_head"] = w(next(keys), (cfg.dim, cfg.vocab_size))
    out["final_norm"] = jnp.ones((cfg.dim,), dtype)
    return out


# -- the block -------------------------------------------------------------------


def _layer_of(stack: dict, idx, dense: bool = False) -> dict:
    """Layer ``idx`` of a per-kind stack. An int8 leaf stays a ``QTensor``
    for :func:`_proj`; ``dense`` dequantises (what the shared MoE paths of
    ``models/llama.py`` take)."""
    out = {
        k: jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, idx, 0, keepdims=False), v)
        for k, v in stack.items()
    }
    return {k: dequant(v) for k, v in out.items()} if dense else out


def _proj(x, w):
    """A projection whose result stays in the accumulator's float32: what
    follows it here (conv, SiLU, norms, gates, a float32 recurrence) is
    elementwise and cheap, and rounding every intermediate to bfloat16 reads
    twice the error of rounding the matmuls' inputs alone (measured on the
    chip at published widths, 5 layers: 2.2 % against the control's 1.0 %)."""
    if isinstance(w, QTensor):
        # the per-output-channel scale on the float32 result: exact, where
        # scaling the int8 weights first rounds every one of them to bfloat16
        y = jnp.dot(x, w.q.astype(x.dtype), preferred_element_type=jnp.float32)
        return y * w.scale.astype(jnp.float32)
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def router_logits(h32, router):
    """The router's logits in float32 from the float32 normed stream. The
    sigmoid rule chooses the top 8 of 256 scores plus a bias, where the
    eighth and the ninth lie closer than a bfloat16 rounding more often than
    not, and a flipped choice is a position far off (the share of positions
    within the tolerance read 0.78 on the chip with bfloat16 logits, 0.85
    with bfloat16 inputs: my chip runs, PR 30). ``[rows, d] × [d, 256]`` at
    full precision is a thousandth of the layer's experts."""
    w = router.q.astype(jnp.float32) * router.scale.astype(jnp.float32) if isinstance(router, QTensor) else router
    return jnp.dot(h32, w.astype(jnp.float32), precision=lax.Precision.HIGHEST)


def _swiglu(x, w_gate, w_up, w_down):
    """SwiGLU through :func:`_proj` (the dense layers and the shared expert)."""
    mid = (jax.nn.silu(_proj(x, w_gate)) * _proj(x, w_up)).astype(x.dtype)
    return _proj(mid, w_down)


def _l2norm(x):
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + L2_EPS)


def _rows(arena, idx, slot, b: int):
    """Lanes ``slot .. slot + b`` (all ``b`` lanes without a slot) of layer
    ``idx`` of a stacked per-lane arena."""
    layer = lax.dynamic_index_in_dim(arena, idx, 0, keepdims=False)
    return layer if slot is None else lax.dynamic_slice_in_dim(layer, slot, b, axis=0)


def _put_rows(arena, value, idx, slot):
    start = (idx, 0 if slot is None else slot) + (0,) * (arena.ndim - 2)
    return lax.dynamic_update_slice(arena, value[None].astype(arena.dtype), start)


def _groups(n_lanes: int, *arrays):
    """Each ``[1, T + B, ...]`` array of a launch with two groups of rows as
    the pair (the chunk's ``[1, T, ...]``, the lanes' ``[B, 1, ...]``)."""
    return [(a[:, :-n_lanes], a[0, -n_lanes:, None]) for a in arrays]


def _put_groups(arena, rows, idx, slot, positions, n_lanes: int, at=lambda p: p):
    """Both groups' new rows into layer ``idx`` of a positional stack, each at
    its own ``(lane, position)`` and before either group is read: the
    chunk's at arena row ``slot``, the lanes' at rows ``0 .. B``. ``at``: the
    row of a position (a ring's ``p mod R``)."""
    (rows_c, rows_l), (pos_c, pos_l) = _groups(n_lanes, rows.astype(arena.dtype), positions)
    return arena.at[idx, slot, at(pos_c)].set(rows_c).at[idx, jnp.arange(n_lanes)[:, None], at(pos_l)].set(rows_l)


def _by_group(attend, n_lanes: int, q, positions, valid, slot):
    """``attend(q, positions, valid, slot)`` over a launch's rows: one call,
    or with ``n_lanes`` one for each group, the chunk's over arena row ``slot``
    and the lanes' over their own, joined as ``[1, T + B, ...]``."""
    if not n_lanes:
        return attend(q, positions, valid, slot)
    (q_c, q_l), (pos_c, pos_l), (val_c, val_l) = _groups(n_lanes, q, positions, valid)
    o_c, o_l = attend(q_c, pos_c, val_c, slot), attend(q_l, pos_l, val_l, None)
    return jnp.concatenate([o_c, o_l.reshape(1, n_lanes, *o_l.shape[2:])], axis=1)


def _cut_rows(n_lanes: int, a):
    """A ``[1, T + B, ...]`` array as (the chunk's ``[1, T, ...]``, the lanes'
    ``[B, 1, ...]``), cut as ROWS ``[T + B, ...]``; :func:`_join_rows` joins
    them so. Cut or joined along the second of three dimensions, the chip's
    compiler lays a ``[1, T + B, C]`` value out a row a tile and every
    elementwise pass over it takes eight times its bytes (7 of a 53 ms launch
    of Olmo-Hybrid's, my chip run, PR 48)."""
    return a[0][None, :-n_lanes], a[0][-n_lanes:, None]


def _join_rows(o_c, o_l):
    return jnp.concatenate([o_c[0], o_l[:, 0]], axis=0)[None]


def _stack_by_group(fn, n_lanes: int, slot, stack, *arrays):
    """``fn(*arrays, slot, stack) -> (out, stack)`` over a launch's rows, for
    what reads and writes a per-lane stack (a linear mixer's state): one call,
    or with ``n_lanes`` one for each group, as its own launch would make it:
    the chunk's ``[1, T]`` rows on lane ``slot``'s leaf, then the lanes' ``[B,
    1]`` as a ``T = 1`` step of every lane on its own. Neither group's rows
    reach the other's leaves; the chunk's own lane is among the B and does
    not step (:func:`forward`). ``out`` comes back joined as ``[1, T + B,
    ...]``."""
    if not n_lanes:
        return fn(*arrays, slot, stack)
    chunk, lanes = zip(*(_cut_rows(n_lanes, a) for a in arrays))
    o_c, stack = fn(*chunk, slot, stack)
    o_l, stack = fn(*lanes, None, stack)
    return _join_rows(o_c, o_l), stack


def _short_conv(h, lp, cfg: ModelConfig, conv, idx, slot, valid, n_lanes: int):
    """``h``'s input projection ``wqkv`` through the short causal conv, each
    lane's rows continuing its window of layer ``idx``: the outputs ``[B, T,
    C]``, the windows moved on by the valid tokens and the slot to put them
    back at (:func:`_put_rows`, once the delta rule has run: a call without
    ``n_lanes`` holds its operations in the order it always had, the pinned
    programs of ``tests/test_hlo_contracts.py``). With ``n_lanes`` a group at
    a time, as its own launch would: the chunk's ``[1, T]`` rows on lane
    ``slot``'s window, then the lanes' ``[B, 1]`` as a ``T = 1`` step of every
    lane on its own, the chunk's lane among them as the chunk leaves it (it
    does not step: :func:`forward`), so the B windows are what goes back. A
    lane's window never slides over the chunk's rows nor the chunk's over a
    lane's."""

    def window(valid, slot):  # the tokens that move a group's windows on, and the windows as they stand
        b = valid.shape[0]
        return jnp.sum(valid, axis=1).astype(jnp.int32), _rows(conv, idx, slot, b).reshape(b, cfg.kda_conv - 1, -1)

    if not n_lanes:
        n_valid, rows = window(valid, slot)
        return *kda_ops.causal_conv(_proj(h, lp["wqkv"]), rows, lp["conv"], n_valid), slot
    (valid_c, valid_l), (x_c, x_l) = (_cut_rows(n_lanes, a) for a in (valid, _proj(h, lp["wqkv"])))
    n_valid, rows = window(valid_c, slot)
    y_c, new_c = kda_ops.causal_conv(x_c, rows, lp["conv"], n_valid)
    n_valid, rows = window(valid_l, None)
    rows = lax.dynamic_update_slice_in_dim(rows, new_c.astype(rows.dtype), slot, axis=0)
    y_l, new = kda_ops.causal_conv(x_l, rows, lp["conv"], n_valid)
    return _join_rows(y_c, y_l), new, None


def kda_mixer(h, lp, cfg: ModelConfig, state, conv, idx, slot, valid, plan: HybridPlan, n_lanes: int = 0):
    """``h [B, T, d]`` (normed) → the mixer's output, and the state and conv
    stacks with layer ``idx``'s lanes stepped by the valid tokens. ``n_lanes``
    as :func:`mla_mixer` takes it: the projections and everything elementwise
    run over the ``[1, T + B]`` rows once; the conv windows and the delta
    rule, which read and write a lane's leaves, run a group at a time
    (:func:`_short_conv`, :func:`_stack_by_group`)."""
    b, t, _ = h.shape
    nh, dk = cfg.kda_heads, cfg.kda_head_dim
    qkv, window, put_at = _short_conv(h, lp, cfg, conv, idx, slot, valid, n_lanes)
    q, k, v = jnp.split(jax.nn.silu(qkv).reshape(b, t, 3, nh, dk), 3, axis=2)
    q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]
    q = _l2norm(q) * dk**-0.5
    k = _l2norm(k)
    decay_in = _proj(_proj(h, lp["w_fa"]).astype(h.dtype), lp["w_fb"]) + lp["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(lp["a_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(decay_in).reshape(b, t, nh, dk)
    beta = jax.nn.sigmoid(_proj(h, lp["w_beta"]))
    if cfg.delta_neg_eigval:  # β ∈ (0, 2): the transition I − β k kᵀ has an eigenvalue in (−1, 1)
        beta = beta * 2.0
    g, beta = kda_ops.mask_inputs(g, beta, valid)

    def rule(q, k, v, g, beta, slot, state):
        b, t = beta.shape[:2]
        if t == 1 and plan.kda_decode == "pallas_kda_decode" and slot is None:
            from ..ops.pallas_kda import kda_decode

            with jax.named_scope("kda_step"):
                o, state = kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state, idx)
            return o[:, None], state
        rows = _rows(state, idx, slot, b)
        if t == 1:
            with jax.named_scope("kda_step"):
                o, rows = kda_ops.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], rows)
            o = o[:, None]
        else:  # its two halves are scoped ``kda_prepass`` and ``kda_scan``
            o, rows = kda_ops.kda_chunked(q, k, v, g, beta, rows)
        return o, _put_rows(state, rows, idx, slot)

    o, state = _stack_by_group(rule, n_lanes, slot, state, q, k, v, g, beta)
    conv = _put_rows(conv, window.reshape(window.shape[0], -1), idx, put_at)
    gate = jax.nn.sigmoid(_proj(_proj(h, lp["w_ga"]).astype(h.dtype), lp["w_gb"])).reshape(b, t, nh, dk)
    o = rms_norm(o, lp["o_norm"], cfg.norm_eps) * gate
    return _proj(o.reshape(b, t, nh * dk).astype(h.dtype), lp["wo"]), state, conv


def gdn_mixer(h, lp, cfg: ModelConfig, state, conv, idx, slot, valid, plan: HybridPlan, n_lanes: int = 0):
    """``h [B, T, d]`` → the Gated DeltaNet mixer's output, and the state
    ``[n, B, dk, H·dv]`` and conv stacks with layer ``idx``'s lanes stepped by
    the valid tokens. One decay a head; ``β = 2 · sigmoid`` under
    ``cfg.delta_neg_eigval``; a full-rank SiLU gate on the normed output.
    ``n_lanes`` as :func:`kda_mixer`."""
    b, t, _ = h.shape
    nh, dk, dv = cfg.kda_heads, cfg.kda_head_dim, cfg.delta_v_dim
    ck = nh * dk
    qkv, window, put_at = _short_conv(h, lp, cfg, conv, idx, slot, valid, n_lanes)
    qkv = jax.nn.silu(qkv)
    q = _l2norm(qkv[..., :ck].reshape(b, t, nh, dk)) * dk**-0.5
    k = _l2norm(qkv[..., ck : 2 * ck].reshape(b, t, nh, dk))
    v = qkv[..., 2 * ck :].reshape(b, t, nh, dv)
    g = -jnp.exp(lp["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        _proj(h, lp["w_a"]) + lp["dt_bias"].astype(jnp.float32)
    )  # [B, T, H]
    beta = jax.nn.sigmoid(_proj(h, lp["w_beta"])) * (2.0 if cfg.delta_neg_eigval else 1.0)
    g, beta = kda_ops.mask_inputs(g[..., None], beta, valid)

    def rule(q, k, v, g, beta, slot, state):
        b, t = beta.shape[:2]
        if t == 1 and plan.gdn_decode == "pallas_gdn_decode" and slot is None:
            from ..ops.pallas_kda import gdn_decode

            o, state = gdn_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0, :, 0], beta[:, 0], state, idx)
            return o[:, None], state
        # the stored tile [dk, H·dv] viewed a head at a time for the jnp forms
        rows = jnp.swapaxes(_rows(state, idx, slot, b).reshape(b, dk, nh, dv), 1, 2)
        if t == 1:
            o, rows = kda_ops.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], rows)
            o = o[:, None]
        else:
            o, rows = kda_ops.kda_chunked(q, k, v, g, beta, rows)
        return o, _put_rows(state, jnp.swapaxes(rows, 1, 2).reshape(b, dk, nh * dv), idx, slot)

    o, state = _stack_by_group(rule, n_lanes, slot, state, q, k, v, g, beta)
    conv = _put_rows(conv, window.reshape(window.shape[0], -1), idx, put_at)
    gate = jax.nn.silu(_proj(h, lp["w_g"])).reshape(b, t, nh, dv)
    o = rms_norm(o, lp["o_norm"], cfg.norm_eps) * gate
    return _proj(o.reshape(b, t, nh * dv).astype(h.dtype), lp["wo"]), state, conv


def _attn_rotate(x, positions, cfg: ModelConfig, kind: str):
    """``x [B, T, n, hd]`` float32 with the rotary embedding of a ``kind``
    layer: a "swa" layer's plain rotate-half over the whole head
    (``cfg.swa_rope_theta``); a "full" layer's over the first
    ``cfg.rotary_dim`` dims, with YaRN's frequencies for a head that wide and
    cos and sin scaled (``ops/rope.apply_rope``). Unrotated where the kind's
    base is 0."""
    if kind == WINDOW_KIND:
        return apply_rope(x, positions, cfg.swa_rope_theta) if cfg.swa_rope_theta else x
    if not cfg.rope_theta:
        return x
    if cfg.rope_factor == 1.0 and cfg.rope_partial == 1.0 and cfg.rope_attention_factor == 1.0:
        return apply_rope(x, positions, cfg.rope_theta)
    with jax.named_scope("rope_partial"):
        inv_freq = None
        if cfg.rope_factor > 1.0:
            inv_freq = yarn_frequencies(
                cfg.rotary_dim, cfg.rope_theta, cfg.rope_factor, cfg.rope_original_max,
                cfg.rope_beta_fast, cfg.rope_beta_slow,
            )
        return apply_rope(
            x, positions, cfg.rope_theta, inv_freq=inv_freq, rotary_dim=cfg.rotary_dim,
            scale=cfg.rope_attention_factor,
        )


def _gated(o, h, wg, per_head: bool):
    """Attention's output ``o [B, T, H, hd]`` times a sigmoid of a linear
    function of the layer's normed input ``h``, as ``[B, T, H·hd]`` float32:
    ``wg [d, H]`` gates a head (``per_head``: Laguna), ``[d, H·hd]`` every
    channel of the output (the sparse layers; a "full" layer under
    ``attn_gate="full"``)."""
    b, t, nh, hd = o.shape
    with jax.named_scope("attn_gate"):
        o = o.astype(jnp.float32)
        if per_head:
            return (o * jax.nn.sigmoid(_proj(h, wg))[..., None]).reshape(b, t, nh * hd)
        return o.reshape(b, t, nh * hd) * jax.nn.sigmoid(_proj(h, wg))


def full_mixer(
    h, lp, cfg: ModelConfig, ck, cv, idx, slot, positions, valid, plan: HybridPlan, n_lanes: int = 0,
    kind: str = "full", arena_len: int | None = None,
):
    """``h [B, T, d]`` → softmax attention's output and the K/V stacks with
    this step's rows written at their positions (rows past S drop). The rows
    hold :func:`stored_kv_heads` heads; the query is padded to match and the
    padding's output dropped. A lane that does not step attends to one row
    instead of all S, like :func:`mla_mixer`'s; ``n_lanes`` as there.

    ``kind == "swa"``: ``ck``, ``cv`` are the ring leaves, a position's row is
    ``models/llama._ring_index`` (``arena_len``: the global leaf's length,
    where parked lanes sit and whose writes a ring drops) and a row sees its
    last ``cfg.window`` positions (the kernels' and the reference's
    ``window=``). The kind's own head count, rotary embedding and, under
    ``cfg.attn_gate``, a sigmoid gate on the output, a head or as wide as it
    (:func:`_gated`).

    Every model that has this mixer runs it as the body of its kind's
    0-or-1-trip loop (:func:`forward`'s ``of_kind``), where the projections
    stay beside what reads them: the barrier after them keeps the compiler
    from fusing the norm and the head split into the matmul's output and then
    wanting the weights the other way round (compiled for a described v5e,
    PR 51: it transposes the whole ``wq`` stack once a launch, 629 MB for
    Laguna's two)."""
    from ..ops import attention as attn_ops
    from .llama import _ring_index

    b, t, _ = h.shape
    nh, nkv, hd = cfg.heads_of(kind), cfg.n_kv_heads, cfg.head_dim
    group, stored = nh // nkv, ck.shape[3]
    q, k, v = lax.optimization_barrier((_proj(h, lp["wq"]), _proj(h, lp["wk"]), _proj(h, lp["wv"])))
    if cfg.qk_norm:  # over the whole projection, before the heads are split
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    q, k, v = q.reshape(b, t, nh, hd), k.reshape(b, t, nkv, hd), v.reshape(b, t, nkv, hd)
    q, k = _attn_rotate(q, positions, cfg, kind), _attn_rotate(k, positions, cfg, kind)
    heads = lambda a, n: jnp.pad(a.astype(h.dtype), [(0, 0), (0, 0), (0, n - a.shape[2]), (0, 0)])  # noqa: E731
    pallas = getattr(plan, kind + "_decode").startswith("pallas:")
    impl = attn_ops.pallas_dense if pallas else attn_ops._reference_dense
    at, kw = (lambda p: p), {}
    if kind == WINDOW_KIND:
        at, kw = (lambda p: _ring_index(p, ck.shape[2], arena_len)), {"window": cfg.window}

    def attend(q, positions, valid, slot):
        seen = jnp.where(valid, positions, 0) if positions.shape[1] == 1 else positions
        return impl(heads(q, stored * group), ck, cv, seen, None, idx, slot, **kw)[:, :, :nh]

    if n_lanes:
        ck = _put_groups(ck, heads(k, stored), idx, slot, positions, n_lanes, at)
        cv = _put_groups(cv, heads(v, stored), idx, slot, positions, n_lanes, at)
    else:
        lanes = jnp.arange(b)[:, None] + (0 if slot is None else slot)
        ck = ck.at[idx, lanes, at(positions)].set(heads(k, stored).astype(ck.dtype))
        cv = cv.at[idx, lanes, at(positions)].set(heads(v, stored).astype(cv.dtype))
    o = _by_group(attend, n_lanes, q, positions, valid, slot)
    o = _gated(o, h, lp["wg"], cfg.gate_form == "per_head") if cfg.attn_gate else o.reshape(b, t, nh * hd)
    return _proj(o.astype(h.dtype), lp["wo"]), ck, cv


def sparse_mixer(
    h, lp, cfg: ModelConfig, ck, cv, pooled, idx, slot, positions, valid, plan: HybridPlan, n_lanes: int = 0,
):
    """``h [B, T, d]`` → block-sparse attention's output, and the ``k``, ``v``
    and pooled-key stacks with this step's rows written (``ops/
    sparse_attention.py``). q and k take an RMSNorm a head, and the rotary
    embedding a "full" layer would (MiniCPM-SALA: none). A query whose
    context is at most ``cfg.sparse_dense_len`` rows reads all of it; past
    that it scores the lane's visible pooled keys (``sparse_index``), takes
    ``cfg.sparse_topk`` blocks (``sparse_select``) and reads those alone
    (``sparse_attend``): a lane's step by the plan's kernel or XLA's gather, a
    chunk's rows through a row-by-block mask. The output is gated by
    a sigmoid of the normed input as wide as itself. ``n_lanes`` as
    :func:`mla_mixer`: both groups' rows and pooled keys are written before
    either group is read."""
    from ..ops import attention as attn_ops

    b, t, _ = h.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    stored, sizes = ck.shape[3], sparse_ops.SparseSizes.of(cfg)
    q, k, v = lax.optimization_barrier((_proj(h, lp["wq"]), _proj(h, lp["wk"]), _proj(h, lp["wv"])))
    q = rms_norm(q.reshape(b, t, nh, hd), lp["q_norm"], cfg.norm_eps)
    k = rms_norm(k.reshape(b, t, nkv, hd), lp["k_norm"], cfg.norm_eps)
    v = v.reshape(b, t, nkv, hd)
    q, k = _attn_rotate(q, positions, cfg, "full"), _attn_rotate(k, positions, cfg, "full")
    heads = lambda a, n: jnp.pad(a.astype(h.dtype), [(0, 0), (0, 0), (0, n - a.shape[2]), (0, 0)])  # noqa: E731
    dense = attn_ops.pallas_dense if plan.sparse_decode.startswith("pallas:") else attn_ops._reference_dense
    listed = sparse_ops.blocks_step(kernel=plan.sparse_decode.endswith("pallas:sparse_decode"))

    def append(pooled, positions, valid, slot):  # the kernels a group's rows completed
        lanes = jnp.arange(positions.shape[0]) + (0 if slot is None else slot)
        return sparse_ops.append_pooled(
            pooled, ck, idx, lanes, positions[:, 0], jnp.sum(valid, axis=1), positions.shape[1], sizes)

    if n_lanes:
        ck = _put_groups(ck, heads(k, stored), idx, slot, positions, n_lanes)
        cv = _put_groups(cv, heads(v, stored), idx, slot, positions, n_lanes)
        (pos_c, pos_l), (val_c, val_l) = _groups(n_lanes, positions, valid)
        pooled = append(append(pooled, pos_c, val_c, slot), pos_l, val_l, None)
    else:
        lanes = jnp.arange(b)[:, None] + (0 if slot is None else slot)
        ck = ck.at[idx, lanes, positions].set(heads(k, stored).astype(ck.dtype))
        cv = cv.at[idx, lanes, positions].set(heads(v, stored).astype(cv.dtype))
        pooled = append(pooled, positions, valid, slot)

    def attend(q, positions, valid, slot):
        b, t = positions.shape
        lane = 0 if slot is None else slot
        with jax.named_scope("sparse_index"):
            scores = sparse_ops.block_scores(q, _rows(pooled, idx, slot, b), positions, sizes)
        with jax.named_scope("sparse_select"):
            blocks = sparse_ops.select_blocks(scores, positions, sizes)
        with jax.named_scope("sparse_attend"):
            if t > 1:
                mask = sparse_ops.rows_by_blocks(blocks, positions, scores.shape[-1], sizes.dense_len)
                last = jnp.max(jnp.where(valid, positions, 0))
                return sparse_ops.attend_masked(q, ck, cv, idx, lane, positions, last, mask, nkv, sizes.block)
            # a lane under ``dense_len`` reads its rows through the dense
            # kernel (the others hand it one row); a lane past it its blocks
            under = positions[:, 0] < sizes.dense_len
            seen = jnp.where(valid & under[:, None], positions, 0)
            o_dense = dense(heads(q, stored * (nh // nkv)), ck, cv, seen, None, idx, slot)[:, :, :nh]
            o_blocks = listed(q[:, 0], ck, cv, idx, lane, blocks[:, 0], positions[:, 0], nkv, sizes.block)
            return jnp.where(under[:, None, None, None], o_dense.astype(jnp.float32), o_blocks[:, None])

    o = _by_group(attend, n_lanes, q, positions, valid, slot)
    return _proj(_gated(o, h, lp["wg"], per_head=False).astype(h.dtype), lp["wo"]), ck, cv, pooled


def lightning_mixer(h, lp, cfg: ModelConfig, state, idx, slot, positions, valid, plan: HybridPlan, n_lanes: int = 0):
    """``h [B, T, d]`` → lightning attention's output, and the state stack
    ``[n, B, H, dk, dv]`` with layer ``idx``'s lanes stepped by the valid
    tokens (``ops/lightning.py``): an RMSNorm a head on q and k, rotate-half
    RoPE (``cfg.lightning_rope_theta``), q scaled by ``dk^-½``, a constant
    decay a head from the layer's ``slope``; one RMSNorm over the concatenated
    heads' output and a sigmoid gate as wide. No conv: the cache's ``conv`` is
    ``None``. ``n_lanes`` as :func:`kda_mixer`."""
    b, t, _ = h.shape
    nh, dk = cfg.kda_heads, cfg.kda_head_dim
    q, k, v = lax.optimization_barrier((_proj(h, lp["wq"]), _proj(h, lp["wk"]), _proj(h, lp["wv"])))
    q = rms_norm(q.reshape(b, t, nh, dk), lp["q_norm"], cfg.norm_eps)
    k = rms_norm(k.reshape(b, t, nh, dk), lp["k_norm"], cfg.norm_eps)
    v = v.reshape(b, t, nh, dk)
    if cfg.lightning_rope_theta:
        q, k = (apply_rope(a, positions, cfg.lightning_rope_theta) for a in (q, k))
    g = jnp.broadcast_to(-lp["slope"].astype(jnp.float32), (b, t, nh))
    g, k = lightning_ops.mask_inputs(g, k, valid)

    def rule(q, k, v, g, slot, state):
        b, t = g.shape[:2]
        rows = _rows(state, idx, slot, b)
        if t == 1:
            with jax.named_scope("lightning_step"):
                o, rows = lightning_ops.lightning_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], rows)
                o = o[:, None]
        else:
            with jax.named_scope("lightning_chunk"):
                o, rows = lightning_ops.lightning_chunked(q, k, v, g, rows)
        return o, _put_rows(state, rows, idx, slot)

    o, state = _stack_by_group(rule, n_lanes, slot, state, q * dk**-0.5, k, v, g)
    o = rms_norm(o.reshape(b, t, nh * dk), lp["o_norm"], cfg.norm_eps) * jax.nn.sigmoid(_proj(h, lp["wg"]))
    return _proj(o.astype(h.dtype), lp["wo"]), state


def _mla_rotate(x, positions, cfg: ModelConfig):
    """``x [B, T, n, r]`` float32 rotated by its tokens' positions with the
    model's frequencies and pairing (``ops/rope.py``)."""
    inv_freq = None
    if cfg.rope_factor > 1.0:
        inv_freq = yarn_frequencies(
            x.shape[-1], cfg.rope_theta, cfg.rope_factor, cfg.rope_original_max, cfg.rope_beta_fast, cfg.rope_beta_slow
        )
    return apply_rope(x, positions, cfg.rope_theta, interleave=cfg.rope_interleave, inv_freq=inv_freq)


def _mla_query(h, lp, cfg: ModelConfig, positions, in_loop: bool = False):
    """The layer's queries ``[B, T, H, nope + r]`` in the weights' dtype, as
    the absorption takes them. A full-rank ``wq`` with nothing after it
    (Kimi-Linear) is rounded where it leaves the matmul; otherwise the
    low-rank pair, the rotation of the last ``r`` dims and the scale by
    position are float32 and the query is rounded once, after them."""
    b, t, _ = h.shape
    shape = (b, t, cfg.n_heads, cfg.mla_nope_dim + cfg.mla_rope_dim)
    if cfg.mla_q_rank:
        with jax.named_scope("mla_q_lora"):
            c_q = rms_norm(_proj(h, lp["wq_a"]), lp["q_norm"], cfg.norm_eps).astype(h.dtype)
            q = _proj(c_q, lp["wq_b"])
    else:
        q = _proj(h, lp["wq"])
    if in_loop:
        q = lax.optimization_barrier(q)
    if not (cfg.mla_rotary or cfg.q_pos_scale_beta):
        return q.astype(h.dtype).reshape(shape)
    q = q.reshape(shape)
    with jax.named_scope("mla_rope"):
        if cfg.mla_rotary:
            q = jnp.concatenate(
                [q[..., : cfg.mla_nope_dim], _mla_rotate(q[..., cfg.mla_nope_dim :], positions, cfg)], axis=-1
            )
        if cfg.q_pos_scale_beta:
            # Llama-4's scale: 1 inside the original context, then a step a
            # whole multiple of it (the softmax sharpens as the context grows)
            past = (positions // cfg.rope_original_max).astype(jnp.float32)
            q = q * (1.0 + cfg.q_pos_scale_beta * jnp.log1p(past))[..., None, None]
    return q.astype(h.dtype)


def mla_mixer(
    h, lp, cfg: ModelConfig, latent, idx, slot, positions, valid, plan: HybridPlan, n_lanes: int = 0,
    in_loop: bool = False,
):
    """``h [B, T, d]`` (normed) → the mixer's output and the latent stack with
    this step's rows written at their positions (rows past S drop). A lane
    that does not step (``valid`` false: parked at the arena's last row)
    attends to one row instead of all S: its output is nobody's. Where the
    plan names the kernels, neither call shape slices a lane's row out of the
    stack or writes scores to HBM. Under ``cfg.mla_rotary`` the row's shared
    key dims are written ROTATED by the row's position, and the query's
    matching dims by the query's: their product depends on the distance, and
    the row absorbs like an unrotated one.

    ``n_lanes``: ``h`` is ``[1, T + B, d]``, a chunk's T rows for arena row
    ``slot`` and then one row for each of the arena's B lanes
    (:func:`forward`'s ``lanes``). The projections, the norms and the
    rotation run over all of them at once; both groups' rows are written
    before either is read, and each group's attention is the call its own
    launch would make: the chunk's over lane ``slot``, the lanes' over their
    own. ``in_loop``: the call is the body of this kind's 0-or-1-trip loop
    (:func:`forward`'s ``of_kind``) and takes :func:`full_mixer`'s barrier
    after the query's ``wq``; a model with this mixer alone (Mistral-Small-4)
    has no such loop, and its programs stay what they were."""
    b, t, _ = h.shape
    nh, rank, nope = cfg.n_heads, cfg.mla_kv_rank, cfg.mla_nope_dim
    q = _mla_query(h, lp, cfg, positions, in_loop)
    ckv = _proj(h, lp["wkva"])
    pad = jnp.zeros((b, t, latent.shape[-1] - ckv.shape[-1]), ckv.dtype)
    c, k_s = rms_norm(ckv[..., :rank], lp["kv_norm"], cfg.norm_eps), ckv[..., rank:]
    if cfg.mla_rotary:
        with jax.named_scope("mla_rope"):
            k_s = _mla_rotate(k_s[:, :, None], positions, cfg)[:, :, 0]
    row = jnp.concatenate([c, k_s, pad], -1)
    if n_lanes:
        latent = _put_groups(latent, row, idx, slot, positions, n_lanes)
    else:
        lanes = jnp.arange(b)[:, None] + (0 if slot is None else slot)
        latent = latent.at[idx, lanes, positions].set(row.astype(latent.dtype))
    w_kvb = dequant(lp["wkvb"]).reshape(rank, nh, nope + cfg.mla_v_dim)
    q_full = mla_ops.absorb_query(q, w_kvb, nope)  # float32: rounded once, where the scores take it
    q_full = jnp.pad(q_full, [(0, 0)] * 3 + [(0, pad.shape[-1])])  # zeros against the padding
    scale = (nope + cfg.mla_rope_dim) ** -0.5 * cfg.softmax_mscale**2

    def attend(q_full, positions, valid, slot):
        b, t = positions.shape
        if t == 1 and plan.mla_decode == "pallas_mla_decode":
            from ..ops.pallas_mla import mla_decode

            seen = jnp.where(valid[:, 0], positions[:, 0], 0)
            return mla_decode(q_full[:, 0], latent, seen, idx, 0 if slot is None else slot,
                              scale=scale, rank=rank)[:, None]
        if t > 1 and plan.mla_prefill == "pallas_mla_prefill":
            from ..ops.pallas_mla import mla_prefill

            # a bucket's padding sees nothing: a tile of it costs nothing
            seen = jnp.where(valid, positions, -1)
            return mla_prefill(q_full, latent, seen, idx, 0 if slot is None else slot, scale=scale, rank=rank)
        return mla_ops.attend(q_full, _rows(latent, idx, slot, b), positions, scale, rank)

    o_lat = _by_group(attend, n_lanes, q_full, positions, valid, slot)
    o = jnp.einsum(
        "bthr,rhv->bthv", o_lat.astype(h.dtype), w_kvb[..., nope:], preferred_element_type=jnp.float32
    )
    return _proj(o.reshape(b, t, nh * cfg.mla_v_dim).astype(h.dtype), lp["wo"]), latent


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T]
    positions: jnp.ndarray,  # [B, T]
    cache: HybridCache | None = None,
    plan: HybridPlan | None = None,
    moe_impl=None,
    slot=None,
    valid: jnp.ndarray | None = None,
    lanes: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    last: jnp.ndarray | None = None,
):
    """``models/llama.forward`` for a config with ``layer_kinds``: logits
    ``[B, T, V]`` and the updated cache. Without a cache: the full causal
    forward from zero state (positions have to be ``0 .. T − 1``). ``valid
    [B, T]``: the real rows, a prefix of each sequence (module docstring);
    absent, a ``T = 1`` call through a cache follows the cache's controls
    and any other call steps every token.

    ``lanes = (tokens [B, 1], positions [B, 1])`` and ``last``, as
    ``models/llama.forward`` takes them: ``tokens [1, T]`` is a lane's chunk
    at arena row ``slot`` (``valid`` its real rows) and ``lanes`` one decode
    step of each of the arena's B lanes, which follows the cache's controls
    as a ``T = 1`` call does. The ``T + B`` rows go together through
    everything that reads weights; a lane that does not step writes its row
    where it stands (the arena's last row, where it is parked), attends to
    one row and is routed to no expert. The logits are ``[1 + B, V]``: the
    chunk's row ``last``, then the lanes'. The chunk's own lane is one of the
    B: the engine parks it at the arena's last row while it prefills, at or
    past its ``stop``, so its step is not valid and a linear mixer's state and
    conv of that lane are the chunk's alone."""
    from .llama import _moe_mlp, _moe_mlp_sorted, check_ring_launch, moe_sorts

    b, t = tokens.shape
    plan = plan if plan is not None else plan_hybrid(cfg)
    keep_cache = cache is not None
    if cache is None:
        # a ring drops what is written at the arena's last row (a parked
        # lane's): the arena of a model with one is a row longer than the tokens
        rows = t + 1 if cfg.n_window else -(-t // cfg.sparse_block) * cfg.sparse_block if cfg.n_sparse else t
        cache = init_cache(cfg, b, rows, params["final_norm"].dtype)
    stop = cache.stop
    if cache.wk is not None:
        check_ring_launch(cache.wk.shape[2], cache.k.shape[2], cfg.window, t)

    def controls(tokens, positions, lanes):
        """A one-token step's ``valid [n, 1]`` from the cache's controls, and
        ``stop`` with the lanes a fed EOS closes."""
        lane_stop, lane_eos = stop[lanes], cache.eos[lanes]
        is_eos = tokens[:, 0] == lane_eos
        open_ = positions[:, 0] < lane_stop
        return (open_ & ~is_eos)[:, None], stop.at[lanes].set(jnp.where(open_ & is_eos, 0, lane_stop))

    n_lanes = 0
    if lanes is not None:
        if not keep_cache or slot is None or b != 1:
            raise ValueError("lanes ride one lane's chunk at ``slot`` of the cache")
        lane_tokens, lane_positions = lanes
        n_lanes = lane_tokens.shape[0]
        lane_valid, stop = controls(lane_tokens, lane_positions, jnp.arange(n_lanes))
        valid = jnp.ones((1, t), bool) if valid is None else valid
        tokens, positions, valid = (
            jnp.concatenate([a, g.reshape(1, n_lanes)], axis=1)
            for a, g in ((tokens, lane_tokens), (positions, lane_positions), (valid, lane_valid))
        )
    elif valid is None:
        if keep_cache and t == 1:
            valid, stop = controls(tokens, positions, jnp.arange(b) + (0 if slot is None else slot))
        else:
            valid = jnp.ones((b, t), bool)

    # the residual stream stays float32 through the 27 layers (bfloat16 at
    # every add reads 1.8 % at 5 layers against the control's 0.9 %, my chip
    # run, PR 30); what a matmul takes is rounded to the weights' dtype once
    act = params["final_norm"].dtype
    x = embed_lookup(params["embed"], tokens).astype(jnp.float32)
    if cfg.embed_scale != 1.0:
        x = x * cfg.embed_scale
    # a layer's index within its kind's stacks and leaves ("swa" layers are
    # the ones that are not positional where the model has no linear mixer)
    kinds = np.array([k in POSITIONAL_KINDS for k in cfg.layer_kinds])
    mixer_idx = np.where(kinds, np.cumsum(kinds) - 1, np.cumsum(~kinds) - 1).astype(np.int32)
    dense = np.arange(cfg.n_layers) < cfg.n_dense_layers
    ffn_idx = np.where(dense, np.arange(cfg.n_layers), np.arange(cfg.n_layers) - cfg.n_dense_layers)
    moe_stack = params.get("moe")
    experts = None
    only = None
    if moe_stack is not None and moe_impl is None and moe_sorts(cfg, params, b * t + n_lanes):
        experts = stacked_experts(moe_stack)
        moe_stack = {k: v for k, v in moe_stack.items() if k not in EXPERT_WEIGHTS}
        if n_lanes:
            # the chunk's rows as a plain chunk routes them, and the lanes
            # that step: any other lane's row is nobody's (``_moe_mlp_sorted``)
            only = jnp.concatenate([jnp.ones(t, bool), valid[0, t:]])
    # what the shared MoE paths take dequantised (the router's logits and the
    # shared expert are computed here, from the int8 leaves)
    routed = {k: v for k, v in (moe_stack or {}).items() if k != "router" and not k.startswith("ws_")}
    shared = {k: v for k, v in (moe_stack or {}).items() if k.startswith("ws_")}
    lin_kind, pos_kind = cfg.linear_kind, cfg.positional_kind

    def mixer(h, rows, ring, state, conv, is_pos, idx):
        """``rows``: the positional leaves (``(latent,)``, ``(k, v)`` or, with
        the pooled keys, ``(k, v, ck)``);
        ``ring``: the window layers' (``(wk, wv)`` or nothing)."""

        def linear(h, idx, state, conv):
            lp = _layer_of(params[lin_kind], idx)
            if lin_kind == "lightning":  # no conv: the leaf is None and stays so
                return *lightning_mixer(h, lp, cfg, state, idx, slot, positions, valid, plan, n_lanes), conv
            fn = kda_mixer if lin_kind == "kda" else gdn_mixer
            return fn(h, lp, cfg, state, conv, idx, slot, valid, plan, n_lanes)

        def positional(h, idx, *rows, in_loop=False):
            lp = _layer_of(params[pos_kind], idx)
            if pos_kind == "mla":
                return mla_mixer(h, lp, cfg, *rows, idx, slot, positions, valid, plan, n_lanes, in_loop=in_loop)
            fn = sparse_mixer if pos_kind == "sparse" else full_mixer
            return fn(h, lp, cfg, *rows, idx, slot, positions, valid, plan, n_lanes)

        def windowed(h, idx, *ring):
            with jax.named_scope("attn_window"):
                return full_mixer(
                    h, _layer_of(params[WINDOW_KIND], idx), cfg, *ring, idx, slot, positions, valid, plan,
                    n_lanes, kind=WINDOW_KIND, arena_len=rows[0].shape[2],
                )

        def on_global(h, idx, *rows):
            with jax.named_scope("attn_global"):
                return positional(h, idx, *rows)

        def of_kind(fn, trips, y, *leaves):
            """``fn``, one kind's mixer, as a loop of 0 or 1 trips over the
            leaves it updates; where it does not trip, ``(y, *leaves)`` as
            they came. A ``lax.cond`` would do, but XLA copies what a branch
            passes through untouched: the other kind's whole stack, every
            layer (compiled for a described v5e: 2.7 GB of state copied in
            each MLA layer). A while loop's carry stays one buffer whether it
            trips or not, like the layer scan's own. What the body computes
            from ``h`` and from layer ``idx`` of its kind's weight stacks
            depends on nothing the loop carries, so XLA lifts it out of the
            loop into the scan's body, where it runs in the OTHER kind's
            layers too (compiled for a described v5e, PR 51: both kinds'
            input projections and a copy of both ``wo`` slices in every layer
            of three models). The barrier ties ``h`` and ``idx`` to the carry,
            so a kind's work stays in its loop; it changes no value
            (``tests/test_tpu_compile.py`` holds the first,
            ``tests/test_hybrid_values.py`` the second)."""

            def body(_, carry):
                leaves, h_in, i = lax.optimization_barrier((carry[1:], h, idx))
                return fn(h_in, i, *leaves)

            return lax.fori_loop(0, trips, body, (y, *leaves))

        if pos_kind is None:
            y, state, conv = linear(h, idx, state, conv)
        elif lin_kind is None and not ring:
            y, *rows = positional(h, idx, *rows)
        else:
            # two kinds of mixer: full beside swa, or a linear one beside a positional one
            trips = is_pos.astype(jnp.int32)
            if ring:
                y, *rows = of_kind(on_global, trips, jnp.zeros(h.shape, jnp.float32), *rows)
                y, *ring = of_kind(windowed, 1 - trips, y, *ring)
            else:
                y, state, conv = of_kind(linear, 1 - trips, jnp.zeros(h.shape, jnp.float32), state, conv)
                y, *rows = of_kind(functools.partial(positional, in_loop=True), trips, y, *rows)
        return y, tuple(rows), tuple(ring), state, conv

    def ffn(h32, is_dense, idx):
        def dense_ffn(h32):
            h = h32.astype(act)
            lp = _layer_of(params["dense"], idx)
            return _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])

        def moe_ffn(h32):
            h = h32.astype(act)
            lp = _layer_of(routed, idx, dense=True)
            logits = router_logits(h32, _layer_of({"router": moe_stack["router"]}, idx)["router"])
            if experts is not None:
                y = _moe_mlp_sorted(h, lp, cfg, experts, idx, logits=logits, routed=only)
            else:
                y = moe_impl(h, lp) if moe_impl is not None else _moe_mlp(h, lp, cfg, logits=logits)
            if cfg.n_shared_experts:
                lp = _layer_of(shared, idx)
                with jax.named_scope("moe_shared_expert"):
                    y = y.astype(jnp.float32) + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
            return y.astype(jnp.float32)

        if not cfg.n_dense_layers:
            return moe_ffn(h32)
        if cfg.n_dense_layers >= cfg.n_layers:
            return dense_ffn(h32)
        return lax.cond(is_dense, dense_ffn, moe_ffn, h32)

    def layer_step(carry, xs):
        x, rows, ring, state, conv = carry
        attn_norm, mlp_norm, is_pos, m_idx, is_dense, f_idx = xs
        if cfg.post_norm:
            # the OLMo-2 placement: each sublayer reads the stream as it is
            # and the residual adds its NORMED output
            y, rows, ring, state, conv = mixer(x.astype(act), rows, ring, state, conv, is_pos, m_idx)
            x = x + rms_norm(y.astype(jnp.float32), attn_norm, cfg.norm_eps)
            x = x + rms_norm(ffn(x, is_dense, f_idx).astype(jnp.float32), mlp_norm, cfg.norm_eps)
            return (x, rows, ring, state, conv), None
        h = rms_norm(x, attn_norm, cfg.norm_eps).astype(act)
        y, rows, ring, state, conv = mixer(h, rows, ring, state, conv, is_pos, m_idx)
        y2 = lambda x: ffn(rms_norm(x, mlp_norm, cfg.norm_eps), is_dense, f_idx).astype(jnp.float32)  # noqa: E731
        if cfg.residual_scale != 1.0:  # µP: every sublayer's output scaled before the residual adds it
            x = x + y.astype(jnp.float32) * cfg.residual_scale
            return (x + y2(x) * cfg.residual_scale, rows, ring, state, conv), None
        x = x + y.astype(jnp.float32)
        x = x + y2(x)
        return (x, rows, ring, state, conv), None

    xs = (
        params["layers"]["attn_norm"], params["layers"]["mlp_norm"],
        jnp.asarray(kinds), jnp.asarray(mixer_idx), jnp.asarray(dense), jnp.asarray(ffn_idx, jnp.int32),
    )
    carried = cache.carried()
    (x, rows, ring, state, conv), _ = lax.scan(
        layer_step, (x, tuple(carried.values()), cache.ring(), cache.state, cache.conv), xs
    )
    if n_lanes:
        # the head's rows: the chunk's ``last`` and the lanes', not T + B
        x = jnp.concatenate([lax.dynamic_slice_in_dim(x[0], last, 1, 0), x[0, t:]], axis=0)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.logit_divisor != 1.0:
        x = x / cfg.logit_divisor
    logits = _proj(x.astype(act), params["lm_head"])
    if not keep_cache:
        return logits, None
    named = dict(zip(carried, rows))
    named.update(zip(cache.RING, ring))
    return logits, cache._replace(**named, state=state, conv=conv, stop=stop)
