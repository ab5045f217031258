"""The hybrid block (Kimi-Linear): two mixers and two FFNs through the one
forward.

``models/llama.forward`` hands a config with ``layer_kinds`` to
:func:`forward` here; the engine calls one ``forward`` and never learns a
layer's kind. A layer is ``x += mixer(rmsnorm(x)); x += ffn(rmsnorm(x))``
with

- the mixer **KDA** (``ops/kda.py``: gated delta-rule linear attention; q, k,
  v through a short causal depthwise conv and SiLU, l2-normalised q and k, a
  per-channel decay from a low-rank pair, a sigmoid β, a head-wise RMSNorm
  and a low-rank sigmoid gate on the output) or **MLA** (``ops/mla.py``:
  latent attention with no rotary embedding anywhere; the cache row is the
  normalised latent and the shared key dimensions, the up-projection
  absorbed into query and output);
- the FFN a dense SwiGLU (the first ``n_dense_layers``) or the MoE of
  ``models/llama.py`` with the sigmoid router rule, a shared expert and the
  experts this chip holds.

**Weights are stacked per kind** (``params["kda"]`` ``[n_kda, …]``,
``["mla"]``, ``["dense"]``, ``["moe"]``; the two pre-norm vectors of every
layer in ``["layers"]``) and **each kind of block is traced once**: one
``lax.scan`` over all layers whose body switches mixer and FFN by the
layer's kind (``lax.cond``) and indexes the per-kind stacks, and the caches,
by the layer's index within its kind.

**The cache is a pytree this module builds** (:class:`HybridCache`,
``llama.init_cache``): positional rows ``latent [n_mla, B, S, 640]`` (576 values and padding to whole lane tiles; read
up to a lane's position, like a K/V arena) and per-lane state ``state
[n_kda, B, H, dk, dv]`` float32 and ``conv [n_kda, B, (W − 1)·3·H·dk]`` —
which cannot be truncated, rewound or overwritten harmlessly. All ride in
the scan's carry and are updated in place.

**Masking is part of the mathematics.** A token that is not valid leaves
state and conv untouched (β = 0, g = 0, conv not shifted). Prefill says
which rows of its bucket are real (``valid``). A decode step (``T = 1``,
no ``valid``) reads it from two per-lane control leaves the cache carries:
a lane steps its state while ``position < stop[lane]`` and the token fed is
not ``eos[lane]`` (a fed EOS closes the lane: ``stop = 0``). The engine sets
both when it admits a request (``admit_lane``): the last token a request
generates is never fed, so a parked or idle lane, and the steps a pipelined
chunk runs past a request's end, cannot touch a session's state.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import kda as kda_ops
from ..ops import mla as mla_ops
from ..ops.moe import EXPERT_WEIGHTS, stacked_experts
from ..ops.norms import rms_norm
from ..ops.quant import QTensor, dequant, embed_lookup
from .configs import ModelConfig

NO_STOP = np.iinfo(np.int32).max
L2_EPS = 1e-6


class HybridCache(NamedTuple):
    """``latent`` is positional (rows up to a lane's position); ``state`` and
    ``conv`` are per-lane; ``stop`` and ``eos`` are the per-lane decode
    controls (module docstring)."""

    latent: jnp.ndarray  # [n_mla, B, S, latent_width]: R + r values, zero padding
    state: jnp.ndarray  # [n_kda, B, H, dk, dv] float32
    conv: jnp.ndarray  # [n_kda, B, (W - 1)·3·H·dk]: the last W − 1 conv inputs, row after row
    stop: jnp.ndarray  # [B] int32
    eos: jnp.ndarray  # [B] int32 (-1: no token closes the lane)

    POSITIONAL = ("latent",)


def latent_width(cfg: ModelConfig) -> int:
    """Columns of a stored latent row: the ``R + r`` values (576 published)
    and zeros up to whole 128-lane tiles (640). Unpadded, the TPU keeps the
    arena position-minor to save the padding itself, and every step program
    that hands it to a kernel relayouts it on the way in and out (compiled
    for a described v5e: two 2.1 GB copies a launch)."""
    return -(-(cfg.mla_kv_rank + cfg.mla_rope_dim) // 128) * 128


def init_cache(
    cfg: ModelConfig, lanes: int, max_seq: int, dtype=jnp.bfloat16, live: bool = True
) -> HybridCache:
    """A zeroed cache. ``live``: every lane steps (direct callers, tests);
    an engine starts its lanes closed (``stop = 0``) and opens one when it
    admits a request."""
    h, dk = cfg.kda_heads, cfg.kda_head_dim
    return HybridCache(
        latent=jnp.zeros((cfg.n_mla, lanes, max_seq, latent_width(cfg)), dtype),
        state=jnp.zeros((cfg.n_kda, lanes, h, dk, dk), jnp.float32),
        # the W − 1 rows of a lane side by side: a dimension of 3 next to the
        # channels would be padded to a whole sublane tile (or, minor-most,
        # to 128 lanes: compiled for a described v5e, 94 MB became 3.75 GB)
        conv=jnp.zeros((cfg.n_kda, lanes, (cfg.kda_conv - 1) * 3 * h * dk), dtype),
        stop=jnp.full((lanes,), NO_STOP if live else 0, jnp.int32),
        eos=jnp.full((lanes,), -1, jnp.int32),
    )


# -- the cache manager's three moves on a lane --------------------------------


def admit_lane(cache: HybridCache, lane, fresh, stop, eos) -> HybridCache:
    """Open ``lane`` for a request: its decode may step the state up to
    position ``stop`` (exclusive) and closes on ``eos``. ``fresh``: a new
    context starts from zero state (the latent rows need no reset: they are
    read only up to the position)."""
    keep = jnp.where(fresh, 0.0, 1.0)
    lane_of = lambda a: lax.dynamic_slice_in_dim(a, lane, 1, axis=1)  # noqa: E731
    put = lambda a, v: lax.dynamic_update_slice_in_dim(a, v, lane, axis=1)  # noqa: E731
    return cache._replace(
        state=put(cache.state, lane_of(cache.state) * keep),
        conv=put(cache.conv, lane_of(cache.conv) * keep.astype(cache.conv.dtype)),
        stop=cache.stop.at[lane].set(stop),
        eos=cache.eos.at[lane].set(eos),
    )


def snapshot_lane(cache: HybridCache, lane, bucket: int) -> dict:
    """A lane's leaves by name: the positional rows ``[:, :bucket]``, the
    per-lane state whole."""
    lane_of = lambda a: lax.dynamic_index_in_dim(a, lane, axis=1, keepdims=False)  # noqa: E731
    return {
        "latent": lane_of(cache.latent)[:, :bucket],
        "state": lane_of(cache.state),
        "conv": lane_of(cache.conv),
    }


def restore_lane(cache: HybridCache, lane, leaves: dict) -> HybridCache:
    """Write a snapshot's leaves into ``lane`` (positional rows from 0) and
    close the lane until a request is admitted."""

    def put(arena, value):
        start = (0, lane) + (0,) * (arena.ndim - 2)
        return lax.dynamic_update_slice(arena, value[:, None].astype(arena.dtype), start)

    return cache._replace(
        latent=put(cache.latent, leaves["latent"]),
        state=put(cache.state, leaves["state"]),
        conv=put(cache.conv, leaves["conv"]),
        stop=cache.stop.at[lane].set(0),
    )


# -- the kernels a step traces -------------------------------------------------


class HybridPlan(NamedTuple):
    """Which implementation each mechanism's calls trace, chosen once (an
    engine chooses at build and reports it)."""

    kda_decode: str
    kda_prefill: str
    mla_decode: str
    mla_prefill: str
    reason: str

    def describe(self) -> dict:
        return {**self._asdict(), "prefill": self.mla_prefill, "decode": self.mla_decode,
                "arena": "stack+layer"}


def plan_hybrid(cfg: ModelConfig, use_pallas: bool | None = None) -> HybridPlan:
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    aligned = cfg.kda_head_dim % 128 == 0 and cfg.kda_heads % 8 == 0
    if use_pallas and aligned:
        return HybridPlan(
            "pallas_kda_decode", "xla_chunked", "pallas_mla_decode", "xla_absorbed",
            "tpu backend; state and latent stacks read where they lie",
        )
    why = "no tpu backend" if not use_pallas else "KDA heads not (8, 128)-aligned"
    return HybridPlan("xla_step", "xla_chunked", "xla_absorbed", "xla_absorbed", why)


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
        "mla": {
            "wq": ((nm, d, cfg.n_heads * qk), True),
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
            "router_bias": ((ne, cfg.n_experts), False),
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


def vector_values(name: str, shape: tuple, key, dtype):
    """The dense vectors of a hybrid model, seeded: norms at one; ``a_log``
    and ``dt_bias`` drawn so that a token's decays spread over about (0.5,
    0.999) (``−g = exp(a_log) · softplus(dt_bias + small)``: a reference that
    drops the gate is far off); conv filters of order ½; the selection bias
    non-zero, so that choosing by ``s + b`` and weighing by ``s`` differ."""
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
            name: w(next(keys), shape) if matrix else vector_values(name, shape, next(keys), dtype)
            for name, (shape, matrix) in group.items()
        }
        for g, group in shapes.items()
    }
    out["embed"] = w(next(keys), (cfg.vocab_size, cfg.dim))
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


def kda_mixer(h, lp, cfg: ModelConfig, state, conv, idx, slot, valid, plan: HybridPlan):
    """``h [B, T, d]`` (normed) → the mixer's output, and the state and conv
    stacks with layer ``idx``'s lanes stepped by the valid tokens."""
    b, t, _ = h.shape
    nh, dk = cfg.kda_heads, cfg.kda_head_dim
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
    conv_rows = _rows(conv, idx, slot, b).reshape(b, cfg.kda_conv - 1, 3 * nh * dk)
    qkv, new_conv = kda_ops.causal_conv(_proj(h, lp["wqkv"]), conv_rows, lp["conv"], n_valid)
    q, k, v = jnp.split(jax.nn.silu(qkv).reshape(b, t, 3, nh, dk), 3, axis=2)
    q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]
    q = _l2norm(q) * dk**-0.5
    k = _l2norm(k)
    decay_in = _proj(_proj(h, lp["w_fa"]).astype(h.dtype), lp["w_fb"]) + lp["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(lp["a_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(decay_in).reshape(b, t, nh, dk)
    beta = jax.nn.sigmoid(_proj(h, lp["w_beta"]))
    g, beta = kda_ops.mask_inputs(g, beta, valid)
    if t == 1 and plan.kda_decode == "pallas_kda_decode" and slot is None:
        from ..ops.pallas_kda import kda_decode

        o, state = kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state, idx)
        o = o[:, None]
    else:
        rows = _rows(state, idx, slot, b)
        if t == 1:
            o, rows = kda_ops.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], rows)
            o = o[:, None]
        else:
            o, rows = kda_ops.kda_chunked(q, k, v, g, beta, rows)
        state = _put_rows(state, rows, idx, slot)
    conv = _put_rows(conv, new_conv.reshape(b, -1), idx, slot)
    gate = jax.nn.sigmoid(_proj(_proj(h, lp["w_ga"]).astype(h.dtype), lp["w_gb"])).reshape(b, t, nh, dk)
    o = rms_norm(o, lp["o_norm"], cfg.norm_eps) * gate
    return _proj(o.reshape(b, t, nh * dk).astype(h.dtype), lp["wo"]), state, conv


def mla_mixer(h, lp, cfg: ModelConfig, latent, idx, slot, positions, valid, plan: HybridPlan):
    """``h [B, T, d]`` (normed) → the mixer's output and the latent stack with
    this step's rows written at their positions (rows past S drop). A lane
    that does not step (``valid`` false: parked at the arena's last row)
    attends to one row instead of all S: its output is nobody's."""
    b, t, _ = h.shape
    nh, rank, nope = cfg.n_heads, cfg.mla_kv_rank, cfg.mla_nope_dim
    q = _proj(h, lp["wq"]).astype(h.dtype).reshape(b, t, nh, nope + cfg.mla_rope_dim)
    ckv = _proj(h, lp["wkva"])
    pad = jnp.zeros((b, t, latent.shape[-1] - ckv.shape[-1]), ckv.dtype)
    row = jnp.concatenate([rms_norm(ckv[..., :rank], lp["kv_norm"], cfg.norm_eps), ckv[..., rank:], pad], -1)
    lanes = jnp.arange(b)[:, None] + (0 if slot is None else slot)
    latent = latent.at[idx, lanes, positions].set(row.astype(latent.dtype))
    w_kvb = dequant(lp["wkvb"]).reshape(rank, nh, nope + cfg.mla_v_dim)
    q_full = mla_ops.absorb_query(q, w_kvb, nope)  # float32: rounded once, where the scores take it
    q_full = jnp.pad(q_full, [(0, 0)] * 3 + [(0, pad.shape[-1])])  # zeros against the padding
    scale = (nope + cfg.mla_rope_dim) ** -0.5
    if t == 1 and plan.mla_decode == "pallas_mla_decode":
        from ..ops.pallas_mla import mla_decode

        seen = jnp.where(valid[:, 0], positions[:, 0], 0)
        o_lat = mla_decode(q_full[:, 0], latent, seen, idx, 0 if slot is None else slot,
                           scale=scale, rank=rank)[:, None]
    else:
        o_lat = mla_ops.attend(q_full, _rows(latent, idx, slot, b), positions, scale, rank)
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
):
    """``models/llama.forward`` for a config with ``layer_kinds``: logits
    ``[B, T, V]`` and the updated cache. Without a cache: the full causal
    forward from zero state (positions have to be ``0 .. T − 1``). ``valid
    [B, T]``: the real rows, a prefix of each sequence (module docstring);
    absent, a ``T = 1`` call through a cache follows the cache's controls
    and any other call steps every token."""
    from .llama import _moe_mlp, _moe_mlp_sorted, moe_sorts

    b, t = tokens.shape
    plan = plan if plan is not None else plan_hybrid(cfg)
    keep_cache = cache is not None
    if cache is None:
        cache = init_cache(cfg, b, t, params["final_norm"].dtype)
    stop = cache.stop
    if valid is None:
        if keep_cache and t == 1:
            lanes = jnp.arange(b) + (0 if slot is None else slot)
            lane_stop, lane_eos = stop[lanes], cache.eos[lanes]
            is_eos = tokens[:, 0] == lane_eos
            open_ = positions[:, 0] < lane_stop
            valid = (open_ & ~is_eos)[:, None]
            stop = stop.at[lanes].set(jnp.where(open_ & is_eos, 0, lane_stop))
        else:
            valid = jnp.ones((b, t), bool)

    # the residual stream stays float32 through the 27 layers (bfloat16 at
    # every add reads 1.8 % at 5 layers against the control's 0.9 %, my chip
    # run, PR 30); what a matmul takes is rounded to the weights' dtype once
    act = params["final_norm"].dtype
    x = embed_lookup(params["embed"], tokens).astype(jnp.float32)
    kinds = np.array([k == "mla" for k in cfg.layer_kinds])
    mixer_idx = np.where(kinds, np.cumsum(kinds) - 1, np.cumsum(~kinds) - 1).astype(np.int32)
    dense = np.arange(cfg.n_layers) < cfg.n_dense_layers
    ffn_idx = np.where(dense, np.arange(cfg.n_layers), np.arange(cfg.n_layers) - cfg.n_dense_layers)
    moe_stack = params.get("moe")
    experts = None
    if moe_stack is not None and moe_impl is None and moe_sorts(cfg, params, b * t):
        experts = stacked_experts(moe_stack)
        moe_stack = {k: v for k, v in moe_stack.items() if k not in EXPERT_WEIGHTS}
    # what the shared MoE paths take dequantised (the router's logits and the
    # shared expert are computed here, from the int8 leaves)
    routed = {k: v for k, v in (moe_stack or {}).items() if k != "router" and not k.startswith("ws_")}
    shared = {k: v for k, v in (moe_stack or {}).items() if k.startswith("ws_")}

    def mixer(h, latent, state, conv, is_mla, idx):
        def kda(state, conv):
            return kda_mixer(h, _layer_of(params["kda"], idx), cfg, state, conv, idx, slot, valid, plan)

        def mla(latent):
            return mla_mixer(h, _layer_of(params["mla"], idx), cfg, latent, idx, slot, positions, valid, plan)

        if not cfg.n_mla:
            y, state, conv = kda(state, conv)
        elif not cfg.n_kda:
            y, latent = mla(latent)
        else:
            # Either mixer as a loop of 0 or 1 trips over the stacks it
            # updates. A ``lax.cond`` would do, but XLA copies what a branch
            # passes through untouched: the other kind's whole stack, every
            # layer (compiled for a described v5e: 2.7 GB of state copied in
            # each MLA layer). A while loop's carry stays one buffer whether
            # it trips or not, like the layer scan's own.
            trips = is_mla.astype(jnp.int32)
            y, state, conv = lax.fori_loop(
                0, 1 - trips, lambda _, c: kda(c[1], c[2]), (jnp.zeros(h.shape, jnp.float32), state, conv)
            )
            y, latent = lax.fori_loop(0, trips, lambda _, c: mla(c[1]), (y, latent))
        return y, latent, state, conv

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
                y = _moe_mlp_sorted(h, lp, cfg, experts, idx, logits=logits)
            else:
                y = moe_impl(h, lp) if moe_impl is not None else _moe_mlp(h, lp, cfg, logits=logits)
            if cfg.n_shared_experts:
                lp = _layer_of(shared, idx)
                y = y.astype(jnp.float32) + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
            return y.astype(jnp.float32)

        if not cfg.n_dense_layers:
            return moe_ffn(h32)
        if cfg.n_dense_layers >= cfg.n_layers:
            return dense_ffn(h32)
        return lax.cond(is_dense, dense_ffn, moe_ffn, h32)

    def layer_step(carry, xs):
        x, latent, state, conv = carry
        attn_norm, mlp_norm, is_mla, m_idx, is_dense, f_idx = xs
        h = rms_norm(x, attn_norm, cfg.norm_eps).astype(act)
        y, latent, state, conv = mixer(h, latent, state, conv, is_mla, m_idx)
        x = x + y.astype(jnp.float32)
        x = x + ffn(rms_norm(x, mlp_norm, cfg.norm_eps), is_dense, f_idx).astype(jnp.float32)
        return (x, latent, state, conv), None

    xs = (
        params["layers"]["attn_norm"], params["layers"]["mlp_norm"],
        jnp.asarray(kinds), jnp.asarray(mixer_idx), jnp.asarray(dense), jnp.asarray(ffn_idx, jnp.int32),
    )
    (x, latent, state, conv), _ = lax.scan(layer_step, (x, cache.latent, cache.state, cache.conv), xs)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(act)
    logits = _proj(x, params["lm_head"])
    new_cache = HybridCache(latent, state, conv, stop, cache.eos) if keep_cache else None
    return logits, new_cache
