"""The ``solar_open2`` family: Solar-Open2's block as
``agentainer_tpu/models/hybrid.py`` computes it (KDA gated delta-rule linear
attention with ``β = 2 · sigmoid`` beside NoPE grouped-query softmax
attention whose output a sigmoid as wide as itself gates, every layer
sigmoid-routed experts with a selection bias, a shared expert, and the chip's
share of the routed experts). ``families/llama.py`` says what a family
answers; the plain reference is ``families/solar_open2_reference.py``. Beside
the usual answers: ``state_bytes_per_lane``, the bytes a call of the state
kernel needs (``kda_decode_bytes``), the experts a step's tokens choose
(``experts_chosen``), the bytes a decode step must move and the least time it
can take (``decode_step_bytes``, ``decode_step_floor_s``). Nothing heavy is
imported at module level.

A configuration file of this family holds the published ``config.json`` keys
as run; ``n_routed_experts`` counts the experts **held here**,
``experts_published`` the router's width, ``expert_parallel`` the deployment
(``ep`` chips share each layer; this is chip ``chip``, holding experts ``chip ·
held ..``). ``gqa_layers`` is the published list whole: the layers under
``num_hidden_layers`` are the ones here.
"""

from __future__ import annotations

REHEARSAL_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "num_hidden_layers": 5, "n_routed_experts": 2, "experts_published": 8, "num_experts_per_tok": 2,
    "expert_parallel": {"ep": 4, "chip": 0},
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4, "num_kv_heads": None},
}

# one whole period and the layer after it (G K K K G in the published order):
# both mixers, a KDA layer after a GQA one and a GQA layer after KDA ones.
# Their float32 copy would be 15.5 GB and does not fit beside the program's
# int8 weights (5.5 GB): ``reference`` dequantises a layer at a time (3.1 GB)
N_LAYERS = 5
N_PREFILL = 192  # three KDA chunks of 64; 192 rows: over the MoE cut (121), so the sorted FFN
N_DECODE = 8
CACHE_LEN = 256
VOCAB_BLOCK = 32_768  # columns of the head the reference dequantises at once (0.5 GB in float32)

STATE_BYTES = 4  # the recurrent state is float32
ARENA_BYTES = 2  # K/V rows and conv state are bf16
INT8 = 1


def _kinds(doc: dict, n_layers: int) -> tuple:
    gqa = {int(i) for i in doc["gqa_layers"]}  # 0-indexed, as published
    return tuple("full" if i in gqa else "kda" for i in range(n_layers))


def model_config(doc: dict, n_layers: int | None = None):
    """The program's ``ModelConfig`` from a configuration file whose top
    level holds Solar-Open2's published ``config.json`` keys, as run. A
    program whose hybrid block has no gate as wide as the attention's output
    and plans its kernels by pair of kinds cannot run it (``TypeError``: the
    parent of the PR that adds the family fails the cell at once and cleanly)."""
    from agentainer_tpu.models.configs import ModelConfig

    if not hasattr(ModelConfig, "gate_form"):
        raise TypeError(
            "this program's hybrid block has no attention gate as wide as the output and plans its kernels by "
            "pair of kinds: it cannot run KDA beside gated NoPE GQA (the solar_open2 family)"
        )
    if doc.get("use_rope") or doc.get("kda_use_full_proj") or int(doc["first_k_dense_replace"]):
        raise ValueError("the program serves this family without rotary embedding, with KDA's low-rank pairs, no dense layer")
    if not doc.get("use_gqa_gate") or not doc.get("norm_topk_prob"):
        raise ValueError("the program serves this family with the GQA gate and renormalised sigmoid gates")
    lin = doc["linear_attn_config"]
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError("the program's KDA has as many key/value heads as query heads")
    layers = int(n_layers if n_layers is not None else doc["num_hidden_layers"])
    held, published = int(doc["n_routed_experts"]), int(doc.get("experts_published", doc["n_routed_experts"]))
    chip = int((doc.get("expert_parallel") or {}).get("chip", 0))
    return ModelConfig(
        name=doc["name"],
        vocab_size=int(doc["vocab_size"]),
        dim=int(doc["hidden_size"]),
        n_layers=layers,
        n_heads=int(doc["num_attention_heads"]),
        n_kv_heads=int(doc["num_key_value_heads"]),
        head_size=int(doc["head_dim"]),
        ffn_dim=int(doc["moe_intermediate_size"]),
        max_seq_len=int(doc["max_position_embeddings"]),
        rope_theta=0.0,  # ``use_rope: false``: the published base is unused
        norm_eps=float(doc["rms_norm_eps"]),
        n_experts=published,
        experts_per_token=int(doc["num_experts_per_tok"]),
        moe_renormalize=True,
        layer_kinds=_kinds(doc, layers),
        kda_heads=int(lin["num_heads"]),
        kda_head_dim=int(lin["head_dim"]),
        kda_conv=int(lin["short_conv_kernel_size"]),
        delta_neg_eigval=bool(doc["kda_allow_neg_eigval"]),
        attn_gate="full",
        n_shared_experts=int(doc["n_shared_experts"]),
        moe_router="sigmoid",
        moe_scale=float(doc["routed_scaling_factor"]),
        experts_held=held if held < published else 0,
        expert_offset=chip * held if held < published else 0,
    )


def numerics_sizes(doc: dict) -> dict:
    layers = min(N_LAYERS, int(doc["num_hidden_layers"]))
    return {"layers": layers, "prefill": N_PREFILL, "decode": N_DECODE, "cache_len": CACHE_LEN}


def program(cfg, dev, dtype, cache_len: int) -> dict:
    """The program's side: seeded synthetic weights as served (its own int8
    generator; the vectors stay dense), a fresh cache as the model builds it
    (K and V rows, recurrent state, conv state), and jitted prefill and
    one-token decode through it with the kernels the program plans on this
    device (prefill of 192 rows takes the chunked KDA, ``flash_prefill`` and,
    over the MoE cut, the sorted grouped FFN over the held experts; decode the
    fused state update, ``flash_decode`` and the all-held-experts einsum).
    Signatures as ``families/llama.py``."""
    import jax
    import jax.numpy as jnp

    from agentainer_tpu.engine.quant import synthetic_quantized_params
    from agentainer_tpu.models.hybrid import plan_hybrid
    from agentainer_tpu.models.llama import forward, init_cache

    params = synthetic_quantized_params(cfg, dtype, device=dev)
    plan = plan_hybrid(cfg)

    @jax.jit
    def prefill(params, cache, toks):
        pos = jnp.arange(toks.shape[0], dtype=jnp.int32)[None]
        logits, cache = forward(params, cfg, toks[None], pos, cache, cache_attn_impl=plan)
        return logits[0], cache

    @jax.jit
    def decode(params, cache, tok, pos):
        logits, cache = forward(params, cfg, tok[None, None], pos[None, None], cache, cache_attn_impl=plan)
        return logits[0, 0], cache

    return {
        "params": params,
        "new_cache": lambda: init_cache(cfg, 1, cache_len, dtype=dtype),
        "prefill": prefill,
        "decode": decode,
        "attention": {k: v for k, v in plan.describe().items() if k != "reason"},
    }


def dense(x):
    """An int8 leaf as the float32 values it stands for; a dense leaf in float32."""
    import jax.numpy as jnp

    from agentainer_tpu.ops.quant import QTensor

    return (x.q.astype(jnp.float32) * x.scale.astype(jnp.float32)) if isinstance(x, QTensor) else x.astype(jnp.float32)


def reference_layer(params, cfg, i: int) -> dict:
    """Layer ``i`` of the program's pytree as the reference's dict of float32
    weights: its two norms, its mixer's leaves from the kind's stack (the
    merged q|k|v projection and conv filters of a KDA layer split into the
    published three) and its MoE's, the same held experts."""
    import jax
    import jax.numpy as jnp

    kind = cfg.layer_kinds[i]
    j = cfg.layer_kinds[:i].count(kind)  # the layer's index within its kind's stack
    lp = {}
    for group, at in (("layers", i), (kind, j), ("moe", i)):
        lp.update({k: dense(jax.tree.map(lambda a: a[at], v)) for k, v in params[group].items()})
    if kind == "kda":
        for name, part in zip("qkv", jnp.split(lp.pop("wqkv"), 3, axis=-1)):
            lp["w" + name] = part
        for name, part in zip("qkv", jnp.split(lp.pop("conv"), 3, axis=-1)):
            lp["conv_" + name] = part
    return lp


def reference(params, cfg):
    """The reference's side, **computed in blocks**: the weights handed over
    are the program's own pytree (int8 leaves and all), and ``forward(weights,
    tokens, act) -> logits [T, V]`` is ``families/solar_open2_reference.py``'s
    ``embed``, ``layer`` and ``head`` on float32 weights dequantised where they
    are used: the embedding's gathered rows, ONE layer at a time (the merged
    q|k|v projection and conv filters of a KDA layer split into the published
    three; the same held experts) and the head a block of the vocabulary at a
    time. The barrier ties a layer's int8 leaves to the stream that enters it,
    so the compiler cannot dequantise the next layer while this one's float32
    copy is live."""
    import importlib

    import jax
    import jax.numpy as jnp
    from jax import lax

    from agentainer_tpu.ops.quant import QTensor

    block = importlib.import_module("families.solar_open2_reference")

    kw = dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, kda_heads=cfg.kda_heads,
        kda_head_dim=cfg.kda_head_dim, norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token,
        routed_scale=cfg.moe_scale, renormalize=cfg.moe_renormalize, neg_eigval=cfg.delta_neg_eigval,
        expert_offset=cfg.expert_offset,
    )

    def forward(w, tokens, act):
        with jax.default_matmul_precision("highest"):
            table = w["embed"]  # ``block.embed`` is this gather, made before the rows are dequantised
            x = dense(QTensor(table.q[tokens], table.scale) if isinstance(table, QTensor) else table[tokens])
            for i in range(cfg.n_layers):
                x, w = lax.optimization_barrier((x, w))
                x = block.layer(x, reference_layer(w, cfg, i), act=act, **kw)
            final_norm, lm_head = dense(w["final_norm"]), w["lm_head"]
            logits = []
            for at in range(0, cfg.vocab_size, VOCAB_BLOCK):
                x, lm_head = lax.optimization_barrier((x, lm_head))
                cols = jax.tree.map(lambda a: a[..., at : at + VOCAB_BLOCK], lm_head)
                logits.append(block.head(x, final_norm, dense(cols), cfg.norm_eps, act))
            return jnp.concatenate(logits, axis=-1)

    return params, forward


# -- the yardstick's arithmetic, from the file's sizes alone ---------------------


def _sizes(doc: dict) -> dict:
    lin = doc["linear_attn_config"]
    layers = int(doc["num_hidden_layers"])
    kinds = _kinds(doc, layers)
    return {
        "d": int(doc["hidden_size"]), "layers": layers, "vocab": int(doc["vocab_size"]),
        "n_kda": kinds.count("kda"), "n_full": kinds.count("full"),
        "kh": int(lin["num_heads"]), "dk": int(lin["head_dim"]), "conv": int(lin["short_conv_kernel_size"]),
        "h": int(doc["num_attention_heads"]), "kv": int(doc["num_key_value_heads"]), "hd": int(doc["head_dim"]),
        "f": int(doc["moe_intermediate_size"]), "held": int(doc["n_routed_experts"]),
        "experts": int(doc.get("experts_published", doc["n_routed_experts"])),
        "k": int(doc["num_experts_per_tok"]), "shared": int(doc["n_shared_experts"]),
    }


def layer_weight_elements(doc: dict) -> dict:
    """Matrix elements by part (vectors left out: a KDA layer's conv filters,
    decay bias and norms are 107 K elements beside 137.6 M)."""
    s = _sizes(doc)
    c = s["kh"] * s["dk"]
    expert = 3 * s["d"] * s["f"]
    return {
        "kda": 4 * s["d"] * c + 2 * (s["d"] * s["dk"] + s["dk"] * c) + s["d"] * s["kh"],
        # q, o and the gate as wide as the output; k and v
        "full": 3 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"],
        "expert": expert,
        "moe_fixed": s["d"] * s["experts"] + s["shared"] * expert,  # router and shared expert
    }


def experts_chosen(doc: dict, lanes: float) -> float:
    """Held experts that ``lanes`` tokens, each choosing ``k`` of the
    router's ``E`` at random, reach in one layer: ``held · (1 − (1 − k / E) ^
    lanes)`` (32.1 of 40 at 64 lanes). What a step must read of the routed
    experts, whatever implements it: today's all-held-experts einsum reads
    them all."""
    s = _sizes(doc)
    return s["held"] * (1.0 - (1.0 - s["k"] / s["experts"]) ** max(lanes, 0.0))


def weight_bytes(doc: dict, experts: float | None = None) -> float:
    """Bytes of weights as served (int8): the mixers, router and shared
    expert of every layer, ``experts`` routed experts a layer (absent: every
    expert HELD here) and the output head (a step gathers its tokens' rows of
    the embedding and reads no more of it)."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    experts = s["held"] if experts is None else experts
    return INT8 * (
        s["n_kda"] * lw["kda"] + s["n_full"] * lw["full"]
        + s["layers"] * (lw["moe_fixed"] + experts * lw["expert"]) + s["d"] * s["vocab"]
    )


def state_bytes_per_lane(doc: dict) -> int:
    """The per-lane recurrent state (float32) and conv state (bf16)."""
    s = _sizes(doc)
    state = s["n_kda"] * s["kh"] * s["dk"] * s["dk"] * STATE_BYTES
    conv = s["n_kda"] * (s["conv"] - 1) * 3 * s["kh"] * s["dk"] * ARENA_BYTES
    return state + conv


def kv_bytes_per_token(doc: dict) -> int:
    """Positional bytes a token adds: a K and a V row in every GQA layer."""
    s = _sizes(doc)
    return s["n_full"] * 2 * s["kv"] * s["hd"] * ARENA_BYTES


def cache_bytes(doc: dict) -> dict:
    """The cache's leaves at the configuration's lanes and ``max_seq``."""
    s, opts = _sizes(doc), doc.get("engine_options") or {}
    lanes, seq = int(opts.get("max_batch", 1)), int(opts.get("max_seq", doc["max_position_embeddings"]))
    rows = s["n_full"] * lanes * seq * s["kv"] * s["hd"] * ARENA_BYTES
    return {
        "k": rows, "v": rows,
        "state": lanes * s["n_kda"] * s["kh"] * s["dk"] * s["dk"] * STATE_BYTES,
        "conv": lanes * s["n_kda"] * (s["conv"] - 1) * 3 * s["kh"] * s["dk"] * ARENA_BYTES,
    }


def kernel_calls_per_step(doc: dict) -> dict:
    """Calls of each kernel in one decode step: one a layer of its kind."""
    s = _sizes(doc)
    return {"kda_decode": s["n_kda"], "flash_decode": s["n_full"]}


def kda_decode_bytes(doc: dict, lanes: float) -> float:
    """One call of the KDA decode kernel (one layer): every stepping lane's
    state read and written, 64 KB a head each way."""
    s = _sizes(doc)
    return 2.0 * lanes * s["kh"] * s["dk"] * s["dk"] * STATE_BYTES


def decode_step_bytes(doc: dict, live_kv_tokens: float, live_lanes: float | None = None) -> float:
    """Bytes one decode step (one token for every lane) must move: the
    weights as served with the routed experts the lanes' tokens CHOOSE, the
    recurrent and conv state of the stepping lanes read AND written, and the
    K/V rows of the live context. ``live_lanes`` absent: every lane of the
    configuration's ``max_batch``."""
    lanes = live_lanes if live_lanes is not None else float((doc.get("engine_options") or {}).get("max_batch", 1))
    return (
        weight_bytes(doc, experts_chosen(doc, lanes)) + 2.0 * lanes * state_bytes_per_lane(doc)
        + live_kv_tokens * kv_bytes_per_token(doc)
    )


def decode_step_floor_s(doc: dict, live_kv_tokens: float, hbm_bytes_per_s: float, live_lanes: float | None = None) -> float:
    """The least time a decode step can take on a chip of that memory rate (a
    step's FLOPs are a hundredth of what the rate's time allows: the memory
    bounds it)."""
    return decode_step_bytes(doc, live_kv_tokens, live_lanes) / hbm_bytes_per_s


def kda_prefill_flops(doc: dict, n_tokens: int, chunk: int = 64) -> float:
    """Matmul FLOPs of the chunked delta rule for ``n_tokens`` of one layer
    (projections not counted): per chunk of C tokens and head, K̄K̂ᵀ and Q̄K̂ᵀ
    (2·2·C²·dk), the solve (C²·dv), K̄S₀, Q̄S₀, B·U and the state update
    (4 · 2·C·dk·dv)."""
    s = _sizes(doc)
    per_chunk = 4.0 * chunk * chunk * s["dk"] + chunk * chunk * s["dk"] + 8.0 * chunk * s["dk"] * s["dk"]
    return s["kh"] * per_chunk * (n_tokens / chunk)


def prefill_flops(doc: dict, n_tokens: int, mean_context: float, routed: bool = True) -> float:
    """Matmul FLOPs (2 per multiply-add) to prefill ``n_tokens`` whose mean
    attendable context is ``mean_context`` on this chip: the weights a token
    meets (``routed``: its chosen experts that are held here, k · held / E on
    average; otherwise every held expert), the delta rule of the KDA layers
    and the scores and values of the GQA layers."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    experts = s["k"] * s["held"] / s["experts"] if routed else s["held"]
    matmul = 2.0 * (
        s["n_kda"] * lw["kda"] + s["n_full"] * lw["full"]
        + s["layers"] * (lw["moe_fixed"] + experts * lw["expert"]) + s["d"] * s["vocab"]
    )
    attn = 4.0 * s["h"] * s["hd"] * mean_context * s["n_full"]
    return n_tokens * (matmul + attn) + s["n_kda"] * kda_prefill_flops(doc, n_tokens)
