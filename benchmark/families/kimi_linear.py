"""The ``kimi_linear`` family: Kimi-Linear's hybrid block as
``agentainer_tpu/models/hybrid.py`` computes it (KDA gated delta-rule linear
attention beside NoPE latent attention, a dense first layer, then sigmoid-
routed experts with a selection bias, a shared expert, and the chip's share
of the routed experts). ``families/llama.py`` says what a family answers;
the plain reference is ``families/kimi_linear_reference.py``. Beside the
usual answers: ``state_bytes_per_lane``, and the bytes and FLOPs a call of
each new kernel needs (``kda_decode_bytes``, ``mla_decode_bytes``,
``kda_prefill_flops``). Nothing heavy is imported at module level.

A configuration file of this family holds the published ``config.json`` keys
as run; ``num_experts`` counts the experts **held here**, ``experts_published``
the router's width, ``expert_parallel`` the deployment (``ep`` chips share
each layer; this is chip ``chip``, holding experts ``chip · held ..``).
"""

from __future__ import annotations

REHEARSAL_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 512,
    "num_hidden_layers": 5, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "num_experts": 2, "experts_published": 8, "num_experts_per_token": 2,
    "expert_parallel": {"ep": 4, "chip": 0},
    "linear_attn_config": {
        "full_attn_layers": [4], "kda_layers": [1, 2, 3, 5], "head_dim": 16, "num_heads": 4,
        "short_conv_kernel_size": 4,
    },
}

# the dense first layer and one whole period after it (K K K M K in the
# published order): every kind of mixer and of FFN, and a KDA layer after an
# MLA one. Their float32 copy for the reference is 7.8 GB (3 GB of it the
# vocabulary's two matrices) beside 1.9 GB of int8 weights
N_LAYERS = 5
N_PREFILL = 192  # three KDA chunks of 64; 192 rows: over the MoE cut (121), so the sorted FFN
N_DECODE = 8
CACHE_LEN = 256

STATE_BYTES = 4  # the recurrent state is float32
ARENA_BYTES = 2  # latent rows and conv state are bf16
INT8 = 1


def _kinds(doc: dict, n_layers: int) -> tuple:
    lin = doc["linear_attn_config"]
    full, kda = set(lin["full_attn_layers"]), set(lin["kda_layers"])
    kinds = []
    for i in range(1, n_layers + 1):  # the published lists are 1-indexed
        if (i in full) == (i in kda):
            raise ValueError(f"layer {i} is in both or neither of kda_layers and full_attn_layers")
        kinds.append("mla" if i in full else "kda")
    return tuple(kinds)


def model_config(doc: dict, n_layers: int | None = None):
    """The program's ``ModelConfig`` from a configuration file whose top
    level holds Kimi-Linear's published ``config.json`` keys, as run. A
    program without the hybrid block's fields cannot build it (``TypeError``:
    the parent of the PR that adds the family fails the cell cleanly)."""
    from agentainer_tpu.models.configs import ModelConfig

    if doc.get("q_lora_rank") is not None or not doc.get("mla_use_nope"):
        raise ValueError("the program's MLA has no query latent and no rotary embedding")
    if int(doc.get("num_expert_group", 1)) != 1 or int(doc.get("topk_group", 1)) != 1:
        raise ValueError("the program's router has no group limit")
    if doc.get("moe_router_activation_func") != "sigmoid" or int(doc.get("moe_layer_freq", 1)) != 1:
        raise ValueError("the program's hybrid router is sigmoid, every layer after the dense ones")
    layers = int(n_layers if n_layers is not None else doc["num_hidden_layers"])
    lin = doc["linear_attn_config"]
    held, published = int(doc["num_experts"]), int(doc.get("experts_published", doc["num_experts"]))
    chip = int((doc.get("expert_parallel") or {}).get("chip", 0))
    return ModelConfig(
        name=doc["name"],
        vocab_size=int(doc["vocab_size"]),
        dim=int(doc["hidden_size"]),
        n_layers=layers,
        n_heads=int(doc["num_attention_heads"]),
        n_kv_heads=int(doc["num_key_value_heads"]),
        ffn_dim=int(doc["moe_intermediate_size"]),
        max_seq_len=int(doc["model_max_length"]),
        rope_theta=float(doc["rope_theta"]),
        norm_eps=float(doc["rms_norm_eps"]),
        n_experts=published,
        experts_per_token=int(doc["num_experts_per_token"]),
        moe_renormalize=bool(doc["moe_renormalize"]),
        layer_kinds=_kinds(doc, layers),
        kda_heads=int(lin["num_heads"]),
        kda_head_dim=int(lin["head_dim"]),
        kda_conv=int(lin["short_conv_kernel_size"]),
        mla_kv_rank=int(doc["kv_lora_rank"]),
        mla_nope_dim=int(doc["qk_nope_head_dim"]),
        mla_rope_dim=int(doc["qk_rope_head_dim"]),
        mla_v_dim=int(doc["v_head_dim"]),
        n_dense_layers=min(layers, int(doc["first_k_dense_replace"])),
        dense_ffn_dim=int(doc["intermediate_size"]),
        n_shared_experts=int(doc["num_shared_experts"]),
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
    (latent rows, recurrent state, conv state), and jitted prefill and
    one-token decode through it with the kernels the program plans on this
    device (prefill of 192 rows takes the chunked KDA and, over the MoE cut,
    the sorted grouped FFN over the held experts; decode the fused state
    update, the latent decode and the all-held-experts einsum). Signatures
    as ``families/llama.py``."""
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


def reference(params, cfg):
    """The reference's side: float32 weights in the layout of
    ``families/kimi_linear_reference.py`` from the program's pytree (int8
    leaves dequantized; the program's merged q|k|v projection and conv
    filters split into the published three), the same held experts, and
    ``forward(weights, tokens, act) -> logits [T, V]``."""
    import importlib

    import jax
    import jax.numpy as jnp

    from agentainer_tpu.ops.quant import QTensor

    block = importlib.import_module("families.kimi_linear_reference")

    def dense(x):
        return (x.q.astype(jnp.float32) * x.scale.astype(jnp.float32)) if isinstance(x, QTensor) else x.astype(jnp.float32)

    def layer_of(group: str, i: int) -> dict:
        return {k: dense(jax.tree.map(lambda a: a[i], v)) for k, v in params[group].items()}

    layers, seen = [], {"kda": 0, "mla": 0}
    for i, kind in enumerate(cfg.layer_kinds):
        lp = {k: dense(v[i]) for k, v in params["layers"].items()}
        mixer = layer_of(kind, seen[kind])
        seen[kind] += 1
        if kind == "kda":
            for name, part in zip("qkv", jnp.split(mixer.pop("wqkv"), 3, axis=-1)):
                lp["w" + name] = part
            for name, part in zip("qkv", jnp.split(mixer.pop("conv"), 3, axis=-1)):
                lp["conv_" + name] = part
        lp.update(mixer)
        lp.update(layer_of("dense", i) if i < cfg.n_dense_layers else layer_of("moe", i - cfg.n_dense_layers))
        layers.append(lp)
    weights = {
        "embed": dense(params["embed"]),
        "layers": layers,
        "final_norm": dense(params["final_norm"]),
        "lm_head": dense(params["lm_head"]),
    }
    kw = dict(
        n_heads=cfg.n_heads, kda_heads=cfg.kda_heads, kda_head_dim=cfg.kda_head_dim,
        kv_rank=cfg.mla_kv_rank, nope_dim=cfg.mla_nope_dim, v_dim=cfg.mla_v_dim,
        norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token, routed_scale=cfg.moe_scale,
        renormalize=cfg.moe_renormalize, expert_offset=cfg.expert_offset,
    )
    return weights, lambda w, tokens, act: block.forward(w, tokens, act=act, **kw)


# -- the yardstick's arithmetic, from the file's sizes alone ---------------------


def _sizes(doc: dict) -> dict:
    lin = doc["linear_attn_config"]
    layers = int(doc["num_hidden_layers"])
    kinds = _kinds(doc, layers)
    return {
        "d": int(doc["hidden_size"]), "layers": layers, "vocab": int(doc["vocab_size"]),
        "n_kda": kinds.count("kda"), "n_mla": kinds.count("mla"),
        "kh": int(lin["num_heads"]), "dk": int(lin["head_dim"]), "conv": int(lin["short_conv_kernel_size"]),
        "h": int(doc["num_attention_heads"]), "rank": int(doc["kv_lora_rank"]),
        "nope": int(doc["qk_nope_head_dim"]), "rope": int(doc["qk_rope_head_dim"]), "dv": int(doc["v_head_dim"]),
        "n_dense": int(doc["first_k_dense_replace"]), "dense_f": int(doc["intermediate_size"]),
        "f": int(doc["moe_intermediate_size"]), "held": int(doc["num_experts"]),
        "experts": int(doc.get("experts_published", doc["num_experts"])),
        "k": int(doc["num_experts_per_token"]), "shared": int(doc["num_shared_experts"]),
    }


def layer_weight_elements(doc: dict) -> dict:
    """Matrix elements by part (vectors left out: a KDA layer's conv filters,
    decay bias and norms are 57 K elements beside 39.5 M)."""
    s = _sizes(doc)
    c = s["kh"] * s["dk"]
    expert = 3 * s["d"] * s["f"]
    return {
        "kda": 4 * s["d"] * c + 2 * (s["d"] * s["dk"] + s["dk"] * c) + s["d"] * s["kh"],
        "mla": s["d"] * s["h"] * (s["nope"] + s["rope"]) + s["d"] * (s["rank"] + s["rope"])
        + s["rank"] * s["h"] * (s["nope"] + s["dv"]) + s["h"] * s["dv"] * s["d"],
        "dense_ffn": 3 * s["d"] * s["dense_f"],
        "expert": expert,
        "moe_fixed": s["d"] * s["experts"] + s["shared"] * expert,  # router and shared expert
    }


def weight_bytes(doc: dict, routed_only: bool = False) -> float:
    """Bytes of weights a step streams: every layer's as served (int8) with
    the experts HELD here all counted (64 lanes x 8 choices over 256 experts
    reach nearly every held expert each step; the served path reads them
    all), the shared expert, the router and the output head."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    n_moe = s["layers"] - s["n_dense"]
    experts = min(s["k"], s["held"]) if routed_only else s["held"]
    return INT8 * (
        s["n_kda"] * lw["kda"] + s["n_mla"] * lw["mla"] + s["n_dense"] * lw["dense_ffn"]
        + n_moe * (lw["moe_fixed"] + experts * lw["expert"]) + s["d"] * s["vocab"]
    )


def state_bytes_per_lane(doc: dict) -> int:
    """The per-lane recurrent state (float32) and conv state (bf16)."""
    s = _sizes(doc)
    state = s["n_kda"] * s["kh"] * s["dk"] * s["dk"] * STATE_BYTES
    conv = s["n_kda"] * (s["conv"] - 1) * 3 * s["kh"] * s["dk"] * ARENA_BYTES
    return state + conv


def kv_bytes_per_token(doc: dict) -> int:
    """Positional bytes a token adds: one latent row in every MLA layer."""
    s = _sizes(doc)
    return s["n_mla"] * (s["rank"] + s["rope"]) * ARENA_BYTES


def kernel_calls_per_step(doc: dict) -> dict:
    """Calls of each new kernel in one decode step: one a layer of its kind."""
    s = _sizes(doc)
    return {"kda_decode": s["n_kda"], "mla_decode": s["n_mla"]}


def kda_decode_bytes(doc: dict, lanes: float) -> float:
    """One call of the KDA decode kernel (one layer): every stepping lane's
    state read and written."""
    s = _sizes(doc)
    return 2.0 * lanes * s["kh"] * s["dk"] * s["dk"] * STATE_BYTES


def mla_decode_bytes(doc: dict, live_kv_tokens: float) -> float:
    """One call of the MLA decode kernel (one layer): the latent rows of the
    live context, read once for all heads."""
    s = _sizes(doc)
    return live_kv_tokens * (s["rank"] + s["rope"]) * ARENA_BYTES


def decode_step_bytes(doc: dict, live_kv_tokens: float, live_lanes: float | None = None) -> float:
    """Bytes one decode step (one token for every lane) must move: the
    weights as served with the held experts, the recurrent state of the
    stepping lanes read AND written (and their conv state), and the latent
    rows of the live context. ``live_lanes`` absent: every lane of the
    configuration's ``max_batch``."""
    lanes = live_lanes if live_lanes is not None else float((doc.get("engine_options") or {}).get("max_batch", 1))
    return weight_bytes(doc) + 2.0 * lanes * state_bytes_per_lane(doc) + live_kv_tokens * kv_bytes_per_token(doc)


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
    and the expanded attention of the MLA layers."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    n_moe = s["layers"] - s["n_dense"]
    experts = s["k"] * s["held"] / s["experts"] if routed else s["held"]
    matmul = 2.0 * (
        s["n_kda"] * lw["kda"] + s["n_mla"] * lw["mla"] + s["n_dense"] * lw["dense_ffn"]
        + n_moe * (lw["moe_fixed"] + experts * lw["expert"]) + s["d"] * s["vocab"]
    )
    attn = 2.0 * s["h"] * (s["nope"] + s["rope"] + s["dv"]) * mean_context * s["n_mla"]
    return n_tokens * (matmul + attn) + s["n_kda"] * kda_prefill_flops(doc, n_tokens)
