"""The ``olmoe`` family: OLMoE-1B-7B's block as ``agentainer_tpu/models/llama.py``
computes it with ``qk_norm`` and without ``moe_renormalize`` (multi-head
attention with an RMSNorm over the whole projected query and key before the
rotary embedding; a mixture of SwiGLU experts whose gates are a softmax over
all experts, the top k kept and not renormalised). ``families/llama.py``
says what a family answers; the plain reference is
``families/olmoe_reference.py``. Nothing heavy is imported at module level.
"""

from __future__ import annotations

REHEARSAL_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 4, "vocab_size": 512, "num_hidden_layers": 2,
    "num_experts": 8, "num_experts_per_tok": 2,
}

# every layer is the same kind, so two show that layers chain; two layers'
# float32 copy for the reference is 3.3 GB beside 0.9 GB of int8 weights
N_LAYERS = 2
N_PREFILL = 96
N_DECODE = 8
CACHE_LEN = 256


def model_config(doc: dict, n_layers: int | None = None):
    """The program's ``ModelConfig`` from a configuration file whose top
    level holds OLMoE's published ``config.json`` keys, as run. A program
    without the two fields of the block cannot build it (``TypeError``)."""
    from agentainer_tpu.models.configs import ModelConfig

    if doc.get("clip_qkv") is not None or doc.get("attention_bias"):
        raise ValueError("the program's block has no QKV clipping and no attention bias")
    heads = int(doc["num_attention_heads"])
    return ModelConfig(
        name=doc["name"],
        vocab_size=int(doc["vocab_size"]),
        dim=int(doc["hidden_size"]),
        n_layers=int(n_layers if n_layers is not None else doc["num_hidden_layers"]),
        n_heads=heads,
        n_kv_heads=int(doc.get("num_key_value_heads", heads)),
        ffn_dim=int(doc["intermediate_size"]),
        max_seq_len=int(doc["max_position_embeddings"]),
        rope_theta=float(doc["rope_theta"]),
        norm_eps=float(doc["rms_norm_eps"]),
        n_experts=int(doc["num_experts"]),
        experts_per_token=int(doc["num_experts_per_tok"]),
        moe_renormalize=bool(doc["norm_topk_prob"]),
        qk_norm=True,
    )


def numerics_sizes(doc: dict) -> dict:
    return {"layers": N_LAYERS, "prefill": N_PREFILL, "decode": N_DECODE, "cache_len": CACHE_LEN}


def program(cfg, dev, dtype, cache_len: int) -> dict:
    """The program's side: seeded synthetic weights as served (its own int8
    generator; the norm vectors stay dense), a fresh KV arena, and jitted
    prefill and one-token decode through that arena, the attention kernels
    the program plans on this device and the MoE path an engine serves on
    one chip (``forward``'s default: the all-experts einsum, exact and
    dropless). Signatures as ``families/llama.py``."""
    import jax
    import jax.numpy as jnp

    from agentainer_tpu.engine.quant import synthetic_quantized_params
    from agentainer_tpu.models.llama import KVCache, forward
    from agentainer_tpu.ops.attention import plan_cache_attention

    params = synthetic_quantized_params(cfg, dtype, device=dev)
    plan = plan_cache_attention(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)

    @jax.jit
    def prefill(params, cache, toks):
        pos = jnp.arange(toks.shape[0], dtype=jnp.int32)[None]
        logits, cache = forward(params, cfg, toks[None], pos, cache, cache_attn_impl=plan.fn)
        return logits[0], cache

    @jax.jit
    def decode(params, cache, tok, pos):
        logits, cache = forward(params, cfg, tok[None, None], pos[None, None], cache, cache_attn_impl=plan.fn)
        return logits[0, 0], cache

    return {
        "params": params,
        "new_cache": lambda: KVCache.create(cfg, 1, cache_len, dtype=dtype),
        "prefill": prefill,
        "decode": decode,
        "attention": {"prefill": plan.prefill, "decode": plan.decode},
    }


def reference(params, cfg):
    """The reference's side: float32 weights in the layout of
    ``families/olmoe_reference.py`` from the program's pytree (int8 leaves
    dequantized), and ``forward(weights, tokens, act) -> logits [T, V]``."""
    import importlib

    import jax
    import jax.numpy as jnp

    from agentainer_tpu.ops.quant import QTensor

    block = importlib.import_module("families.olmoe_reference")

    def dense(x):
        return (x.q.astype(jnp.float32) * x.scale.astype(jnp.float32)) if isinstance(x, QTensor) else x.astype(jnp.float32)

    layers = [
        {k: dense(jax.tree.map(lambda a: a[i], v)) for k, v in params["layers"].items()}
        for i in range(cfg.n_layers)
    ]
    weights = {
        "embed": dense(params["embed"]),
        "layers": layers,
        "final_norm": dense(params["final_norm"]),
        "lm_head": dense(params["lm_head"]),
    }
    kw = dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token,
    )
    return weights, lambda w, tokens, act: block.forward(w, tokens, act=act, **kw)


KV_BYTES = 2  # the arena is bf16
INT8 = 1


def _sizes(doc: dict) -> dict:
    d, h = int(doc["hidden_size"]), int(doc["num_attention_heads"])
    return {
        "d": d, "f": int(doc["intermediate_size"]), "h": h,
        "kv": int(doc.get("num_key_value_heads", h)), "hd": d // h,
        "e": int(doc["num_experts"]), "k": int(doc["num_experts_per_tok"]),
        "layers": int(doc["num_hidden_layers"]), "vocab": int(doc["vocab_size"]),
    }


def layer_weight_elements(doc: dict) -> dict:
    """Matrix elements of one layer, by part (norm vectors left out: the two
    QK-norm weights are 4,096 elements beside 403 M)."""
    s = _sizes(doc)
    ffn_one = 3 * s["d"] * s["f"]
    return {
        "attention": s["d"] * s["h"] * s["hd"] * 2 + s["d"] * s["kv"] * s["hd"] * 2,
        "ffn": ffn_one * s["e"],
        "ffn_routed": ffn_one * s["k"],
        "router": s["d"] * s["e"],
    }


def decode_step_bytes(doc: dict, live_kv_tokens: float) -> float:
    """Bytes one decode step (one token for every lane) must read: every
    layer's weights as served (int8) with every expert counted, because the
    served path reads them all (16 lanes x 8 choices would reach about 87 %
    of 64 experts a layer if choices were uniform; nothing counts them
    yet), the output head, and
    the keys and values of the live context. Embedding rows and scales left
    out, as in ``families/llama.py``."""
    s = _sizes(doc)
    lw = layer_weight_elements(doc)
    per_layer = lw["attention"] + lw["ffn"] + lw["router"]
    weights = (s["layers"] * per_layer + s["d"] * s["vocab"]) * INT8
    return weights + live_kv_tokens * kv_bytes_per_token(doc)


def kv_bytes_per_token(doc: dict) -> int:
    s = _sizes(doc)
    return 2 * s["layers"] * s["kv"] * s["hd"] * KV_BYTES


def prefill_flops(doc: dict, n_tokens: int, mean_context: float, routed: bool = True) -> float:
    """Matmul FLOPs (2 per multiply-add) to prefill ``n_tokens`` whose mean
    attendable context is ``mean_context``. ``routed`` counts the eight
    experts a token is routed to (the algorithm's need); ``False`` counts all
    64 (what the all-experts einsum executes)."""
    s = _sizes(doc)
    lw = layer_weight_elements(doc)
    per_layer = lw["attention"] + (lw["ffn_routed"] if routed else lw["ffn"]) + lw["router"]
    matmul = 2.0 * (s["layers"] * per_layer + s["d"] * s["vocab"])
    attn = 4.0 * s["h"] * s["hd"] * mean_context * s["layers"]
    return n_tokens * (matmul + attn)
