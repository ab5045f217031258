"""The ``mistral4`` family: Mistral-Small-4's text decoder as
``agentainer_tpu/models/hybrid.py`` computes it (latent attention in every
layer with a low-rank query, a YaRN rotary embedding in adjacent pairs on the
shared key dims and the query's matching part, YaRN's ``m²`` on the softmax
scale and Llama-4's query scale by position; a softmax router renormalised
over the chosen experts, a shared expert, and the chip's share of the routed
experts; no linear mixer and no dense layer). ``families/llama.py`` says what
a family answers; the plain reference is ``families/mistral4_reference.py``.
Beside the usual answers: ``latent_row_bytes`` / ``latent_row_bytes_stored``
and the bytes a call of the latent decode kernel needs at this width
(``mla_decode_bytes``). Nothing heavy is imported at module level.

A configuration file of this family holds the published ``config.json`` keys
as run; ``n_routed_experts`` counts the experts **held here**,
``experts_published`` the router's width, ``expert_parallel`` the deployment
(``ep`` chips share each layer; this is chip ``chip``, holding experts
``chip · held ..``), as ``families/kimi_linear.py`` has them.
"""

from __future__ import annotations

# CPU rehearsal: control flow only. The original context stays 8192 (the
# cell's traffic then crosses it as on the chip)
REHEARSAL_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 512,
    "num_hidden_layers": 3, "kv_lora_rank": 32, "q_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 16, "qk_head_dim": 32, "v_head_dim": 16, "n_routed_experts": 2,
    "experts_published": 8, "num_experts_per_tok": 2, "expert_parallel": {"ep": 4, "chip": 0},
}

# One layer at published widths (every layer is the same kind; the CPU tests
# chain three). The float32 budget: a layer's copy for the reference is 3.4 GB
# (32 held experts of 25.2 M, 53.7 M outside them), the output head 2.1 GB, and
# the reference's logits over the WHOLE sequence, which the harness slices
# after the call, 8,456 x 131,072 x 4 B = 4.4 GB, beside 1.9 GB of int8 weights:
# 11.9 GB and the reference's temporaries of 15.75 usable. A second layer
# (3.4 + 0.9 GB) does not fit. The embedding is a gather: the reference is
# handed the float32 rows of the sequence's tokens (``reference``), not a 2.1 GB
# float32 copy of the table. The prefill CROSSES POSITION 8,192: it is fed in
# the engine's chunks of 256 through the cache, 33 of them, so the last 32
# prefill positions and every decode step have YaRN's scaled frequencies over
# distances past the original context and the query's scale at 1 + 0.1 ln 2
PREFILL_CHUNK = 256  # the engine's shipped default of its ``prefill_chunk`` option
N_LAYERS = 1
N_PREFILL = 8448
N_DECODE = 8
CACHE_LEN = 8704

ARENA_BYTES = 2  # latent rows are bf16
INT8 = 1


def model_config(doc: dict, n_layers: int | None = None):
    """The program's ``ModelConfig`` from a configuration file whose top
    level holds Mistral-Small-4's published ``config.json`` keys, as run. A
    program without the MLA switches cannot build it (``TypeError``: the
    parent of the PR that adds the family fails the cell cleanly)."""
    from agentainer_tpu.models.configs import ModelConfig

    rope = doc["rope_parameters"]
    if rope.get("rope_type") != "yarn" or float(rope["mscale"]) != float(rope["mscale_all_dim"]):
        raise ValueError("the program's rotary embedding here is YaRN with unscaled cos and sin (mscale == mscale_all_dim)")
    if int(doc.get("n_group", 1)) != 1 or int(doc.get("topk_group", 1)) != 1:
        raise ValueError("the program's router has no group limit")
    if float(doc["routed_scaling_factor"]) != 1.0 or not doc["norm_topk_prob"]:
        raise ValueError("the program's softmax rule here renormalises the chosen experts and scales them by 1")
    if int(doc["first_k_dense_replace"]) != 0 or doc.get("sliding_window") or doc.get("tie_word_embeddings"):
        raise ValueError("every layer is MoE, attention sees its whole context, the output head is untied")
    if int(doc["qk_head_dim"]) != int(doc["qk_nope_head_dim"]) + int(doc["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is qk_nope_head_dim + qk_rope_head_dim")
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
        ffn_dim=int(doc["moe_intermediate_size"]),
        max_seq_len=int(doc["max_position_embeddings"]),
        rope_theta=float(rope["rope_theta"]),
        norm_eps=float(doc["rms_norm_eps"]),
        n_experts=published,
        experts_per_token=int(doc["num_experts_per_tok"]),
        moe_renormalize=True,
        layer_kinds=("mla",) * layers,
        mla_kv_rank=int(doc["kv_lora_rank"]),
        mla_nope_dim=int(doc["qk_nope_head_dim"]),
        mla_rope_dim=int(doc["qk_rope_head_dim"]),
        mla_v_dim=int(doc["v_head_dim"]),
        mla_q_rank=int(doc["q_lora_rank"]),
        mla_rotary=True,
        rope_interleave=bool(doc["rope_interleave"]),
        rope_factor=float(rope["factor"]),
        rope_original_max=int(rope["original_max_position_embeddings"]),
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        q_pos_scale_beta=float(rope["llama_4_scaling_beta"]),
        n_dense_layers=0,
        dense_ffn_dim=int(doc["intermediate_size"]),  # published, and used by no layer
        n_shared_experts=int(doc["n_shared_experts"]),
        moe_router="softmax",
        experts_held=held if held < published else 0,
        expert_offset=chip * held if held < published else 0,
    )


def numerics_sizes(doc: dict) -> dict:
    layers = min(N_LAYERS, int(doc["num_hidden_layers"]))
    return {"layers": layers, "prefill": N_PREFILL, "decode": N_DECODE, "cache_len": CACHE_LEN}


def program(cfg, dev, dtype, cache_len: int) -> dict:
    """The program's side: seeded synthetic weights as served (its own int8
    generator; the norm vectors stay dense), a fresh cache as the model builds
    it (latent rows and nothing else), and jitted prefill and one-token decode
    through it with the kernels the program plans on this device. The prefill
    feeds its tokens as an engine does, a chunk of ``PREFILL_CHUNK`` rows a
    launch (one scan over the chunks), and returns the LAST chunk's logits
    ``[PREFILL_CHUNK, V]``: the harness reads the last 32 rows, and 8,448 x
    131,072 float32 logits would be 4.4 GB. A chunk of 256 rows is over the
    MoE cut, so prefill takes the sorted grouped FFN over the held experts
    and decode the all-held-experts einsum. Signatures otherwise as
    ``families/llama.py``."""
    import jax
    import jax.numpy as jnp

    from agentainer_tpu.engine.quant import synthetic_quantized_params
    from agentainer_tpu.models.hybrid import plan_hybrid
    from agentainer_tpu.models.llama import forward, init_cache

    params = synthetic_quantized_params(cfg, dtype, device=dev)
    plan = plan_hybrid(cfg)
    chunk = PREFILL_CHUNK

    @jax.jit
    def prefill(params, cache, toks):
        if toks.shape[0] % chunk:
            raise ValueError(f"the prefill is fed in whole chunks of {chunk}")
        chunks = toks.reshape(-1, chunk)
        starts = jnp.arange(chunks.shape[0], dtype=jnp.int32) * chunk

        def step(cache, inp):
            tok, start = inp
            pos = (start + jnp.arange(chunk, dtype=jnp.int32))[None]
            logits, cache = forward(params, cfg, tok[None], pos, cache, cache_attn_impl=plan, slot=jnp.int32(0))
            return cache, logits[0]

        # every chunk but the last only fills the cache
        cache, _ = jax.lax.scan(lambda c, i: (step(c, i)[0], None), cache, (chunks[:-1], starts[:-1]))
        return step(cache, (chunks[-1], starts[-1]))[::-1]

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
    ``families/mistral4_reference.py`` from the program's pytree (int8 leaves
    dequantized; the experts are the held share), and ``forward(weights,
    tokens, act) -> logits [T, V]``. The embedding stays the program's int8
    leaf (shared, not copied): ``forward`` dequantises the rows of the
    sequence's tokens, exactly as the whole table would be, and hands the
    reference those rows as its table with the tokens renumbered 0 .. T - 1."""
    import importlib

    import jax
    import jax.numpy as jnp

    from agentainer_tpu.ops.quant import QTensor

    block = importlib.import_module("families.mistral4_reference")

    def dense(x):
        return (x.q.astype(jnp.float32) * x.scale.astype(jnp.float32)) if isinstance(x, QTensor) else x.astype(jnp.float32)

    def layer_of(group: str, i: int) -> dict:
        return {k: dense(jax.tree.map(lambda a: a[i], v)) for k, v in params[group].items()}

    layers = [{**layer_of("layers", i), **layer_of("mla", i), **layer_of("moe", i)} for i in range(cfg.n_layers)]
    weights = {
        "embed": params["embed"],
        "layers": layers,
        "final_norm": dense(params["final_norm"]),
        "lm_head": dense(params["lm_head"]),
    }
    kw = dict(
        n_heads=cfg.n_heads, kv_rank=cfg.mla_kv_rank, nope_dim=cfg.mla_nope_dim, rope_dim=cfg.mla_rope_dim,
        v_dim=cfg.mla_v_dim, norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token, rope_theta=cfg.rope_theta,
        rope_factor=cfg.rope_factor, original_max=cfg.rope_original_max, beta_fast=cfg.rope_beta_fast,
        beta_slow=cfg.rope_beta_slow, mscale_all_dim=cfg.rope_mscale_all_dim, query_beta=cfg.q_pos_scale_beta,
        expert_offset=cfg.expert_offset,
    )

    def forward(w, tokens, act):
        table = w["embed"]
        rows = dense(QTensor(table.q[tokens], table.scale)) if isinstance(table, QTensor) else table[tokens].astype(jnp.float32)
        return block.forward({**w, "embed": rows}, jnp.arange(tokens.shape[0]), act=act, **kw)

    return weights, forward


# -- the yardstick's arithmetic, from the file's sizes alone ---------------------


def _sizes(doc: dict) -> dict:
    held = int(doc["n_routed_experts"])
    return {
        "d": int(doc["hidden_size"]), "layers": int(doc["num_hidden_layers"]), "vocab": int(doc["vocab_size"]),
        "h": int(doc["num_attention_heads"]), "rank": int(doc["kv_lora_rank"]), "q_rank": int(doc["q_lora_rank"]),
        "nope": int(doc["qk_nope_head_dim"]), "rope": int(doc["qk_rope_head_dim"]), "dv": int(doc["v_head_dim"]),
        "f": int(doc["moe_intermediate_size"]), "held": held, "experts": int(doc.get("experts_published", held)),
        "k": int(doc["num_experts_per_tok"]), "shared": int(doc["n_shared_experts"]),
    }


def layer_weight_elements(doc: dict) -> dict:
    """Matrix elements of one layer, by part (the norm vectors left out: 9,472
    elements beside 53.7 M)."""
    s = _sizes(doc)
    expert = 3 * s["d"] * s["f"]
    return {
        "mla": s["d"] * s["q_rank"] + s["q_rank"] * s["h"] * (s["nope"] + s["rope"])
        + s["d"] * (s["rank"] + s["rope"]) + s["rank"] * s["h"] * (s["nope"] + s["dv"]) + s["h"] * s["dv"] * s["d"],
        "expert": expert,
        "moe_fixed": s["d"] * s["experts"] + s["shared"] * expert,  # router and shared expert
    }


def weight_bytes(doc: dict, routed_only: bool = False) -> float:
    """Bytes of weights a step streams: every layer's as served (int8) with
    the experts HELD here all counted (16 lanes x 4 choices over 128 experts
    reach 13 of the 32 held on average each step, and the all-held-experts
    einsum of a decode step reads every one; a 256-row chunk reaches them
    all), the shared expert, the router and the output head."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    experts = min(s["k"], s["held"]) if routed_only else s["held"]
    return INT8 * (s["layers"] * (lw["mla"] + lw["moe_fixed"] + experts * lw["expert"]) + s["d"] * s["vocab"])


def latent_row_bytes(doc: dict) -> int:
    """A token's latent row in one layer, as published: ``R + r`` values."""
    s = _sizes(doc)
    return (s["rank"] + s["rope"]) * ARENA_BYTES


def latent_row_bytes_stored(doc: dict) -> int:
    """The same row as the arena stores and the kernels fetch it: padded to
    whole 128-lane tiles (320 values as 384)."""
    s = _sizes(doc)
    return -(-(s["rank"] + s["rope"]) // 128) * 128 * ARENA_BYTES


def kv_bytes_per_token(doc: dict) -> int:
    """Positional bytes a token adds: one latent row in every layer."""
    return _sizes(doc)["layers"] * latent_row_bytes(doc)


def mla_decode_bytes(doc: dict, live_kv_tokens: float) -> float:
    """One call of the MLA decode kernel (one layer): the stored latent rows
    of the live context, read once for all heads."""
    return live_kv_tokens * latent_row_bytes_stored(doc)


def decode_step_bytes(doc: dict, live_kv_tokens: float) -> float:
    """Bytes one decode step (one token for every lane) must move: the weights
    as served with the held experts, and the latent rows of the live context
    in every layer. No per-lane state."""
    return weight_bytes(doc) + live_kv_tokens * kv_bytes_per_token(doc)


def prefill_flops(doc: dict, n_tokens: int, mean_context: float, routed: bool = True) -> float:
    """Matmul FLOPs (2 per multiply-add) to prefill ``n_tokens`` whose mean
    attendable context is ``mean_context`` on this chip: the weights a token
    meets (``routed``: its chosen experts that are held here, k · held / E on
    average; otherwise every held expert) and the expanded attention (128
    score dims and 128 value dims a head and slot; the absorbed form the
    kernels run scores 320 and combines 256)."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    experts = s["k"] * s["held"] / s["experts"] if routed else s["held"]
    matmul = 2.0 * (s["layers"] * (lw["mla"] + lw["moe_fixed"] + experts * lw["expert"]) + s["d"] * s["vocab"])
    attn = 2.0 * s["h"] * (s["nope"] + s["rope"] + s["dv"]) * mean_context * s["layers"]
    return n_tokens * (matmul + attn)
