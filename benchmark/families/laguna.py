"""The ``laguna`` family: Laguna-XS.2's decoder as
``agentainer_tpu/models/hybrid.py`` computes it (full attention beside
sliding-window attention with their own counts of query heads over shared
K/V heads, a sigmoid gate a head, a partial YaRN rotation in the full layers
and plain RoPE in the sliding ones, the sliding layers' rows a ring; a dense
first layer, then a softmax router renormalised over the chosen experts and
scaled, a shared expert, and the chip's share of the routed experts).
``families/llama.py`` says what a family answers; the plain reference is
``families/laguna_reference.py``. Beside the usual answers: the arithmetic by
kind of layer (``row_bytes``, ``ring_rows``, ``kv_resident_bytes_per_lane``,
``attended_rows``) and the floor of a mixed launch
(``mixed_step_floor_s``). Nothing heavy is imported at module level.

A configuration file of this family holds the published ``config.json`` keys
as run; ``num_experts`` counts the experts **held here**,
``experts_published`` the router's width, ``expert_parallel`` the deployment
(``ep`` chips share each layer; this is chip ``chip``, holding experts
``chip · held ..``), as ``families/kimi_linear.py`` has them.
"""

from __future__ import annotations

import math

_REHEARSAL_LAYERS = 8
REHEARSAL_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_attention_heads": 6, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 512, "num_hidden_layers": _REHEARSAL_LAYERS, "sliding_window": 16,
    "num_experts": 2, "experts_published": 8, "num_experts_per_tok": 2, "expert_parallel": {"ep": 4, "chip": 0},
    "layer_types": ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention"] * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * (_REHEARSAL_LAYERS - 1),
    "num_attention_heads_per_layer": [6, 8, 8, 8] * 2,
}

# Layer 0 (dense FFN, full attention), three sliding layers and the next full
# layer (MoE): every kind of mixer and of FFN, and a full layer after a
# sliding one. Their float32 copy for the reference is 2.8 GB and the output
# head's 0.8 GB beside 1.1 GB of int8 weights; the reference's logits over
# the whole sequence are 1.75 GB. The prefill is fed in the engine's chunks of
# 256 through the cache, 17 of them: the ring (512 + 256 -> 1,024 rows) wraps
# from position 1,024 on, the full layers' YaRN frequencies see distances past
# the original 4,096 in the last chunk (positions 4,096-4,351) and in every
# decode step
PREFILL_CHUNK = 256  # the engine's shipped default of its ``prefill_chunk`` option
N_LAYERS = 5
N_PREFILL = 4352
N_DECODE = 8
CACHE_LEN = 4608

KV_BYTES = 2  # K and V rows are bf16
INT8 = 1
RING_BLOCK = 512  # the K/V block the flash kernels read a ring in (8 heads of 128, bf16)


def _kinds(doc: dict, n_layers: int) -> tuple:
    names = {"full_attention": "full", "sliding_attention": "swa"}
    kinds = tuple(names[t] for t in doc["layer_types"][:n_layers])
    if len(kinds) != n_layers or kinds[0] != "full":
        raise ValueError("layer_types names a kind for every layer, the first of them full attention")
    return kinds


def _heads(doc: dict, n_layers: int) -> dict:
    """Query heads by kind of layer, from ``num_attention_heads_per_layer``:
    one count a kind."""
    by_kind: dict = {}
    for kind, n in zip(_kinds(doc, n_layers), doc["num_attention_heads_per_layer"]):
        if by_kind.setdefault(kind, int(n)) != int(n):
            raise ValueError("the program's attention has one count of query heads a kind of layer")
    return by_kind


def model_config(doc: dict, n_layers: int | None = None):
    """The program's ``ModelConfig`` from a configuration file whose top
    level holds Laguna-XS.2's published ``config.json`` keys, as run. A
    program without the fields of a second positional kind cannot build it
    (``TypeError``: the parent of the PR that adds the family fails the cell
    cleanly, before anything touches the device)."""
    from agentainer_tpu.models.configs import ModelConfig

    layers = int(n_layers if n_layers is not None else doc["num_hidden_layers"])
    rope = doc["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if full.get("rope_type") != "yarn" or sliding.get("rope_type") != "default":
        raise ValueError("the program rotates a full layer with YaRN's frequencies and a sliding layer with plain RoPE")
    if float(sliding.get("partial_rotary_factor", 1)) != 1.0:
        raise ValueError("the program rotates the whole head of a sliding layer")
    if doc.get("attention_bias") or doc.get("tie_word_embeddings") or doc.get("moe_apply_router_weight_on_input"):
        raise ValueError("no bias, an untied head, the router's weight on the experts' OUTPUT")
    if not doc.get("gating"):
        raise ValueError("the configuration gates every attention head")
    mlp = doc["mlp_layer_types"][:layers]
    n_dense = sum(t == "dense" for t in mlp)
    if list(mlp) != ["dense"] * n_dense + ["sparse"] * (layers - n_dense):
        raise ValueError("the program's dense layers are the first ones")
    heads = _heads(doc, layers)
    if int(doc["num_attention_heads"]) != heads["full"]:
        raise ValueError("num_attention_heads is a full layer's count of query heads")
    held, published = int(doc["num_experts"]), int(doc.get("experts_published", doc["num_experts"]))
    chip = int((doc.get("expert_parallel") or {}).get("chip", 0))
    shared, width = int(doc["shared_expert_intermediate_size"]), int(doc["moe_intermediate_size"])
    if shared % width:
        raise ValueError("the shared expert is a whole number of routed experts wide")
    return ModelConfig(
        name=doc["name"],
        vocab_size=int(doc["vocab_size"]),
        dim=int(doc["hidden_size"]),
        n_layers=layers,
        n_heads=heads["full"],
        n_kv_heads=int(doc["num_key_value_heads"]),
        head_size=int(doc["head_dim"]),
        ffn_dim=width,
        max_seq_len=int(doc["max_position_embeddings"]),
        rope_theta=float(full["rope_theta"]),
        norm_eps=float(doc["rms_norm_eps"]),
        n_experts=published,
        experts_per_token=int(doc["num_experts_per_tok"]),
        moe_renormalize=True,
        layer_kinds=_kinds(doc, layers),
        window=int(doc["sliding_window"]),
        swa_heads=heads.get("swa", 0),
        swa_rope_theta=float(sliding["rope_theta"]),
        rope_partial=float(full["partial_rotary_factor"]),
        rope_factor=float(full["factor"]),
        rope_original_max=int(full["original_max_position_embeddings"]),
        rope_beta_fast=float(full["beta_fast"]),
        rope_beta_slow=float(full["beta_slow"]),
        rope_attention_factor=float(full["attention_factor"]),
        attn_gate=True,
        n_dense_layers=n_dense,
        dense_ffn_dim=int(doc["intermediate_size"]),
        n_shared_experts=shared // width,
        moe_router="softmax",
        moe_scale=float(doc["moe_routed_scaling_factor"]),
        experts_held=held if held < published else 0,
        expert_offset=chip * held if held < published else 0,
    )


def numerics_sizes(doc: dict) -> dict:
    layers = min(N_LAYERS, int(doc["num_hidden_layers"]))
    return {"layers": layers, "prefill": N_PREFILL, "decode": N_DECODE, "cache_len": CACHE_LEN}


def program(cfg, dev, dtype, cache_len: int) -> dict:
    """The program's side: seeded synthetic weights as served (its own int8
    generator; the norm vectors stay dense), a fresh cache as the model builds
    it WITH THE ENGINE'S RING (``llama.ring_plan`` at the engine's chunk: 512 +
    256 rows rounded to the kernels' block, so 1,024 of the 4,608), and jitted
    prefill and one-token decode through it with the kernels the program plans
    on this device. The prefill feeds its tokens as an engine does, a chunk of
    ``PREFILL_CHUNK`` rows a launch (one scan over the chunks), and returns the
    LAST chunk's logits ``[PREFILL_CHUNK, V]``: the harness reads the last 32
    rows. A chunk of 256 rows is over the MoE cut, so prefill takes the sorted
    grouped FFN over the held experts and decode the all-held-experts einsum.
    Signatures otherwise as ``families/llama.py``."""
    import jax
    import jax.numpy as jnp

    from agentainer_tpu.engine.quant import synthetic_quantized_params
    from agentainer_tpu.models.hybrid import plan_hybrid
    from agentainer_tpu.models.llama import forward, init_cache, ring_plan

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

    describe = {k: v for k, v in plan.describe().items() if k != "reason"}
    new_cache = lambda: init_cache(cfg, 1, cache_len, dtype=dtype, **ring_plan(cfg, dtype, chunk))  # noqa: E731
    return {
        "params": params,
        "new_cache": new_cache,
        "prefill": prefill,
        "decode": decode,
        "attention": {**describe, "ring_rows": int(jax.eval_shape(new_cache).wk.shape[2])},
    }


def reference(params, cfg):
    """The reference's side: float32 weights in the layout of
    ``families/laguna_reference.py`` from the program's pytree (int8 leaves
    dequantized; a layer's attention from its kind's own stack; the experts
    are the held share), and ``forward(weights, tokens, act) -> logits [T,
    V]``. The embedding stays the program's int8 leaf: ``forward`` dequantises
    the rows of the sequence's tokens and hands the reference those rows as
    its table with the tokens renumbered 0 .. T - 1."""
    import importlib

    import jax
    import jax.numpy as jnp

    from agentainer_tpu.ops.quant import QTensor

    block = importlib.import_module("families.laguna_reference")

    def dense(x):
        return (x.q.astype(jnp.float32) * x.scale.astype(jnp.float32)) if isinstance(x, QTensor) else x.astype(jnp.float32)

    def layer_of(group: str, i: int) -> dict:
        return {k: dense(jax.tree.map(lambda a: a[i], v)) for k, v in params[group].items()}

    layers, seen = [], {"full": 0, "swa": 0, "dense": 0, "moe": 0}
    for i, kind in enumerate(cfg.layer_kinds):
        ffn = "dense" if i < cfg.n_dense_layers else "moe"
        layers.append({**layer_of("layers", i), **layer_of(kind, seen[kind]), **layer_of(ffn, seen[ffn])})
        seen[kind] += 1
        seen[ffn] += 1
    weights = {
        "embed": params["embed"],
        "layers": layers,
        "final_norm": dense(params["final_norm"]),
        "lm_head": dense(params["lm_head"]),
    }
    kw = dict(
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token,
        layer_types=tuple("full" if k == "full" else "sliding" for k in cfg.layer_kinds), window=cfg.window,
        full_rope=dict(
            theta=cfg.rope_theta, factor=cfg.rope_factor, original_max=cfg.rope_original_max,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
            attention_factor=cfg.rope_attention_factor, rotary_dim=cfg.rotary_dim,
        ),
        sliding_theta=cfg.swa_rope_theta, routed_scale=cfg.moe_scale, expert_offset=cfg.expert_offset,
    )

    def forward(w, tokens, act):
        table = w["embed"]
        rows = dense(QTensor(table.q[tokens], table.scale)) if isinstance(table, QTensor) else table[tokens].astype(jnp.float32)
        return block.forward({**w, "embed": rows}, jnp.arange(tokens.shape[0]), act=act, **kw)

    return weights, forward


# -- the yardstick's arithmetic, from the file's sizes alone ---------------------


def _sizes(doc: dict) -> dict:
    layers = int(doc["num_hidden_layers"])
    kinds, heads = _kinds(doc, layers), _heads(doc, layers)
    held = int(doc["num_experts"])
    n_dense = sum(t == "dense" for t in doc["mlp_layer_types"][:layers])
    return {
        "d": int(doc["hidden_size"]), "layers": layers, "vocab": int(doc["vocab_size"]),
        "h_full": heads["full"], "h_swa": heads.get("swa", 0), "kv": int(doc["num_key_value_heads"]),
        "hd": int(doc["head_dim"]), "f": int(doc["moe_intermediate_size"]),
        "fs": int(doc["shared_expert_intermediate_size"]), "fd": int(doc["intermediate_size"]),
        "held": held, "experts": int(doc.get("experts_published", held)), "k": int(doc["num_experts_per_tok"]),
        "n_window": kinds.count("swa"), "n_global": kinds.count("full"), "window": int(doc["sliding_window"]),
        "n_dense": n_dense, "n_moe": layers - n_dense,
    }


def layer_weight_elements(doc: dict) -> dict:
    """Matrix elements by part (the norm vectors left out: 165,888 elements
    beside 6 G): a layer's attention by kind (q, o, k, v and the gate), one
    expert, a MoE layer's router and shared expert, the dense layer's FFN."""
    s = _sizes(doc)

    def attention(heads: int) -> int:
        return 2 * s["d"] * heads * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"] + s["d"] * heads

    return {
        "full": attention(s["h_full"]), "sliding": attention(s["h_swa"]), "expert": 3 * s["d"] * s["f"],
        "moe_fixed": s["d"] * s["experts"] + 3 * s["d"] * s["fs"], "dense": 3 * s["d"] * s["fd"],
    }


def _layers_elements(doc: dict, experts: float) -> float:
    """Matrix elements of every layer with ``experts`` routed experts a MoE layer."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    return (
        s["n_global"] * lw["full"] + s["n_window"] * lw["sliding"] + s["n_dense"] * lw["dense"]
        + s["n_moe"] * (lw["moe_fixed"] + experts * lw["expert"])
    )


def param_count(doc: dict) -> int:
    """Every parameter held here, vectors included (the count
    ``ModelConfig.param_count`` has to agree with)."""
    s = _sizes(doc)
    return int(_layers_elements(doc, s["held"])) + 2 * s["d"] * s["vocab"] + s["d"] * (2 * s["layers"] + 1)


def weight_bytes(doc: dict) -> float:
    """Bytes of weights a step streams, once: every layer's as served (int8)
    with the experts HELD here all counted (a 256-row chunk's 2,048 choices
    over 256 experts reach every one of the 32 held; the all-held-experts
    einsum of a plain decode step reads them all), the shared expert, the
    router and the output head. The embedding is a row gather."""
    s = _sizes(doc)
    return INT8 * (_layers_elements(doc, s["held"]) + s["d"] * s["vocab"])


def row_bytes(doc: dict) -> int:
    """One position's K and V row in ONE layer (either kind: 8 heads of 128)."""
    s = _sizes(doc)
    return 2 * s["kv"] * s["hd"] * KV_BYTES


def _engine_option(doc: dict, key: str, default):
    return (doc.get("engine_options") or {}).get(key, default)


def ring_rows(doc: dict) -> int:
    """Rows a lane keeps of a sliding layer as the engine sizes the ring
    (``agentainer_tpu/models/llama.ring_rows``): window + a prefill chunk's
    rows, rounded up to the K/V block the kernels read, never more than the
    arena."""
    s = _sizes(doc)
    chunk = int(_engine_option(doc, "prefill_chunk", PREFILL_CHUNK))
    up = lambda n: -(-n // RING_BLOCK) * RING_BLOCK  # noqa: E731
    return min(up(s["window"] + chunk), up(int(_engine_option(doc, "max_seq", doc["max_position_embeddings"]))))


def kv_bytes_per_token(doc: dict, context: float | None = None) -> float:
    """Bytes of cache a token of context costs a reader: a K and a V row in
    every layer that still holds it. ``context`` absent: a token inside the
    window (every layer holds it). At ``context`` the MEAN over the context's
    tokens: the full layers hold every one, the sliding layers the last
    ``window`` of them."""
    s = _sizes(doc)
    if context is None or context <= 0:
        return float(s["layers"] * row_bytes(doc))
    return row_bytes(doc) * (s["n_global"] * context + s["n_window"] * min(context, s["window"])) / context


def kv_resident_bytes_per_lane(doc: dict) -> int:
    """What a lane's cache occupies: ``max_seq`` rows of every full layer,
    ``ring_rows`` of every sliding layer."""
    s = _sizes(doc)
    max_seq = int(_engine_option(doc, "max_seq", doc["max_position_embeddings"]))
    return row_bytes(doc) * (s["n_global"] * max_seq + s["n_window"] * ring_rows(doc))


def decode_step_bytes(doc: dict, live_kv_tokens: float = 0.0, kv_bytes: float | None = None, lanes: float = 1.0) -> float:
    """Bytes one decode step (one token for every lane) must read: the weights
    as served, once, and the K and V rows the step's queries see. ``kv_bytes``
    given (a reader's, from the engine's counters): those. Otherwise
    ``live_kv_tokens`` rows spread over ``lanes`` lanes of equal context, a
    sliding layer counted at ``min(context, window)`` rows a lane."""
    if kv_bytes is None:
        s = _sizes(doc)
        context = live_kv_tokens / max(lanes, 1e-9)
        kv_bytes = lanes * row_bytes(doc) * (s["n_global"] * context + s["n_window"] * min(context, s["window"]))
    return weight_bytes(doc) + kv_bytes


def attended_rows(doc: dict, prompt_tokens: float) -> dict:
    """Sum over a prompt's tokens of the keys each attends to, by kind of
    layer: ``P² / 2`` where every key is seen, and ``W² / 2 + (P - W) · W`` in
    a sliding layer once the prompt passes the window."""
    s = _sizes(doc)
    p, w = float(prompt_tokens), float(s["window"])
    return {"global": p * p / 2.0, "window": p * p / 2.0 if p <= w else w * w / 2.0 + (p - w) * w}


def chunk_rows_read(doc: dict, prompt_tokens: int, chunk: int = PREFILL_CHUNK) -> dict:
    """Sum over a prompt's chunks of the K/V rows a chunk's queries read of
    its lane, by kind of layer, each row counted once a launch: a full layer's
    rows up to the chunk's end, a sliding layer's last ``window + chunk − 1``
    of them. And the launches that feed the prompt."""
    s = _sizes(doc)
    ends = [min(c * chunk, prompt_tokens) for c in range(1, math.ceil(prompt_tokens / chunk) + 1)]
    return {
        "global": float(sum(ends)), "window": float(sum(min(e, s["window"] + chunk - 1) for e in ends)),
        "launches": len(ends),
    }


def token_matmul_flops(doc: dict, routed: bool = True) -> float:
    """Matmul FLOPs (2 per multiply-add) of one token through every layer's
    weights on this chip, the output head left out (``head_flops``):
    ``routed``: its chosen experts that are held here, k · held / E on
    average; otherwise every held expert."""
    s = _sizes(doc)
    return 2.0 * _layers_elements(doc, s["k"] * s["held"] / s["experts"] if routed else s["held"])


def head_flops(doc: dict, rows: float) -> float:
    s = _sizes(doc)
    return 2.0 * rows * s["d"] * s["vocab"]


def attention_flops(doc: dict, global_pairs: float, window_pairs: float) -> float:
    """Scores and the combination (4 FLOPs a head dim and pair) of
    ``global_pairs`` (query, key) pairs in a full layer and ``window_pairs``
    in a sliding one, each kind at its own count of query heads."""
    s = _sizes(doc)
    return 4.0 * s["hd"] * (s["n_global"] * s["h_full"] * global_pairs + s["n_window"] * s["h_swa"] * window_pairs)


def prefill_flops(
    doc: dict, n_tokens: float, mean_context: float, routed: bool = True, mean_window_context: float | None = None
) -> float:
    """Matmul FLOPs to prefill ``n_tokens`` on this chip whose mean attendable
    context is ``mean_context`` in a full layer and ``mean_window_context`` in
    a sliding layer (absent: ``min(mean_context, window)``). The output head
    runs on ONE row of a chunk (the program's, and any implementation's: a
    prefill needs its last row's logits alone)."""
    s = _sizes(doc)
    if mean_window_context is None:
        mean_window_context = min(mean_context, s["window"])
    return (
        n_tokens * token_matmul_flops(doc, routed) + head_flops(doc, n_tokens / PREFILL_CHUNK)
        + attention_flops(doc, n_tokens * mean_context, n_tokens * mean_window_context)
    )


def mixed_step_floor_s(doc: dict, rows: float, lanes: float, attended: dict, peak: dict) -> float:
    """The least time one launch that carries ``rows`` prefill rows of one
    lane and one decode step of ``lanes`` lanes can take on a chip of
    ``peak`` (``bf16_flops``, ``hbm_bytes_per_s``): the larger of the bytes
    ANY implementation must move over the memory's rate (the weights as
    served, once; the K and V rows the launch's queries see, each once:
    ``attended["global_rows"]`` / ``["window_rows"]`` a layer of the kind) and
    the model's FLOPs over the peak (``rows + lanes`` tokens through the
    weights with their routed experts, ``1 + lanes`` rows through the head,
    ``attended["global_pairs"]`` / ``["window_pairs"]`` (query, key) pairs a
    layer of the kind). A floor: what an implementation adds (the rows it
    writes, a second read of a weight, padding) is not in it, so the launch's
    share of it cannot pass 100 %."""
    s = _sizes(doc)
    need_bytes = weight_bytes(doc) + row_bytes(doc) * (
        s["n_global"] * attended["global_rows"] + s["n_window"] * attended["window_rows"]
    )
    need_flops = (
        (rows + lanes) * token_matmul_flops(doc) + head_flops(doc, 1 + lanes)
        + attention_flops(doc, attended["global_pairs"], attended["window_pairs"])
    )
    return max(need_bytes / peak["hbm_bytes_per_s"], need_flops / peak["bf16_flops"])
