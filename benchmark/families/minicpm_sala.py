"""The ``minicpm_sala`` family: MiniCPM-SALA's block as
``agentainer_tpu/models/hybrid.py`` computes it (block-sparse attention that
chooses 64 key blocks a query from pooled keys beside lightning linear
attention, a dense SwiGLU in every layer, MiniCPM's µP scalings).
``families/llama.py`` says what a family answers; the plain reference is
``families/minicpm_sala_reference.py``. Beside the usual answers: the bytes and
FLOPs a call of each new piece needs (``sparse_index_*``, ``sparse_attend_*``,
``lightning_step_bytes``, ``lightning_chunk_flops``), the bytes a decode step
must move and the least time it can take (``decode_step_bytes``,
``decode_step_floor_s``), and ``selection_agreement``: the share of the
program's chosen block sets that equal the reference's, for the builder's chip
run. Nothing heavy is imported at module level.

A configuration file of this family holds the published ``config.json`` keys
as run, and the sparse sizes the published keys do not give under
``sparse_config`` (``assumed`` says where they are from).
"""

from __future__ import annotations

REHEARSAL_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 512, "num_hidden_layers": 4, "mup_denominator": 4, "dim_model_base": 32,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "sparse_config": {"kernel_size": 8, "kernel_stride": 4, "block_size": 16, "init_blocks": 1,
                      "window_size": 32, "topk": 6, "dense_len": 96},
}

# four layers at published widths with both kinds and two sparse ones, the
# second behind a sparse and two lightning layers (``numerics_mixer_types``
# of the configuration file): its float32 copy for the reference is 6.7 GB
# (2.4 GB of it the vocabulary's two matrices) beside 1.7 GB of int8 weights
N_LAYERS = 4
N_DECODE = 8
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

STATE_BYTES = 4  # the recurrent state is float32
ARENA_BYTES = 2  # K/V rows and pooled keys are bf16
INT8 = 1


def _kinds(doc: dict, n_layers: int) -> tuple:
    types = list(doc["mixer_types"])
    if n_layers != len(types):
        # the numerics check's few layers: the kinds the file names for it, else the first ones
        types = list(doc.get("numerics_mixer_types") or types)[:n_layers]
    if len(types) < n_layers or set(types) - {SPARSE, LIGHTNING}:
        raise ValueError(f"mixer_types names {sorted(set(types))} for {n_layers} layers")
    return tuple("sparse" if t == SPARSE else "lightning" for t in types)


def model_config(doc: dict, n_layers: int | None = None):
    """The program's ``ModelConfig`` from a configuration file whose top
    level holds MiniCPM-SALA's published ``config.json`` keys, as run. A
    program without the fields of this family's mixers cannot build it
    (``TypeError``: the parent of the PR that adds the family fails the cell
    cleanly)."""
    from agentainer_tpu.models.configs import ModelConfig

    if doc.get("attention_bias") or doc.get("tie_word_embeddings"):
        raise ValueError("the program's block has no attention bias and unties the output head")
    if doc.get("attn_use_rope") or not doc.get("lightning_use_rope"):
        raise ValueError("this family's sparse layers carry no rotary embedding and its lightning layers one")
    if not (doc.get("qk_norm") and doc.get("use_output_gate") and doc.get("use_output_norm") and doc.get("attn_use_output_gate")):
        raise ValueError("the program's two mixers have the q/k norms, the output gates and the output norm")
    if int(doc["lightning_nh"]) != int(doc["lightning_nkv"]):
        raise ValueError("the program's lightning rule has one key head a value head")
    layers = int(n_layers if n_layers is not None else doc["num_hidden_layers"])
    sp = doc["sparse_config"]
    return ModelConfig(
        name=doc["name"],
        vocab_size=int(doc["vocab_size"]),
        dim=int(doc["hidden_size"]),
        n_layers=layers,
        n_heads=int(doc["num_attention_heads"]),
        n_kv_heads=int(doc["num_key_value_heads"]),
        ffn_dim=int(doc["intermediate_size"]),
        max_seq_len=int(doc["max_position_embeddings"]),
        rope_theta=0.0,
        norm_eps=float(doc["rms_norm_eps"]),
        qk_norm=True,
        head_size=int(doc["head_dim"]),
        layer_kinds=_kinds(doc, layers),
        kda_heads=int(doc["lightning_nh"]),
        kda_head_dim=int(doc["lightning_head_dim"]),
        lightning_rope_theta=float(doc["rope_theta"]),
        sparse_kernel=int(sp["kernel_size"]),
        sparse_stride=int(sp["kernel_stride"]),
        sparse_block=int(sp["block_size"]),
        sparse_init_blocks=int(sp["init_blocks"]),
        sparse_window=int(sp["window_size"]),
        sparse_topk=int(sp["topk"]),
        sparse_dense_len=int(sp["dense_len"]),
        embed_scale=float(doc["scale_emb"]),
        # the depth the scaling was published for, whatever number of layers runs
        residual_scale=float(doc["scale_depth"]) / float(doc["mup_denominator"]) ** 0.5,
        logit_divisor=float(doc["hidden_size"]) / float(doc["dim_model_base"]),
        n_dense_layers=layers,
        dense_ffn_dim=int(doc["intermediate_size"]),
    )


def _launch_rows(dense_len: int) -> int:
    """Rows a launch of the numerics check's prefill: the engine's 256, or at
    rehearsal widths a count that splits a pooling kernel."""
    return 256 if dense_len >= 1024 else 40


def numerics_sizes(doc: dict) -> dict:
    """A prefill two launches past ``dense_len`` (8,704 rows at published
    sizes), so that the 32 compared prefill positions and the 8 decode steps
    all choose their blocks (137 of them, 64 chosen, 33 forced) and the
    pooled-key append has crossed every chunk boundary on the way."""
    sp = doc["sparse_config"]
    chunk = _launch_rows(int(sp["dense_len"]))
    prefill = -(-(int(sp["dense_len"]) + 2 * chunk) // chunk) * chunk  # whole launches: the last one's rows are compared
    block = int(sp["block_size"])
    return {
        "layers": min(N_LAYERS, int(doc["num_hidden_layers"])), "prefill": prefill, "decode": N_DECODE,
        "cache_len": -(-(prefill + N_DECODE) // block) * block,
    }


def program(cfg, dev, dtype, cache_len: int) -> dict:
    """The program's side: seeded synthetic weights as served (its own int8
    generator; the vectors stay dense), a fresh cache as the model builds it
    (K/V rows, pooled keys, the lightning state), a prefill fed a launch of
    ``_launch_rows`` rows at a time through that cache as the engine feeds it (the
    logits of the LAST launch's rows come back: the check compares the last 32)
    and a jitted one-token decode. Signatures as ``families/llama.py``."""
    import jax
    import jax.numpy as jnp

    from agentainer_tpu.engine.quant import synthetic_quantized_params
    from agentainer_tpu.models.hybrid import plan_hybrid
    from agentainer_tpu.models.llama import forward, init_cache

    params = synthetic_quantized_params(cfg, dtype, device=dev)
    plan = plan_hybrid(cfg)
    rows = _launch_rows(cfg.sparse_dense_len)

    @jax.jit
    def launch(params, cache, toks, start):
        pos = (start + jnp.arange(toks.shape[0], dtype=jnp.int32))[None]
        logits, cache = forward(params, cfg, toks[None], pos, cache, cache_attn_impl=plan)
        return logits[0], cache

    def prefill(params, cache, toks):
        for start in range(0, toks.shape[0], rows):
            logits, cache = launch(params, cache, toks[start : start + rows], jnp.int32(start))
        return logits, cache

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
    ``families/minicpm_sala_reference.py`` from the program's pytree (int8
    leaves dequantized), and ``forward(weights, tokens, act) -> logits [T, V]``."""
    import importlib

    import jax
    import jax.numpy as jnp

    from agentainer_tpu.ops.quant import QTensor
    from agentainer_tpu.ops.sparse_attention import SparseSizes

    block = importlib.import_module("families.minicpm_sala_reference")

    def dense(x):
        return (x.q.astype(jnp.float32) * x.scale.astype(jnp.float32)) if isinstance(x, QTensor) else x.astype(jnp.float32)

    def layer_of(group: str, i: int) -> dict:
        return {k: dense(jax.tree.map(lambda a: a[i], v)) for k, v in params[group].items()}

    layers, seen = [], {"sparse": 0, "lightning": 0}
    for i, kind in enumerate(cfg.layer_kinds):
        lp = {k: dense(v[i]) for k, v in params["layers"].items()}
        lp.update(layer_of(kind, seen[kind]))
        seen[kind] += 1
        lp.update(layer_of("dense", i))
        layers.append(lp)
    weights = {
        "embed": dense(params["embed"]),
        "layers": layers,
        "final_norm": dense(params["final_norm"]),
        "lm_head": dense(params["lm_head"]),
    }
    kw = dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, lin_heads=cfg.kda_heads,
        lin_dim=cfg.kda_head_dim, norm_eps=cfg.norm_eps, rope_theta=cfg.lightning_rope_theta,
        sparse=SparseSizes.of(cfg)._asdict(), embed_scale=cfg.embed_scale, residual_scale=cfg.residual_scale,
        logit_divisor=cfg.logit_divisor,
    )
    return weights, lambda w, tokens, act, **more: block.forward(w, tokens, act=act, **kw, **more)


def selection_agreement(doc: dict, seed: int, dev=None, dtype=None) -> dict:
    """The share of (query row past ``dense_len``, K/V head) selections of the
    program's prefill that equal the reference's, a sparse layer at a time, at
    the numerics check's sizes and tokens. The program's sets are read where it
    makes them (``ops/sparse_attention.select_blocks``, wrapped here for the
    run: nothing of the program is changed); the reference is NOT fed them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from agentainer_tpu.ops import sparse_attention

    sizes = numerics_sizes(doc)
    cfg = model_config(doc, n_layers=sizes["layers"])
    dev = dev or jax.devices()[0]
    dtype = dtype or (jnp.bfloat16 if dev.platform == "tpu" else jnp.float32)
    taken, plain = [], sparse_attention.select_blocks

    def tapped(scores, positions, s):
        blocks = plain(scores, positions, s)
        jax.debug.callback(lambda p, b: taken.append((np.asarray(p), np.asarray(b))), positions, blocks, ordered=True)
        return blocks

    sparse_attention.select_blocks = tapped
    try:
        prog = program(cfg, dev, dtype, sizes["cache_len"])
        tokens = jnp.asarray(np.random.default_rng(seed).integers(3, cfg.vocab_size, size=sizes["prefill"]), jnp.int32)
        jax.block_until_ready(prog["prefill"](prog["params"], prog["new_cache"](), tokens))
        jax.effects_barrier()
    finally:
        sparse_attention.select_blocks = plain
    weights, forward = reference(prog["params"], cfg)
    chosen: list = []
    jax.block_until_ready(forward(weights, tokens, lambda x: x, selection=chosen))
    n_sparse, n_blocks = cfg.layer_kinds.count("sparse"), -(-sizes["prefill"] // cfg.sparse_block)
    equal = np.zeros(n_sparse)
    rows = 0
    for call, (pos, blocks) in enumerate(taken):  # a launch's sparse layers in order, launch after launch
        layer, pos = call % n_sparse, pos[0]
        mine = (blocks[0][..., None] == np.arange(n_blocks)).any(axis=-2)  # [t, KV, n_blocks]
        past = pos >= cfg.sparse_dense_len
        same = (mine == np.asarray(chosen[layer])[pos]).all(axis=-1)  # [t, KV]
        equal[layer] += same[past].sum()
        rows += int(past.sum()) * same.shape[1] if layer == 0 else 0
    return {
        "selections_compared": rows, "sparse_layers": n_sparse,
        "share_equal_by_layer": [float(e / rows) if rows else None for e in equal],
        "share_equal": float(equal.sum() / (rows * n_sparse)) if rows else None,
        "dtype": str(jnp.dtype(dtype)), "platform": dev.platform,
    }


# -- the yardstick's arithmetic, from the file's sizes alone ---------------------


def _sizes(doc: dict) -> dict:
    layers = int(doc["num_hidden_layers"])
    kinds = _kinds(doc, layers)
    sp = doc["sparse_config"]
    return {
        "d": int(doc["hidden_size"]), "layers": layers, "vocab": int(doc["vocab_size"]),
        "f": int(doc["intermediate_size"]), "n_sparse": kinds.count("sparse"), "n_lightning": kinds.count("lightning"),
        "h": int(doc["num_attention_heads"]), "kv": int(doc["num_key_value_heads"]), "hd": int(doc["head_dim"]),
        "lh": int(doc["lightning_nh"]), "dk": int(doc["lightning_head_dim"]),
        "kernel": int(sp["kernel_size"]), "stride": int(sp["kernel_stride"]), "block": int(sp["block_size"]),
        "init": int(sp["init_blocks"]), "window": int(sp["window_size"]), "topk": int(sp["topk"]),
        "dense_len": int(sp["dense_len"]),
    }


def layer_weight_elements(doc: dict) -> dict:
    """Matrix elements by part (vectors left out: a sparse layer's two norms
    are 256 elements, a lightning layer's norms and slopes 4,384)."""
    s = _sizes(doc)
    return {
        "sparse": 3 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"],  # q, gate, out; k, v
        "lightning": 5 * s["d"] * s["lh"] * s["dk"],  # q, k, v, gate, out
        "ffn": 3 * s["d"] * s["f"],
    }


def param_count(doc: dict) -> int:
    """Every parameter, vectors included (the count ``ModelConfig.param_count``
    has to agree with)."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    sparse_vectors = 2 * s["hd"]
    lightning_vectors = 2 * s["dk"] + s["lh"] * s["dk"] + s["lh"]
    return (
        s["n_sparse"] * (lw["sparse"] + sparse_vectors) + s["n_lightning"] * (lw["lightning"] + lightning_vectors)
        + s["layers"] * (lw["ffn"] + 2 * s["d"]) + 2 * s["d"] * s["vocab"] + s["d"]
    )


def weight_bytes(doc: dict) -> float:
    """Bytes of weights a step streams: every layer's matrices as served
    (int8) and the output head (the embedding is a row gather)."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    return INT8 * (
        s["n_sparse"] * lw["sparse"] + s["n_lightning"] * lw["lightning"] + s["layers"] * lw["ffn"] + s["d"] * s["vocab"]
    )


def state_bytes_per_lane(doc: dict) -> int:
    """The per-lane lightning state (float32 ``[H, dk, dk]`` a layer); there
    is no conv."""
    s = _sizes(doc)
    return s["n_lightning"] * s["lh"] * s["dk"] * s["dk"] * STATE_BYTES


def kv_bytes_per_token(doc: dict) -> int:
    """Positional bytes a token adds: a K and a V row in every sparse layer."""
    s = _sizes(doc)
    return 2 * s["n_sparse"] * s["kv"] * s["hd"] * ARENA_BYTES


def pooled_bytes_per_token(doc: dict) -> float:
    """The pooled-key leaf's bytes a token: one row of ``[KV, hd]`` every
    ``stride`` tokens in every sparse layer."""
    s = _sizes(doc)
    return s["n_sparse"] * s["kv"] * s["hd"] * ARENA_BYTES / s["stride"]


def cache_bytes(doc: dict) -> dict:
    """The cache's leaves at the configuration's lanes and context, in bytes."""
    opts = doc.get("engine_options") or {}
    lanes, seq = int(opts.get("max_batch", 1)), int(opts.get("max_seq", doc["max_position_embeddings"]))
    return {
        "k+v": lanes * seq * kv_bytes_per_token(doc), "ck": int(lanes * seq * pooled_bytes_per_token(doc)),
        "state": lanes * state_bytes_per_lane(doc),
    }


def rows_read(doc: dict, context: float) -> float:
    """Rows of K (and of V) one sparse layer's query reads of a lane holding
    ``context`` rows, a K/V head: all of them up to ``dense_len``, then the
    ``topk`` chosen blocks."""
    s = _sizes(doc)
    return context if context <= s["dense_len"] else min(context, s["topk"] * s["block"])


def sparse_index_bytes(doc: dict, context: float) -> float:
    """Stage 1 of one sparse layer for one query row: every visible pooled key."""
    s = _sizes(doc)
    return max(context - s["kernel"] + 1, 0) / s["stride"] * s["kv"] * s["hd"] * ARENA_BYTES


def sparse_index_flops(doc: dict, context: float, rows: int = 1) -> float:
    """Stage 1's scores: ``rows`` queries of every head against every visible
    pooled key of the head's group (2 FLOPs a multiply-add)."""
    s = _sizes(doc)
    return 2.0 * rows * s["h"] * s["hd"] * max(context - s["kernel"] + 1, 0) / s["stride"]


def sparse_attend_bytes(doc: dict, context: float) -> float:
    """One lane's step through one sparse layer's attention: the K and V rows
    it reads, each K/V head's own."""
    s = _sizes(doc)
    return 2.0 * rows_read(doc, context) * s["kv"] * s["hd"] * ARENA_BYTES


def sparse_attend_flops(doc: dict, context: float, rows: int = 1, masked: bool = False) -> float:
    """Scores and the value sum of ``rows`` queries: over the rows read, or
    (``masked``: a chunk's row-by-block mask) over every row up to
    ``context``, the masked ones computed and thrown away."""
    s = _sizes(doc)
    return 4.0 * rows * s["h"] * s["hd"] * (context if masked else rows_read(doc, context))


def lightning_step_bytes(doc: dict, lanes: float) -> float:
    """One call of the lightning step (one layer): the state of every lane it
    is given read and written."""
    s = _sizes(doc)
    return 2.0 * lanes * s["lh"] * s["dk"] * s["dk"] * STATE_BYTES


def lightning_chunk_flops(doc: dict, n_tokens: int, chunk: int = 64) -> float:
    """Matmul FLOPs of the chunked rule for ``n_tokens`` of one layer
    (projections not counted): per chunk of C tokens and head, Q Kᵀ and its
    product with V (2·C²·dk + 2·C²·dk), Q S₀ and the state update (2 · 2·C·dk²)."""
    s = _sizes(doc)
    per_chunk = 4.0 * chunk * chunk * s["dk"] + 4.0 * chunk * s["dk"] * s["dk"]
    return s["lh"] * per_chunk * (n_tokens / chunk)


def decode_step_bytes(doc: dict, contexts: list, live_lanes: float | None = None) -> float:
    """Bytes one decode step (one token for each lane in ``contexts``, a
    lane's rows held) must move: the weights as served once, the lightning
    state of the stepping lanes read AND written, the K and V rows each sparse
    layer reads of each lane, and the pooled keys its selection scores."""
    s = _sizes(doc)
    lanes = live_lanes if live_lanes is not None else float(len(contexts))
    per_layer = sum(
        sparse_attend_bytes(doc, c) + (sparse_index_bytes(doc, c) if c > s["dense_len"] else 0.0) for c in contexts
    )
    return weight_bytes(doc) + 2.0 * lanes * state_bytes_per_lane(doc) + s["n_sparse"] * per_layer


def decode_step_floor_s(doc: dict, contexts: list, hbm_bytes_per_s: float, live_lanes: float | None = None) -> float:
    """The least time a decode step can take: its bytes over the chip's rate
    (its FLOPs, 2 a weight and lane, are a hundredth of the chip's in that
    time: the step is memory-bound at 8 lanes)."""
    return decode_step_bytes(doc, contexts, live_lanes) / hbm_bytes_per_s


def prefill_flops(doc: dict, n_tokens: int, mean_context: float, routed: bool = True) -> float:
    """Matmul FLOPs (2 per multiply-add) to prefill ``n_tokens`` whose mean
    attendable context is ``mean_context``: every weight a token meets (the
    FFN is dense: ``routed`` changes nothing), the lightning rule, and a
    sparse layer's selection and attention as the program runs a chunk (the
    row-by-block mask: every row up to the context is scored)."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    matmul = 2.0 * (
        s["n_sparse"] * lw["sparse"] + s["n_lightning"] * lw["lightning"] + s["layers"] * lw["ffn"] + s["d"] * s["vocab"]
    )
    sparse = sparse_attend_flops(doc, mean_context, n_tokens, masked=True)
    if mean_context > s["dense_len"]:
        sparse += sparse_index_flops(doc, mean_context, n_tokens)
    return n_tokens * matmul + s["n_lightning"] * lightning_chunk_flops(doc, n_tokens) + s["n_sparse"] * sparse
