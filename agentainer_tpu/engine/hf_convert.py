"""HuggingFace checkpoint → engine params converter.

This is the TPU-native analogue of the reference's image builder
(pkg/docker/builder.go:98-187: turn a user-supplied artifact into a
runnable image): here the user-supplied artifact is a HF-format Llama /
Mixtral checkpoint directory (config.json + *.safetensors, possibly
sharded), and "building" means mapping it onto the engine's stacked-layer
pytree (models/llama.py) so deploy can point at any published checkpoint.

Weight-name mapping (HF Llama convention → ours). HF stores projections as
[out, in] torch Linear weights; our forward uses x @ W, so every projection
transposes. Our RoPE is the same rotate_half layout HF ships, so q/k need
no permutation.

    model.embed_tokens.weight            → embed                [V, D]
    …layers.{i}.input_layernorm.weight   → layers.attn_norm[i]  [D]
    …layers.{i}.self_attn.{q,k,v}_proj   → wq/wk/wv[i]          [D, H*hd]ᵀ
    …layers.{i}.self_attn.o_proj         → wo[i]                [H*hd, D]ᵀ
    …layers.{i}.post_attention_layernorm → layers.mlp_norm[i]   [D]
    …layers.{i}.mlp.{gate,up,down}_proj  → w_gate/w_up/w_down   ᵀ
    model.norm.weight                    → final_norm           [D]
    lm_head.weight (or tied embeddings)  → lm_head              [D, V]ᵀ

Mixtral MoE:
    …block_sparse_moe.gate               → router[i]            [D, E]ᵀ
    …experts.{e}.w1 / w3 / w2            → w_gate/w_up/w_down[i,e]ᵀ

OLMoE (``num_experts``, ``norm_topk_prob``; QK-norm where its weights are):
    …mlp.gate                            → router[i]            [D, E]ᵀ
    …mlp.experts.{e}.{gate,up,down}_proj → w_gate/w_up/w_down[i,e]ᵀ
    …self_attn.{q,k}_norm.weight         → q_norm/k_norm[i]     [H*hd]

``model_type: mistral4`` (Mistral-Small-4) and ``model_type: solar_open2``
(Solar-Open2): ``config_from_hf`` gives the hybrid block's configuration (MLA
in every layer; KDA beside gated NoPE GQA); ``load_hf_params`` refuses the
hybrid block by name until a checkpoint is there to check tensor names against.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from ..models.configs import ModelConfig


def is_hf_checkpoint(path: str | Path) -> bool:
    p = Path(path).expanduser()
    return p.is_dir() and any(p.glob("*.safetensors"))


def _open_shards(path: Path) -> dict:
    """name → (shard_path). Handles single-file and index-sharded layouts."""
    index = path / "model.safetensors.index.json"
    if index.exists():
        weight_map = json.loads(index.read_text())["weight_map"]
        return {name: path / shard for name, shard in weight_map.items()}
    shards = sorted(path.glob("*.safetensors"))
    out: dict[str, Path] = {}
    from safetensors import safe_open

    for shard in shards:
        with safe_open(shard, framework="np") as f:
            for name in f.keys():
                out[name] = shard
    return out


class _Loader:
    """Lazily opens shards; tensors come out as numpy (bf16 via ml_dtypes)."""

    def __init__(self, path: Path):
        self.map = _open_shards(path)
        self._handles: dict[Path, object] = {}

    def __contains__(self, name: str) -> bool:
        return name in self.map

    def get(self, name: str) -> np.ndarray:
        from safetensors import safe_open

        shard = self.map[name]
        if shard not in self._handles:
            self._handles[shard] = safe_open(shard, framework="np").__enter__()
        return self._handles[shard].get_tensor(name)


def config_from_hf(path: str | Path) -> ModelConfig:
    """Derive a ModelConfig from the checkpoint's own config.json."""
    path = Path(path).expanduser()
    doc = json.loads((path / "config.json").read_text())
    if doc.get("model_type") == "mistral4":
        return _mistral4_config(doc)
    if doc.get("model_type") == "solar_open2":
        return _solar_open2_config(doc)
    # Mixtral publishes ``num_local_experts``, OLMoE ``num_experts``,
    # SmallThinker ``moe_num_primary_experts``
    smallthinker = "moe_num_primary_experts" in doc
    n_experts = int(
        doc.get("num_local_experts") or doc.get("num_experts") or doc.get("moe_num_primary_experts") or 0
    )
    heads, dim = int(doc["num_attention_heads"]), int(doc["hidden_size"])
    # a head width the model states is kept only where it is not the derived one
    head_size = int(doc.get("head_dim") or 0)
    window_layers = tuple(int(x) for x in doc.get("sliding_window_layout") or ())
    return ModelConfig(
        name=doc.get("model_type", "hf") + "-import",
        vocab_size=int(doc["vocab_size"]),
        dim=dim,
        n_layers=int(doc["num_hidden_layers"]),
        n_heads=heads,
        n_kv_heads=int(doc.get("num_key_value_heads", heads)),
        head_size=head_size if head_size * heads != dim else 0,
        ffn_dim=int(doc.get("intermediate_size") or doc["moe_ffn_hidden_size"]),
        max_seq_len=int(doc.get("max_position_embeddings", 8192)),
        rope_theta=float(doc.get("rope_theta", 500_000.0)),
        norm_eps=float(doc.get("rms_norm_eps", 1e-5)),
        n_experts=n_experts,
        experts_per_token=int(doc.get("num_experts_per_tok") or doc.get("moe_num_active_primary_experts") or 2),
        # Mixtral has no such key and always renormalises; OLMoE states it
        moe_renormalize=bool(doc.get("norm_topk_prob", True)),
        # no config.json key states it: the checkpoint has the norm's weights
        # or it has not (a model's name decides nothing here)
        qk_norm="model.layers.0.self_attn.q_norm.weight" in _open_shards(path),
        # the per-layer layouts, as published (the keys the benchmark's
        # ``families/smallthinker.model_config`` reads)
        window=int(doc.get("sliding_window_size") or 0) if any(window_layers) else 0,
        window_layers=window_layers if any(window_layers) else (),
        rope_layers=tuple(int(x) for x in doc.get("rope_layout") or ()),
        # no key of that config.json states the gate's activation or where the
        # router reads: they follow the keys only that block publishes (ReGLU
        # and the layer's input; ``moe_enable_early_router`` where it is given)
        ffn_act="relu" if smallthinker else "silu",
        early_router=bool(doc.get("moe_enable_early_router", smallthinker)),
    )


def _mistral4_config(doc: dict) -> ModelConfig:
    """``model_type: mistral4`` (Mistral-Small-4's text decoder): the hybrid
    block with latent attention in every layer, from the keys the benchmark's
    ``families/mistral4.model_config`` reads. What the keys do not state (the
    softmax router, YaRN's ``m²`` on the softmax scale, the query scale's
    formula) is the family's convention, listed under ``assumed`` in the
    benchmark's configuration file."""
    rope = doc["rope_parameters"]
    if rope.get("rope_type") != "yarn" or float(rope["mscale"]) != float(rope["mscale_all_dim"]):
        raise ValueError("mistral4: the rotary embedding served is YaRN with unscaled cos and sin")
    if int(doc.get("n_group", 1)) != 1 or int(doc.get("topk_group", 1)) != 1 or int(doc["first_k_dense_replace"]):
        raise ValueError("mistral4: no group limit on the router and no dense layer is served")
    if float(doc.get("routed_scaling_factor", 1.0)) != 1.0 or not doc.get("norm_topk_prob", True):
        raise ValueError("mistral4: the softmax rule served renormalises the chosen experts and scales them by 1")
    layers = int(doc["num_hidden_layers"])
    return ModelConfig(
        name="mistral4-import",
        vocab_size=int(doc["vocab_size"]),
        dim=int(doc["hidden_size"]),
        n_layers=layers,
        n_heads=int(doc["num_attention_heads"]),
        n_kv_heads=int(doc["num_key_value_heads"]),
        ffn_dim=int(doc["moe_intermediate_size"]),
        max_seq_len=int(doc["max_position_embeddings"]),
        rope_theta=float(rope["rope_theta"]),
        norm_eps=float(doc["rms_norm_eps"]),
        n_experts=int(doc["n_routed_experts"]),
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
        q_pos_scale_beta=float(rope.get("llama_4_scaling_beta", 0.0)),
        n_shared_experts=int(doc["n_shared_experts"]),
        moe_router="softmax",
    )


def _solar_open2_config(doc: dict) -> ModelConfig:
    """``model_type: solar_open2`` (Solar-Open2): the hybrid block with KDA
    beside gated NoPE GQA, from the keys the benchmark's
    ``families/solar_open2.model_config`` reads. What the keys do not state
    (the sigmoid router with a selection bias, the gate as wide as the output,
    KDA's low-rank pairs as the Kimi Linear report has them) is the family's
    convention, listed under ``assumed`` in the benchmark's configuration
    file. The config only: the weights' names wait for a checkpoint's index."""
    lin = doc["linear_attn_config"]
    layers = int(doc["num_hidden_layers"])
    gqa = {int(i) for i in doc["gqa_layers"]}
    if doc.get("use_rope") or doc.get("kda_use_full_proj") or int(doc["first_k_dense_replace"]):
        raise ValueError("solar_open2: served without rotary embedding, with KDA's low-rank pairs and no dense layer")
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError("solar_open2: KDA is served with as many key/value heads as query heads")
    if not doc.get("norm_topk_prob", True):
        raise ValueError("solar_open2: the sigmoid rule served renormalises the chosen experts")
    return ModelConfig(
        name="solar_open2-import",
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
        n_experts=int(doc["n_routed_experts"]),
        experts_per_token=int(doc["num_experts_per_tok"]),
        moe_renormalize=True,
        layer_kinds=tuple("full" if i in gqa else "kda" for i in range(layers)),
        kda_heads=int(lin["num_heads"]),
        kda_head_dim=int(lin["head_dim"]),
        kda_conv=int(lin["short_conv_kernel_size"]),
        delta_neg_eigval=bool(doc.get("kda_allow_neg_eigval", False)),
        attn_gate="full" if doc.get("use_gqa_gate") else False,
        n_shared_experts=int(doc["n_shared_experts"]),
        moe_router="sigmoid",
        moe_scale=float(doc.get("routed_scaling_factor", 1.0)),
    )


def load_hf_params(
    cfg: ModelConfig, path: str | Path, dtype: jnp.dtype = jnp.bfloat16
) -> dict:
    """Map a HF Llama/Mixtral checkpoint directory onto the engine pytree.

    Returns HOST (numpy, ml_dtypes-backed for bf16) arrays: the engine
    device_puts them with its target sharding, so a TP-sharded model is
    never materialized whole on one chip's HBM — required when the weights
    only fit *because* of TP."""
    if cfg.is_hybrid:
        # config_from_hf reads a mistral4 config.json; the tensors' names of
        # the hybrid block's per-kind stacks wait for a checkpoint to check
        # them against
        raise NotImplementedError(
            "no checkpoint key mapping for the hybrid block (layer_kinds) yet: it is served with synthetic weights"
        )
    if cfg.early_router or cfg.ffn_act != "silu":
        # config_from_hf reads that block's config.json; its tensors' names
        # (experts, router) wait for a checkpoint to check them against
        raise NotImplementedError(
            "no checkpoint key mapping for a block with an early router or a ReGLU gate yet: "
            "it is served with synthetic weights"
        )
    p = Path(path).expanduser().resolve()
    ld = _Loader(p)

    def t(name: str) -> np.ndarray:  # torch Linear [out,in] → x@W layout
        return np.asarray(ld.get(name)).astype(dtype).T

    def vec(name: str) -> np.ndarray:
        return np.asarray(ld.get(name)).astype(dtype)

    def stack(fmt: str, transpose: bool = True) -> np.ndarray:
        fn = t if transpose else vec
        return np.stack([fn(fmt.format(i=i)) for i in range(cfg.n_layers)])

    L = "model.layers.{i}."
    layers = {
        "attn_norm": stack(L + "input_layernorm.weight", transpose=False),
        "wq": stack(L + "self_attn.q_proj.weight"),
        "wk": stack(L + "self_attn.k_proj.weight"),
        "wv": stack(L + "self_attn.v_proj.weight"),
        "wo": stack(L + "self_attn.o_proj.weight"),
        "mlp_norm": stack(L + "post_attention_layernorm.weight", transpose=False),
    }
    if cfg.qk_norm:
        layers["q_norm"] = stack(L + "self_attn.q_norm.weight", transpose=False)
        layers["k_norm"] = stack(L + "self_attn.k_norm.weight", transpose=False)
    if cfg.is_moe:
        # the two published spellings of a mixture: Mixtral's, else OLMoE's
        mixtral = "model.layers.0.block_sparse_moe.gate.weight" in ld
        moe = "block_sparse_moe" if mixtral else "mlp"
        names = ("w1", "w3", "w2") if mixtral else ("gate_proj", "up_proj", "down_proj")
        layers["router"] = stack(L + moe + ".gate.weight")

        def experts(w: str) -> np.ndarray:  # [L, E, …]
            return np.stack(
                [
                    np.stack(
                        [
                            t(f"model.layers.{i}.{moe}.experts.{e}.{w}.weight")
                            for e in range(cfg.n_experts)
                        ]
                    )
                    for i in range(cfg.n_layers)
                ]
            )

        layers["w_gate"], layers["w_up"], layers["w_down"] = (experts(w) for w in names)
    else:
        layers["w_gate"] = stack(L + "mlp.gate_proj.weight")
        layers["w_up"] = stack(L + "mlp.up_proj.weight")
        layers["w_down"] = stack(L + "mlp.down_proj.weight")

    embed = np.asarray(ld.get("model.embed_tokens.weight")).astype(dtype)
    lm_head = (
        t("lm_head.weight") if "lm_head.weight" in ld else embed.T  # tied
    )
    return {
        "embed": embed,
        "layers": layers,
        "final_norm": vec("model.norm.weight"),
        "lm_head": lm_head,
    }
