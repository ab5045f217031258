"""Model config registry.

The flagship targets are Llama-3-8B (BASELINE.json config #2) and
Mixtral-8x7B expert-parallel (config #5). Tiny variants exist for CI and the
virtual CPU mesh — same code path, small shapes.

All dims are chosen TPU-aware: head_dim and hidden sizes are multiples of
128 (MXU/VPU lane width) for the real configs.
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    ffn_dim: int
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    # MoE (0 experts → dense FFN)
    n_experts: int = 0
    experts_per_token: int = 2
    # gates: softmax over the chosen k (Mixtral) when True; softmax over all
    # experts, top-k kept as it is (OLMoE, ``norm_topk_prob: false``) when False
    moe_renormalize: bool = True
    # RMSNorm with a learned weight over the whole projected query and the
    # whole projected key, before the split into heads and the rotary
    # embedding (OLMoE)
    qk_norm: bool = False

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def param_count(self) -> int:
        """Exact parameter count of models/llama.init_params' pytree."""
        embed = self.vocab_size * self.dim
        per_layer_attn = self.dim * self.dim + 2 * self.dim * (
            self.n_kv_heads * self.head_dim
        ) + self.dim * self.dim
        ffn = 3 * self.dim * self.ffn_dim
        if self.is_moe:
            ffn = self.n_experts * ffn + self.dim * self.n_experts
        per_layer = per_layer_attn + ffn + 2 * self.dim
        if self.qk_norm:
            per_layer += (self.n_heads + self.n_kv_heads) * self.head_dim
        return 2 * embed + self.n_layers * per_layer + self.dim

    def param_bytes(self, dtype_bytes: int = 2) -> int:
        """Rough weight footprint for the HBM planner (bf16 default)."""
        return dtype_bytes * self.param_count()

    def active_param_count(self) -> int:
        """Params a single token's forward actually touches: for MoE only
        ``experts_per_token`` of the expert FFNs contract with each token
        (the engine's dense-einsum MoE still computes all experts on one
        chip, but FLOP-utilization accounting follows the routed math)."""
        if not self.is_moe:
            return self.param_count()
        full_ffn = 3 * self.dim * self.ffn_dim
        unused = (self.n_experts - self.experts_per_token) * full_ffn
        return self.param_count() - self.n_layers * unused

    def flops_per_token(self, context_len: int) -> float:
        """Forward-pass FLOPs to process ONE token with ``context_len``
        tokens of attendable KV (matmul FLOPs = 2 × MACs; norms/rope/softmax
        are O(d) noise and excluded). This is the per-step FLOP model MFU is
        computed from (VERDICT r2 item 2): decode steps pass the current
        sequence position, prefill passes the mean position of the chunk.
        """
        # every weight matmul: 2 FLOPs per weight actually contracted
        matmul = 2.0 * self.active_param_count()
        # attention scores + value combine: q·K^T and p·V, each
        # 2 * heads * head_dim * context MACs → 4 FLOPs per context slot
        attn = 4.0 * self.n_heads * self.head_dim * context_len
        return matmul + self.n_layers * attn


_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


# Llama-3-8B architecture (public numbers: 32 layers, 4096 dim, 32 heads /
# 8 KV heads (GQA), 14336 FFN, 128256 vocab, rope theta 5e5).
LLAMA3_8B = register(
    ModelConfig(
        name="llama3-8b",
        vocab_size=128_256,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        ffn_dim=14_336,
        max_seq_len=8192,
        rope_theta=500_000.0,
    )
)

# Mixtral-8x7B architecture (32 layers, 4096 dim, 32/8 heads, 14336 FFN,
# 8 experts top-2, 32000 vocab, theta 1e6).
MIXTRAL_8X7B = register(
    ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32_000,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        ffn_dim=14_336,
        max_seq_len=32_768,
        rope_theta=1_000_000.0,
        n_experts=8,
        experts_per_token=2,
    )
)

# OLMoE-1B-7B architecture (allenai/OLMoE-1B-7B-0125-Instruct config.json:
# 16 layers, 2048 dim, 16/16 heads of 128, 64 experts of 1024 top-8 with
# un-renormalised gates, QK-norm, 50304 vocab, theta 1e4, context 4096).
OLMOE_1B_7B = register(
    ModelConfig(
        name="olmoe-1b-7b",
        vocab_size=50_304,
        dim=2048,
        n_layers=16,
        n_heads=16,
        n_kv_heads=16,
        ffn_dim=1024,
        max_seq_len=4096,
        rope_theta=10_000.0,
        n_experts=64,
        experts_per_token=8,
        moe_renormalize=False,
        qk_norm=True,
    )
)

# Tiny CI configs — same code paths, CPU-mesh friendly shapes.
TINY = register(
    ModelConfig(
        name="tiny",
        vocab_size=512,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_dim=128,
        max_seq_len=256,
        rope_theta=10_000.0,
    )
)

TINY_MOE = register(
    ModelConfig(
        name="tiny-moe",
        vocab_size=512,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        ffn_dim=128,
        max_seq_len=256,
        rope_theta=10_000.0,
        n_experts=4,
        experts_per_token=2,
    )
)

# OLMoE's block at CI shapes: more experts than lanes, un-renormalised
# gates, QK-norm over all heads.
TINY_OLMOE = register(
    ModelConfig(
        name="tiny-olmoe",
        vocab_size=512,
        dim=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        ffn_dim=32,
        max_seq_len=256,
        rope_theta=10_000.0,
        n_experts=8,
        experts_per_token=2,
        moe_renormalize=False,
        qk_norm=True,
    )
)

# A mid-size single-chip benchmark config: large enough to exercise the MXU,
# small enough to init with random weights quickly on one v5e chip.
BENCH_1B = register(
    ModelConfig(
        name="bench-1b",
        vocab_size=32_000,
        dim=2048,
        n_layers=16,
        n_heads=16,
        n_kv_heads=8,
        ffn_dim=5632,
        max_seq_len=4096,
        rope_theta=500_000.0,
    )
)


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model config {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs() -> list[str]:
    return sorted(_REGISTRY)
