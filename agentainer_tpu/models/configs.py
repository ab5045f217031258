"""Model config registry.

The flagship targets are Llama-3-8B (BASELINE.json config #2) and
Mixtral-8x7B expert-parallel (config #5); OLMoE-1B-7B is the same block with
its two switches; Kimi-Linear-48B-A3B is the hybrid block
(``layer_kinds``: gated-delta linear attention beside NoPE latent attention,
a dense first layer, then sigmoid-routed experts with a shared one —
models/hybrid.py); Olmo-Hybrid-7B is the same block's other pair of mixers
(a gated delta rule with one decay a head beside full softmax attention, a
dense SwiGLU in every layer, the OLMo-2 norm placement); Mistral-Small-4-119B
is that block with latent attention in every layer and no linear mixer (a
low-rank query, a YaRN rotary embedding on the shared key dims); Laguna-XS.2
is that block with full attention beside sliding-window attention (two head
counts, two rotary embeddings, a gate a head; the window layers' rows a
ring); MiniCPM-SALA is block-sparse attention beside lightning attention;
Solar-Open2-250B is KDA with negative eigenvalues beside NoPE GQA with a
full-width gate, every layer sigmoid-routed experts. Tiny variants exist for
CI and the virtual CPU mesh —
same code path, small shapes.

All dims are chosen TPU-aware: head_dim and hidden sizes are multiples of
128 (MXU/VPU lane width) for the real configs.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field


LINEAR_KINDS = ("kda", "gdn", "lightning")
POSITIONAL_KINDS = ("mla", "full", "sparse")
# softmax attention over a RING of the last rows (``window``): the one kind
# that may stand beside another positional kind ("full") in one model
WINDOW_KIND = "swa"


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
    # -- the hybrid block (models/hybrid.py); every default is "not hybrid" --
    # mixer of each layer in order. Linear kinds keep a recurrent state and a
    # short-conv state per lane: "kda" (gated delta rule, a decay per key
    # channel, β = sigmoid) or "gdn" (gated delta rule, one decay a head, keys
    # and values of different widths, a full-rank SiLU output gate).
    # Positional kinds keep rows up to a lane's position: "mla" (latent
    # attention without rotary embedding: one shared latent row per token) or
    # "full" (softmax attention over K/V rows, QK-norm under ``qk_norm``, no
    # rotary embedding where ``rope_theta`` is 0). A model has at most one
    # kind of each, and beside "full" it may have "swa" (Laguna): the same
    # softmax attention over the last ``window`` positions, its rows a ring
    # (``models/llama.ring_rows``), with its own count of query heads
    # (``swa_heads``) and its own rotary embedding (``swa_rope_theta``).
    # "sparse" (MiniCPM-SALA's ``minicpm4`` mixer, InfLLM v2) is "full" whose
    # rows past ``sparse_dense_len`` read a CHOSEN set of key blocks: the
    # ``sparse_*`` fields below, a pooled-key leaf beside ``k`` and ``v``, a
    # norm a head and a full-width sigmoid gate on the output. "lightning"
    # (Lightning Attention-2) is a linear kind with no conv, no β and no
    # erase term: ``S_t = λ_h S_{t−1} + k_tᵀ v_t`` with a constant decay a
    # head, ``kda_heads`` heads of ``kda_head_dim``, rotated by
    # ``lightning_rope_theta``.
    # Empty: every layer is RoPE GQA attention over a K/V arena
    layer_kinds: tuple[str, ...] = ()
    kda_heads: int = 0
    kda_head_dim: int = 0  # of keys (and of values where ``kda_v_dim`` is 0)
    kda_v_dim: int = 0  # of values ("gdn": the state is [kda_head_dim, kda_v_dim] a head)
    kda_conv: int = 4  # short causal depthwise conv over q, k, v
    # β = 2 · sigmoid(·): the transition I − β k kᵀ has an eigenvalue in (−1, 1)
    delta_neg_eigval: bool = False
    # the residual adds the NORMED OUTPUT of each sublayer (x += norm(f(x)),
    # no pre-norm: the OLMo-2 family) instead of x += f(norm(x))
    post_norm: bool = False
    mla_kv_rank: int = 0  # the cached latent c (kv_lora_rank)
    mla_nope_dim: int = 0  # per-head key dims expanded from the latent
    mla_rope_dim: int = 0  # key dims shared by all heads, cached beside c
    mla_v_dim: int = 0
    # -- MLA's switches; every default is "as Kimi-Linear" --------------------
    # the query's own low-rank pair: q = W_qb · RMSNorm(W_qa x) with a latent
    # of this width (``q_lora_rank``). 0: one full-rank ``wq``
    mla_q_rank: int = 0
    # the ``mla_rope_dim`` shared key dims and the query's matching part carry
    # a rotary embedding (``rope_theta`` and the ``rope_*`` fields below); the
    # cached row then holds the ROTATED k_s. False: no rotary embedding
    # anywhere (Kimi-Linear's ``mla_use_nope``)
    mla_rotary: bool = False
    # the rotation pairs dims (2i, 2i + 1) instead of (i, i + d/2)
    rope_interleave: bool = False
    # YaRN (``rope_type: yarn``): frequencies whose wavelength passes
    # ``rope_original_max`` are divided by ``rope_factor``, those that turn
    # ``rope_beta_fast`` times in it are kept, a linear ramp between
    # (``ops/rope.yarn_frequencies``). 1: plain RoPE
    rope_factor: float = 1.0
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    # the softmax scale takes (0.1 · this · ln rope_factor + 1)² (``softmax_mscale``)
    rope_mscale_all_dim: float = 0.0
    # Llama-4's query scale by position: q · (1 + this · ln(1 + ⌊p /
    # rope_original_max⌋)), 1 below ``rope_original_max``. 0: none
    q_pos_scale_beta: float = 0.0
    # -- "full" beside "swa" (Laguna); every default is "as Olmo-Hybrid" ------
    # query heads of a "swa" layer (a "full" layer has ``n_heads``; both kinds
    # share ``n_kv_heads`` and ``head_dim``). 0: ``n_heads``
    swa_heads: int = 0
    # a "swa" layer's rotary embedding: plain rotate-half over the whole head
    # with this base. 0: none
    swa_rope_theta: float = 0.0
    # a "full" layer rotates the FIRST ``rope_partial`` share of a head's dims
    # (rotate-half inside them; the rest carry no position), with
    # ``rope_theta`` and the ``rope_*`` fields above (YaRN where
    # ``rope_factor`` > 1), cos and sin both times ``rope_attention_factor``
    rope_partial: float = 1.0
    rope_attention_factor: float = 1.0
    # attention's output times a sigmoid of a linear function of the layer's
    # normed input. ``True`` or "per_head": one gate a head (``wg [d, heads]``:
    # Laguna); "full": one a channel, as wide as the output (``wg [d, heads ·
    # head_dim]``: Solar-Open2's ``use_gqa_gate``). Read ``gate_form``
    attn_gate: bool | str = False
    # -- "sparse" beside "lightning" (MiniCPM-SALA); 0: the model has no such layer --
    # a pooled key is the mean of ``sparse_kernel`` consecutive keys, one
    # every ``sparse_stride``; a key block is ``sparse_block`` rows; a query
    # reads the first ``sparse_init_blocks`` blocks, the blocks of its last
    # ``sparse_window`` rows and, ``sparse_topk`` in all, those whose pooled
    # keys score highest. A query whose context is at most
    # ``sparse_dense_len`` rows reads all of it
    sparse_kernel: int = 0
    sparse_stride: int = 0
    sparse_block: int = 0
    sparse_init_blocks: int = 0
    sparse_window: int = 0
    sparse_topk: int = 0
    sparse_dense_len: int = 0
    # a "lightning" layer rotates q and k (rotate-half, the whole head). 0: no rotation
    lightning_rope_theta: float = 0.0
    # MiniCPM's µP scalings: the embedding times ``embed_scale``, every
    # sublayer's output times ``residual_scale`` before the residual adds it,
    # the final normed stream over ``logit_divisor`` before the head. 1: none
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    # the first ``n_dense_layers`` have a dense SwiGLU of ``dense_ffn_dim``;
    # the rest are MoE with ``ffn_dim`` wide experts
    n_dense_layers: int = 0
    dense_ffn_dim: int = 0
    n_shared_experts: int = 0  # SwiGLU of n_shared × ffn_dim, added whole
    # "softmax": ``moe_renormalize`` picks Mixtral's or OLMoE's rule.
    # "sigmoid": sigmoid scores, top-k of score + a selection bias, the
    # chosen scores (renormalised if ``moe_renormalize``) × ``moe_scale``
    moe_router: str = "softmax"
    moe_scale: float = 1.0
    # the chip's share of the experts: the stack holds experts
    # [expert_offset, expert_offset + experts_held) of ``n_experts``; the
    # router keeps all ``n_experts`` outputs. 0: every expert is held
    experts_held: int = 0
    expert_offset: int = 0
    # -- the K/V block's per-layer switches; every default is "as Llama" ------
    # width of one attention head where the model states it (SmallThinker:
    # 28 heads of 128 over a hidden size of 2560). 0: ``dim // n_heads``.
    # Read ``head_dim``; nothing outside this class derives it
    head_size: int = 0
    # sliding-window attention: a query at position i of a layer whose flag
    # in ``window_layers`` is 1 sees keys j with 0 <= i - j < ``window``; the
    # other layers see their whole context. ``window_layers`` has one 0/1
    # flag a layer, or is empty (no window layer: the block of every model
    # before this field). The cache of a window layer is a ring of
    # ``models/llama.ring_rows`` rows a lane, not ``max_seq``
    window: int = 0
    window_layers: tuple[int, ...] = ()
    # one 0/1 flag a layer: 1 rotates q and k (rotate-half, ``rope_theta``),
    # 0 gives the layer no positional embedding at all. Empty: every layer
    # rotates
    rope_layers: tuple[int, ...] = ()
    # the gate activation of the (expert) FFN: "silu" (SwiGLU) or "relu"
    # (ReGLU: relu(x Wg) * (x Wu))
    ffn_act: str = "silu"
    # the router reads the layer's INPUT (the residual stream as it enters
    # the layer, before any norm) instead of the normed post-attention stream
    early_router: bool = False

    def __post_init__(self):
        for name in ("window_layers", "rope_layers"):
            flags = getattr(self, name)
            if flags and (len(flags) != self.n_layers or set(flags) - {0, 1}):
                raise ValueError(f"{name}: one 0/1 flag a layer ({self.n_layers}), got {flags}")
        if any(self.window_layers) and self.window <= 0:
            raise ValueError("window_layers names window layers and window is 0")
        if self.ffn_act not in ("silu", "relu"):
            raise ValueError(f"ffn_act {self.ffn_act!r}: silu or relu")
        if self.attn_gate not in (False, True, "per_head", "full"):
            raise ValueError(f'attn_gate {self.attn_gate!r}: False, True ("per_head") or "full"')
        if (self.rope_factor != 1.0 or self.q_pos_scale_beta) and self.rope_original_max <= 0:
            raise ValueError("rope_factor and q_pos_scale_beta are read against rope_original_max, which is 0")
        if self.mla_rotary and (self.mla_rope_dim % 2 or not self.rope_theta):
            raise ValueError("mla_rotary rotates an even mla_rope_dim by a rope_theta that is not 0")
        if WINDOW_KIND in self.layer_kinds and (self.window <= 0 or "full" not in self.layer_kinds):
            raise ValueError('"swa" layers see a window > 0 and stand beside "full" layers (the arena\'s length is theirs)')
        if self.rope_partial != 1.0 and self.rotary_dim % 2:
            raise ValueError("rope_partial rotates an even number of a head's dims")
        if "sparse" in self.layer_kinds:
            k, s, b = self.sparse_kernel, self.sparse_stride, self.sparse_block
            if min(k, s, b, self.sparse_topk) <= 0 or k % s or b % s or self.sparse_window % b or self.sparse_dense_len % b:
                raise ValueError("sparse layers: kernel and block are whole strides, window and dense_len whole blocks, topk > 0")
            if self.sparse_topk < self.sparse_forced_blocks:
                raise ValueError("sparse_topk is under the blocks every query is made to read (init + window)")
            if self.sparse_dense_len < self.sparse_topk * b:
                raise ValueError("sparse_dense_len is under sparse_topk blocks: a sparse query would have fewer blocks than it selects")

    @property
    def head_dim(self) -> int:
        """Width of an attention head: the model's own (``head_size``) or
        ``dim // n_heads``. ``n_heads * head_dim`` need not be ``dim``."""
        return self.head_size or self.dim // self.n_heads

    @property
    def n_window(self) -> int:
        """Layers whose attention is windowed (their cache is a ring): the
        K/V block's flagged layers, or the hybrid block's "swa" layers."""
        return sum(self.window_layers) or self.layer_kinds.count(WINDOW_KIND)

    @property
    def window_heads(self) -> int:
        """Query heads of a window layer."""
        return self.swa_heads or self.n_heads

    def heads_of(self, kind: str) -> int:
        """Query heads of a layer of ``kind``."""
        return self.window_heads if kind == WINDOW_KIND else self.n_heads

    @property
    def gate_form(self) -> str:
        """The output gate of the "full" and "swa" layers: "none", "per_head"
        or "full" (``attn_gate``)."""
        return {False: "none", True: "per_head"}.get(self.attn_gate, self.attn_gate)

    def gate_width(self, heads: int) -> int:
        """Columns of such a layer's ``wg`` (0: no gate)."""
        return {"none": 0, "per_head": heads, "full": heads * self.head_dim}[self.gate_form]

    @property
    def rotary_dim(self) -> int:
        """Dims of a "full" layer's head that are rotated (the first ones)."""
        return int(self.head_dim * self.rope_partial)

    @property
    def n_global(self) -> int:
        return self.n_layers - self.n_window

    @property
    def n_sparse(self) -> int:
        return self.layer_kinds.count("sparse")

    @property
    def sparse_forced_blocks(self) -> int:
        """Blocks a query past ``sparse_dense_len`` reads whatever they score."""
        return self.sparse_init_blocks + self.sparse_window // self.sparse_block

    @property
    def is_hybrid(self) -> bool:
        return bool(self.layer_kinds)

    @property
    def n_held(self) -> int:
        """Experts in the stack (all of them unless the chip holds a share)."""
        return self.experts_held or self.n_experts

    @property
    def n_kda(self) -> int:
        return sum(k == "kda" for k in self.layer_kinds)

    @property
    def n_mla(self) -> int:
        return sum(k == "mla" for k in self.layer_kinds)

    @property
    def linear_kind(self) -> str | None:
        """The model's one kind of linear mixer (``LINEAR_KINDS``), or None."""
        return next((k for k in LINEAR_KINDS if k in self.layer_kinds), None)

    @property
    def positional_kind(self) -> str | None:
        return next((k for k in POSITIONAL_KINDS if k in self.layer_kinds), None)

    @property
    def n_linear(self) -> int:
        return sum(k in LINEAR_KINDS for k in self.layer_kinds)

    @property
    def n_positional(self) -> int:
        """Layers whose rows are kept from position 0 (not the ring's)."""
        return sum(k in POSITIONAL_KINDS for k in self.layer_kinds)

    @property
    def softmax_mscale(self) -> float:
        """YaRN's correction of the softmax scale, squared where it is used
        (``scale · m²``): ``m = 0.1 · mscale_all_dim · ln(factor) + 1``; 1
        where the config scales no rotary embedding."""
        if self.rope_factor <= 1.0 or not self.rope_mscale_all_dim:
            return 1.0
        return 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0

    @property
    def delta_v_dim(self) -> int:
        return self.kda_v_dim or self.kda_head_dim

    def _hybrid_counts(self) -> dict:
        """Parameters of the hybrid pytree by part (models/hybrid.init_params)."""
        d, h, hk = self.dim, self.kda_heads, self.kda_head_dim
        kda = (
            3 * d * h * hk + h * hk * d  # q, k, v, o
            + 2 * (d * hk + hk * h * hk)  # decay and output-gate low-rank pairs
            + d * h  # beta
            + 3 * self.kda_conv * h * hk  # conv filters
            + h + h * hk + hk  # A_log, dt_bias, head norm
        )
        qk = self.mla_nope_dim + self.mla_rope_dim
        # the query: one matrix, or the low-rank pair and its norm vector
        wq = d * self.n_heads * qk
        if self.mla_q_rank:
            wq = d * self.mla_q_rank + self.mla_q_rank + self.mla_q_rank * self.n_heads * qk
        mla = (
            wq
            + d * (self.mla_kv_rank + self.mla_rope_dim)
            + self.mla_kv_rank * self.n_heads * (self.mla_nope_dim + self.mla_v_dim)
            + self.n_heads * self.mla_v_dim * d
            + self.mla_kv_rank
        )
        expert = 3 * d * self.ffn_dim
        # the router, its selection bias (the sigmoid rule's) and the shared expert
        bias = self.n_experts if self.moe_router == "sigmoid" else 0
        moe = d * self.n_experts + bias + self.n_shared_experts * expert
        ck, cv = h * hk, h * self.delta_v_dim
        gdn = (
            d * (2 * ck + cv) + cv * d  # q, k, v, o
            + d * cv  # output gate
            + 2 * d * h  # decay and beta
            + self.kda_conv * (2 * ck + cv)  # conv filters
            + 2 * h + self.delta_v_dim  # A_log, dt_bias, head norm
        )
        hd = self.head_dim

        def attention(heads: int) -> int:  # q, o, k, v, the gate (a head or full width), the two norms
            n = 2 * d * heads * hd + 2 * d * self.n_kv_heads * hd + d * self.gate_width(heads)
            return n + ((heads + self.n_kv_heads) * hd if self.qk_norm else 0)

        # q, o, the full-width gate, k, v, and a norm vector a head for q and k
        sparse = 3 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + 2 * hd
        # q, k, v, the gate, o; the q and k norms a head, the output norm, the decays
        lightning = 5 * d * h * hk + 2 * hk + h * hk + h
        return {"kda": kda, "mla": mla, "gdn": gdn, "full": attention(self.n_heads),
                WINDOW_KIND: attention(self.window_heads), "expert": expert, "moe_fixed": moe,
                "dense": 3 * d * self.dense_ffn_dim, "sparse": sparse, "lightning": lightning}

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def param_count(self) -> int:
        """Exact parameter count of models/llama.init_params' pytree."""
        embed = self.vocab_size * self.dim
        if self.is_hybrid:
            c = self._hybrid_counts()
            n_moe = self.n_layers - self.n_dense_layers
            return (
                2 * embed + self.dim + 2 * self.n_layers * self.dim
                + sum(c[k] for k in self.layer_kinds)
                + self.n_dense_layers * c["dense"]
                + (n_moe * (c["moe_fixed"] + self.n_held * c["expert"]) if n_moe else 0)
            )
        per_layer_attn = 2 * self.dim * self.head_dim * (self.n_heads + self.n_kv_heads)
        ffn = 3 * self.dim * self.ffn_dim
        if self.is_moe:
            ffn = self.n_held * ffn + self.dim * self.n_experts
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
        if self.is_hybrid:
            # a token's k routed experts, wherever they live (the model's need)
            c = self._hybrid_counts()
            n_moe = self.n_layers - self.n_dense_layers
            return self.param_count() + n_moe * (self.experts_per_token - self.n_held) * c["expert"]
        full_ffn = 3 * self.dim * self.ffn_dim
        # a token's k routed experts, wherever they live (the model's need)
        unused = (self.n_held - self.experts_per_token) * full_ffn
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
        if self.is_hybrid:
            # a KDA layer reads and rewrites its state whatever the context
            # (4 passes over H·dk·dv: decay, k·S, rank-1 update, q·S); an MLA
            # layer scores 192 dims and combines 128 per head and slot
            delta = 8.0 * self.kda_heads * self.kda_head_dim * self.delta_v_dim
            if self.linear_kind == "lightning":  # kᵀv into the state and q·S out of it: no erase term
                delta = 4.0 * self.kda_heads * self.kda_head_dim * self.delta_v_dim
            mla = 2.0 * self.n_heads * context_len * (
                self.mla_nope_dim + self.mla_rope_dim + self.mla_v_dim
            )
            full = 4.0 * self.n_heads * self.head_dim * context_len
            # past ``sparse_dense_len`` a sparse layer scores every pooled key
            # and attends to ``sparse_topk`` blocks of rows
            sparse = full
            if self.n_sparse and context_len > self.sparse_dense_len:
                read = min(context_len, self.sparse_topk * self.sparse_block)
                sparse = self.n_heads * self.head_dim * (4.0 * read + 2.0 * context_len / self.sparse_stride)
            positional = {"mla": mla, "full": full, "sparse": sparse}.get(self.positional_kind, 0.0)
            # a window layer sees at most its window of the context
            swa = 4.0 * self.window_heads * self.head_dim * min(context_len, self.window)
            return matmul + self.n_linear * delta + self.n_positional * positional + self.n_window * swa
        # attention scores + value combine: q·K^T and p·V, each
        # 2 * heads * head_dim * context MACs → 4 FLOPs per context slot
        # (a window layer sees at most its window of them)
        attn = 4.0 * self.n_heads * self.head_dim
        seen = self.n_global * context_len + self.n_window * min(context_len, self.window)
        return matmul + attn * seen


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



def kimi_linear_kinds(n_layers: int, period: int = 4) -> tuple[str, ...]:
    """Kimi-Linear's published order (1-indexed ``full_attn_layers`` 4, 8, …
    and the last layer): every ``period``-th layer and the last are MLA, the
    rest KDA — so 27 layers end …24 MLA, 25 KDA, 26 KDA, 27 MLA."""
    return tuple(
        "mla" if (i % period == 0 or i == n_layers) else "kda" for i in range(1, n_layers + 1)
    )


# Kimi-Linear-48B-A3B (moonshotai/Kimi-Linear-48B-A3B-Instruct config.json:
# 27 layers, hidden 2304, 20 KDA layers of 32 heads × 128 with a conv of 4
# beside 7 NoPE MLA layers (latent 512 + 64 shared key dims, q/k 192, v 128),
# a dense first layer of 9216, then 256 experts of 1024 top-8 behind a
# sigmoid router with a selection bias, renormalised × 2.446, one shared
# expert; vocabulary 163,840, untied). All 256 experts: 49.1 B parameters —
# a chip serves its share (``experts_held``; benchmark/configs).
KIMI_LINEAR_48B = register(
    ModelConfig(
        name="kimi-linear-48b",
        vocab_size=163_840,
        dim=2304,
        n_layers=27,
        n_heads=32,
        n_kv_heads=32,
        ffn_dim=1024,
        max_seq_len=1_048_576,
        rope_theta=10_000.0,  # published and unused: mla_use_nope
        norm_eps=1e-5,
        n_experts=256,
        experts_per_token=8,
        moe_renormalize=True,
        layer_kinds=kimi_linear_kinds(27),
        kda_heads=32,
        kda_head_dim=128,
        kda_conv=4,
        mla_kv_rank=512,
        mla_nope_dim=128,
        mla_rope_dim=64,
        mla_v_dim=128,
        n_dense_layers=1,
        dense_ffn_dim=9216,
        n_shared_experts=1,
        moe_router="sigmoid",
        moe_scale=2.446,
    )
)

# The hybrid block at CI shapes: 9 layers in the published pattern (K K K M
# K K K M M: the short last period), a dense first layer, 8 experts top-2
# with a shared one.
TINY_KIMI_LINEAR = register(
    ModelConfig(
        name="tiny-kimi-linear",
        vocab_size=512,
        dim=64,
        n_layers=9,
        n_heads=4,
        n_kv_heads=4,
        ffn_dim=32,
        max_seq_len=256,
        norm_eps=1e-5,
        n_experts=8,
        experts_per_token=2,
        moe_renormalize=True,
        layer_kinds=kimi_linear_kinds(9),
        kda_heads=4,
        kda_head_dim=16,
        kda_conv=4,
        mla_kv_rank=32,
        mla_nope_dim=16,
        mla_rope_dim=8,
        mla_v_dim=16,
        n_dense_layers=1,
        dense_ffn_dim=128,
        n_shared_experts=1,
        moe_router="sigmoid",
        moe_scale=2.446,
    )
)

# Mistral-Small-4-119B-2603 (mistralai/Mistral-Small-4-119B-2603 config.json,
# ``model_type: mistral4``, the text decoder: 36 layers, hidden 4096, every
# layer MLA with 32 heads (latent 256 + 64 shared key dims, q/k 64 + 64, v
# 128) and a low-rank query (1024), the shared key dims and the query's
# matching half rotated in adjacent pairs with YaRN's frequencies (factor 128
# over an original 8,192, theta 1e4), the softmax scale x (0.1 ln 128 + 1)^2,
# the query x (1 + 0.1 ln(1 + floor(p / 8192))); no dense layer
# (``first_k_dense_replace`` 0: the published ``intermediate_size`` 12288 is
# used by none), 128 experts of 2048 top-4 behind a softmax router
# renormalised over the chosen four, one shared expert; vocabulary 131,072,
# untied). All 128 experts: 119.0 B parameters — a chip serves its share
# (``experts_held``; benchmark/configs).
MISTRAL_SMALL_4_119B = register(
    ModelConfig(
        name="mistral-small-4-119b",
        vocab_size=131_072,
        dim=4096,
        n_layers=36,
        n_heads=32,
        n_kv_heads=32,
        ffn_dim=2048,
        max_seq_len=1_048_576,
        rope_theta=10_000.0,
        norm_eps=1e-6,
        n_experts=128,
        experts_per_token=4,
        moe_renormalize=True,
        layer_kinds=("mla",) * 36,
        mla_kv_rank=256,
        mla_nope_dim=64,
        mla_rope_dim=64,
        mla_v_dim=128,
        mla_q_rank=1024,
        mla_rotary=True,
        rope_interleave=True,
        rope_factor=128.0,
        rope_original_max=8192,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_mscale_all_dim=1.0,
        q_pos_scale_beta=0.1,
        n_shared_experts=1,
        moe_router="softmax",
    )
)

# The same block at CI shapes: 3 MLA layers and nothing else, a query latent
# narrower than the hidden size, 16 rotated dims whose YaRN ramp has a pair
# strictly inside it (original context 32, factor 8: a 256-token test crosses
# the boundary eight times), 8 experts top-2 with a shared one.
TINY_MISTRAL4 = register(
    ModelConfig(
        name="tiny-mistral4",
        vocab_size=512,
        dim=64,
        n_layers=3,
        n_heads=4,
        n_kv_heads=4,
        ffn_dim=32,
        max_seq_len=256,
        rope_theta=10_000.0,
        norm_eps=1e-6,
        n_experts=8,
        experts_per_token=2,
        moe_renormalize=True,
        layer_kinds=("mla",) * 3,
        mla_kv_rank=32,
        mla_nope_dim=16,
        mla_rope_dim=16,
        mla_v_dim=16,
        mla_q_rank=24,
        mla_rotary=True,
        rope_interleave=True,
        rope_factor=8.0,
        rope_original_max=32,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_mscale_all_dim=1.0,
        q_pos_scale_beta=0.1,
        n_shared_experts=1,
        moe_router="softmax",
    )
)

def laguna_kinds(n_layers: int, period: int = 4) -> tuple[str, ...]:
    """Laguna's published ``layer_types``: every ``period``-th layer from
    layer 0 attends to its whole context, the rest to a sliding window."""
    return tuple("full" if i % period == 0 else WINDOW_KIND for i in range(n_layers))


# Laguna-XS.2 (poolside/Laguna-XS.2 config.json, ``model_type: laguna``: 40
# layers, hidden 2048, heads of 128 over 8 KV heads in every layer, (full,
# sliding, sliding, sliding) x 10 with a window of 512; a full layer has 48
# query heads and rotates the first 64 dims of a head with YaRN's frequencies
# (theta 5e5, factor 64 over an original 4,096, beta_fast 64, cos and sin x
# 1.4158883), a sliding layer 64 query heads and plain RoPE (theta 1e4) over
# the whole head; a sigmoid gate a head on the attention's output; a dense
# SwiGLU of 8192 in layer 0, then 256 experts of 512, top-8 of a softmax
# renormalised over the chosen eight x 2.5, one shared expert of 512;
# vocabulary 100,352, untied). All 256 experts: 33.44 B parameters — a chip
# serves its share (``experts_held``; benchmark/configs).
LAGUNA_XS2 = register(
    ModelConfig(
        name="laguna-xs.2",
        vocab_size=100_352,
        dim=2048,
        n_layers=40,
        n_heads=48,
        n_kv_heads=8,
        head_size=128,
        ffn_dim=512,
        max_seq_len=262_144,
        rope_theta=500_000.0,
        norm_eps=1e-6,
        n_experts=256,
        experts_per_token=8,
        moe_renormalize=True,
        layer_kinds=laguna_kinds(40),
        window=512,
        swa_heads=64,
        swa_rope_theta=10_000.0,
        rope_partial=0.5,
        rope_factor=64.0,
        rope_original_max=4096,
        rope_beta_fast=64.0,
        rope_beta_slow=1.0,
        rope_attention_factor=1.4158883083359672,
        attn_gate=True,
        n_dense_layers=1,
        dense_ffn_dim=8192,
        n_shared_experts=1,
        moe_router="softmax",
        moe_scale=2.5,
    )
)

# The same block at CI shapes with the published RATIOS: two periods (F S S S
# F S S S), 6 query heads in a full layer and 8 in a sliding one over 2 KV
# heads of 16, a window of 16, an original context of 32 stretched 8 times
# (a 256-token test passes it; with theta 100 over the 8 rotated dims the
# ramp has a pair strictly inside it), half of a head rotated, a dense first
# layer, 8 experts top-2 x 2.5 beside a shared one.
TINY_LAGUNA = register(
    ModelConfig(
        name="tiny-laguna",
        vocab_size=512,
        dim=64,
        n_layers=8,
        n_heads=6,
        n_kv_heads=2,
        head_size=16,
        ffn_dim=32,
        max_seq_len=256,
        rope_theta=100.0,
        norm_eps=1e-6,
        n_experts=8,
        experts_per_token=2,
        moe_renormalize=True,
        layer_kinds=laguna_kinds(8),
        window=16,
        swa_heads=8,
        swa_rope_theta=10_000.0,
        rope_partial=0.5,
        rope_factor=8.0,
        rope_original_max=32,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_attention_factor=1.2079441541679836,
        attn_gate=True,
        n_dense_layers=1,
        dense_ffn_dim=128,
        n_shared_experts=1,
        moe_router="softmax",
        moe_scale=2.5,
    )
)

# Olmo-Hybrid-7B (allenai/Olmo-Hybrid-7B config.json: 32 layers, hidden
# 3840, (linear, linear, linear, full) x 8; the linear layers a gated delta
# rule with one decay a head, 30 heads of 96-wide keys and 192-wide values,
# conv of 4, negative eigenvalues allowed; the full layers 30/30 heads of 128
# with QK-norm and no rotary embedding (``rope_theta`` null); a dense SwiGLU of
# 11008 in every layer; the OLMo-2 family's norm placement; vocabulary
# 100,352, untied). 7.42 B parameters.
def olmo_hybrid_kinds(n_layers: int, period: int = 4) -> tuple[str, ...]:
    return tuple("full" if i % period == 0 else "gdn" for i in range(1, n_layers + 1))


OLMO_HYBRID_7B = register(
    ModelConfig(
        name="olmo-hybrid-7b",
        vocab_size=100_352,
        dim=3840,
        n_layers=32,
        n_heads=30,
        n_kv_heads=30,
        ffn_dim=11_008,
        max_seq_len=65_536,
        rope_theta=0.0,  # published null: no rotary embedding
        norm_eps=1e-6,
        qk_norm=True,
        layer_kinds=olmo_hybrid_kinds(32),
        kda_heads=30,
        kda_head_dim=96,
        kda_v_dim=192,
        kda_conv=4,
        delta_neg_eigval=True,
        post_norm=True,
        n_dense_layers=32,
        dense_ffn_dim=11_008,
    )
)

# The same block at CI shapes: two periods (L L L F L L L F), head counts
# that are no multiple of 8 and keys narrower than values.
TINY_OLMO_HYBRID = register(
    ModelConfig(
        name="tiny-olmo-hybrid",
        vocab_size=512,
        dim=96,
        n_layers=8,
        n_heads=6,
        n_kv_heads=6,
        ffn_dim=128,
        max_seq_len=256,
        rope_theta=0.0,
        norm_eps=1e-6,
        qk_norm=True,
        layer_kinds=olmo_hybrid_kinds(8),
        kda_heads=6,
        kda_head_dim=12,
        kda_v_dim=24,
        kda_conv=4,
        delta_neg_eigval=True,
        post_norm=True,
        n_dense_layers=8,
        dense_ffn_dim=128,
    )
)

# MiniCPM-SALA (openbmb/MiniCPM-SALA config.json, ``model_type: minicpm_sala``:
# 32 layers, hidden 4096, dense SwiGLU of 16384; ``mixer_types`` names
# ``minicpm4`` at layers 0, 9, 16, 17, 22, 29, 30, 31 (InfLLM v2's block-sparse
# attention: 32 query / 2 K/V heads of 128, no rotary embedding, a norm a
# head, an output gate) and ``lightning-attn`` at the other 24 (Lightning
# Attention-2: 32 heads of 128, rotate-half RoPE at theta 1e4, a constant
# decay a head, an output norm and gate); MiniCPM's µP scalings (``scale_emb``
# 12, ``scale_depth`` 1.4 over √32, ``dim_model_base`` 256); vocabulary
# 73,448, untied, context 524,288). The sparse sizes are the ``sparse_config``
# of openbmb/MiniCPM4-8B, which ``minicpm4`` names. 9.48 B parameters.
SALA_SPARSE_LAYERS = (0, 9, 16, 17, 22, 29, 30, 31)


def sala_kinds(n_layers: int = 32, sparse_at: tuple[int, ...] = SALA_SPARSE_LAYERS) -> tuple[str, ...]:
    return tuple("sparse" if i in sparse_at else "lightning" for i in range(n_layers))


MINICPM_SALA = register(
    ModelConfig(
        name="minicpm-sala",
        vocab_size=73_448,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=2,
        ffn_dim=16_384,
        max_seq_len=524_288,
        rope_theta=0.0,  # ``attn_use_rope: false``: the sparse layers carry no position
        norm_eps=1e-6,
        qk_norm=True,
        head_size=128,
        layer_kinds=sala_kinds(),
        kda_heads=32,
        kda_head_dim=128,
        lightning_rope_theta=10_000.0,
        sparse_kernel=32,
        sparse_stride=16,
        sparse_block=64,
        sparse_init_blocks=1,
        sparse_window=2048,
        sparse_topk=64,
        sparse_dense_len=8192,
        embed_scale=12.0,
        residual_scale=1.4 / 32**0.5,
        logit_divisor=4096 / 256,
        n_dense_layers=32,
        dense_ffn_dim=16_384,
    )
)

# The same block at CI shapes: both kinds at every junction (S L L S S L L S),
# every sparse size scaled down with the widths: pooled keys of 8 rows every
# 4, blocks of 16, one initial block and a window of two forced, 6 of at
# least 7 blocks chosen past 96 rows.
TINY_MINICPM_SALA = register(
    ModelConfig(
        name="tiny-minicpm-sala",
        vocab_size=512,
        dim=64,
        n_layers=8,
        n_heads=8,
        n_kv_heads=2,
        ffn_dim=128,
        max_seq_len=512,
        rope_theta=0.0,
        norm_eps=1e-6,
        qk_norm=True,
        head_size=16,
        layer_kinds=sala_kinds(8, (0, 3, 4, 7)),
        kda_heads=4,
        kda_head_dim=16,
        lightning_rope_theta=10_000.0,
        sparse_kernel=8,
        sparse_stride=4,
        sparse_block=16,
        sparse_init_blocks=1,
        sparse_window=32,
        sparse_topk=6,
        sparse_dense_len=96,
        embed_scale=12.0,
        residual_scale=1.4 / 8**0.5,
        logit_divisor=2.0,
        n_dense_layers=8,
        dense_ffn_dim=128,
    )
)

def solar_open2_kinds(n_layers: int, period: int = 4) -> tuple[str, ...]:
    """Solar-Open2's published ``gqa_layers`` (0-indexed: 0, 4, 8, …): every
    ``period``-th layer from layer 0 is softmax GQA, the rest KDA — G K K K."""
    return tuple("full" if i % period == 0 else "kda" for i in range(n_layers))


# Solar-Open2-250B (upstage/Solar-Open2-250B config.json, ``model_type:
# solar_open2``: 48 layers, hidden 4096; ``gqa_layers`` 0, 4, …, 44 are softmax
# attention with 64 query / 8 K/V heads of 128, no rotary embedding
# (``use_rope: false``), no QK-norm, and a sigmoid gate as wide as the output
# (``use_gqa_gate``); the other 36 are KDA, 64 heads of 128 with a conv of 4,
# low-rank decay and gate pairs (``kda_use_full_proj: false``) and β = 2 ·
# sigmoid (``kda_allow_neg_eigval``); every layer a MoE (``first_k_dense_
# replace`` 0: the published ``intermediate_size`` 10240 is used by none) of
# 320 experts of 1280, top-8 behind a sigmoid router with a selection bias,
# renormalised x 1, one shared expert; vocabulary 196,608, untied). All 320
# experts: 250.29 B parameters, 14.7 B active a token — a chip serves its share
# (``experts_held``; benchmark/configs).
SOLAR_OPEN2 = register(
    ModelConfig(
        name="solar-open2",
        vocab_size=196_608,
        dim=4096,
        n_layers=48,
        n_heads=64,
        n_kv_heads=8,
        head_size=128,
        ffn_dim=1280,
        max_seq_len=1_048_576,
        rope_theta=0.0,  # ``use_rope: false`` (the published 10000 is unused)
        norm_eps=1e-5,
        n_experts=320,
        experts_per_token=8,
        moe_renormalize=True,
        layer_kinds=solar_open2_kinds(48),
        kda_heads=64,
        kda_head_dim=128,
        kda_conv=4,
        delta_neg_eigval=True,
        attn_gate="full",
        n_shared_experts=1,
        moe_router="sigmoid",
        moe_scale=1.0,
    )
)

# The same block at CI shapes: two periods and a layer (G K K K G K K K G:
# each kind after the other, both ways), 8 query heads over 2 K/V heads of 16
# (a group of 4), 4 KDA heads of 16, 8 experts top-2 beside a shared one.
TINY_SOLAR_OPEN2 = register(
    ModelConfig(
        name="tiny-solar-open2",
        vocab_size=512,
        dim=64,
        n_layers=9,
        n_heads=8,
        n_kv_heads=2,
        head_size=16,
        ffn_dim=32,
        max_seq_len=256,
        rope_theta=0.0,
        norm_eps=1e-5,
        n_experts=8,
        experts_per_token=2,
        moe_renormalize=True,
        layer_kinds=solar_open2_kinds(9),
        kda_heads=4,
        kda_head_dim=16,
        kda_conv=4,
        delta_neg_eigval=True,
        attn_gate="full",
        n_shared_experts=1,
        moe_router="sigmoid",
        moe_scale=1.0,
    )
)

# SmallThinker-21BA3B-Instruct (PowerInfer/SmallThinker-21BA3B-Instruct
# config.json: 52 layers, hidden 2560, 28 query / 4 KV heads of 128 (28 x 128
# = 3584: the head is its own key), layouts (0, 1, 1, 1) x 13: layers 0, 4, 8,
# ... are global attention with NO positional embedding, the other 39 a
# 4096-token sliding window with rotate-half RoPE (theta 1.5e6); 64 ReGLU
# experts of 768, top-6, softmax over the chosen six, the router reading the
# layer's input; vocabulary 151,936, untied, context 16,384). All 64 experts:
# 21.5 B parameters — a chip serves its share (``experts_held``).
def smallthinker_layout(n_layers: int, period: int = 4) -> tuple[int, ...]:
    """The published ``sliding_window_layout`` and ``rope_layout`` (they are
    the same list): every ``period``-th layer from layer 0 is global and
    unrotated, the rest windowed and rotated."""
    return tuple(int(i % period != 0) for i in range(n_layers))


SMALLTHINKER_21B = register(
    ModelConfig(
        name="smallthinker-21b",
        vocab_size=151_936,
        dim=2560,
        n_layers=52,
        n_heads=28,
        n_kv_heads=4,
        head_size=128,
        ffn_dim=768,
        max_seq_len=16_384,
        rope_theta=1_500_000.0,
        norm_eps=1e-6,
        n_experts=64,
        experts_per_token=6,
        moe_renormalize=True,
        window=4096,
        window_layers=smallthinker_layout(52),
        rope_layers=smallthinker_layout(52),
        ffn_act="relu",
        early_router=True,
    )
)

# The same block at CI shapes: two periods (G W W W G W W W), a window of 16,
# a head of 16 over a hidden size of 48 (3 x 16 = 48 would hide the key: 6
# heads of 16 = 96), a GQA group of 3, 8 experts top-2.
TINY_SMALLTHINKER = register(
    ModelConfig(
        name="tiny-smallthinker",
        vocab_size=512,
        dim=48,
        n_layers=8,
        n_heads=6,
        n_kv_heads=2,
        head_size=16,
        ffn_dim=32,
        max_seq_len=256,
        rope_theta=10_000.0,
        norm_eps=1e-6,
        n_experts=8,
        experts_per_token=2,
        moe_renormalize=True,
        window=16,
        window_layers=smallthinker_layout(8),
        rope_layers=smallthinker_layout(8),
        ffn_act="relu",
        early_router=True,
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
