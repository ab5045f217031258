"""Expert parallelism for MoE layers (BASELINE.json config #5).

Experts shard over the ``ep`` mesh axis: each device owns ``E/ep`` experts'
weights (the HBM win — Mixtral-8x7B's experts dominate its footprint). Two
compute strategies:

- ``make_routed_moe`` (the serving default for ep > 1): top-k TOKEN
  DISPATCH — each device routes with the replicated router over the full
  expert set, gathers only the tokens routed to ITS local experts into
  fixed-capacity buffers (models/llama._moe_mlp_routed), and a psum over
  ``ep`` combines the partial outputs. Per-token MLP FLOPs ∝ k, not E.
  Dispatch is a local gather rather than an all-to-all because serve-time
  activations are replicated over ep (no dp×ep token sharding to exchange);
  the psum is the only ep collective, and it rides ICI.
- ``moe_expert_parallel`` (dense fallback): every device computes its local
  experts for EVERY token and masks at combine — branch-free but ~E/k×
  the routed FLOPs.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..models.configs import ModelConfig
from ..models.llama import _moe_mlp_routed, moe_gates


def _moe_local(x, router, w_gate, w_up, w_down, *, axis_name: str, cfg: ModelConfig):
    """x [B,T,D] replicated over ep; expert weights sharded on their leading
    expert axis: w_gate/w_up [E/ep, D, F], w_down [E/ep, F, D]."""
    ax = lax.axis_index(axis_name)
    e_local = w_gate.shape[0]
    # replicated routing over the FULL expert set
    weights, chosen = moe_gates(x @ router, cfg, x.dtype)  # [B,T,K]
    onehot = jax.nn.one_hot(chosen, cfg.n_experts, dtype=x.dtype)  # [B,T,K,E]
    combine = jnp.einsum("btk,btke->bte", weights, onehot)  # [B,T,E]
    # slice my experts' combine weights
    my_combine = lax.dynamic_slice_in_dim(combine, ax * e_local, e_local, axis=2)
    gate = jax.nn.silu(jnp.einsum("btd,edf->btef", x, w_gate))
    up = jnp.einsum("btd,edf->btef", x, w_up)
    expert_out = jnp.einsum("btef,efd->bted", gate * up, w_down)
    partial_out = jnp.einsum("bted,bte->btd", expert_out, my_combine)
    return lax.psum(partial_out, axis_name)


def make_routed_moe(
    mesh: Mesh,
    cfg: ModelConfig,
    capacity_factor: float = 2.0,
    axis: str = "ep",
):
    """Engine-facing routed MoE under a mesh: returns ``impl(h, lp) → out``
    for models/llama.forward's ``moe_impl`` hook (called inside the layer
    scan with the current layer's dequantized weights).

    Partial-manual shard_map: only ``ep`` is manual — tp-sharded expert
    widths stay in GSPMD's hands, so their Megatron collectives compose
    with the manual ep psum.
    """
    ep = int(mesh.shape[axis])
    if cfg.n_experts % ep:
        raise ValueError(f"ep={ep} must divide n_experts={cfg.n_experts}")
    e_loc = cfg.n_experts // ep

    def local(x, router, w_gate, w_up, w_down):
        ax = lax.axis_index(axis)
        out = _moe_mlp_routed(
            x,
            {"router": router, "w_gate": w_gate, "w_up": w_up, "w_down": w_down},
            cfg,
            capacity_factor=capacity_factor,
            base=ax * e_loc,
        )
        return lax.psum(out, axis)

    expert_spec = P(axis, None, None)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(None, None), expert_spec, expert_spec, expert_spec),
        out_specs=P(),
        axis_names={axis},
    )

    def impl(h, lp):
        return fn(h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"])

    return impl


def moe_expert_parallel(
    x: jnp.ndarray,
    layer_params: dict,
    cfg: ModelConfig,
    mesh: Mesh,
    axis: str = "ep",
) -> jnp.ndarray:
    """Layer params carry per-layer MoE weights (no layer axis):
    router [D,E], w_gate/w_up [E,D,F], w_down [E,F,D]."""
    ep = mesh.shape[axis]
    if cfg.n_experts % ep != 0:
        raise ValueError(f"ep={ep} must divide n_experts={cfg.n_experts}")
    fn = partial(_moe_local, axis_name=axis, cfg=cfg)
    expert_spec = P(axis, None, None)
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(), P(None, None), expert_spec, expert_spec, expert_spec),
        out_specs=P(),
    )(x, layer_params["router"], layer_params["w_gate"], layer_params["w_up"], layer_params["w_down"])
