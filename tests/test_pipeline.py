"""Pipeline parallelism: the stacked-layer scan staged over a pp mesh axis
with collective_permute between stages (parallel/pipeline.py), driven by
the real train step. Loss must match the unstaged run — pipelining
reorders compute across devices, not math. (VERDICT round-1 item 7.)

Runs on the virtual 8-device CPU mesh (tests/conftest.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.models.configs import get_config
from agentainer_tpu.parallel.mesh import make_mesh
from agentainer_tpu.train import make_train_step

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs the virtual multi-device mesh"
)

CFG = get_config("tiny")  # n_layers=2 → pp=2 stages of 1 layer each
TOKENS = np.random.default_rng(11).integers(0, CFG.vocab_size, (4, 17)).astype(np.int32)


def _one_step(n_devices: int, pp: int, **kw):
    mesh = make_mesh(n_devices, pp=pp)
    init_fn, step_fn, shard_batch = make_train_step(CFG, mesh, **kw)
    state = init_fn(jax.random.PRNGKey(0))
    state, loss = step_fn(state, shard_batch(jnp.asarray(TOKENS)))
    return float(loss), state


def test_pp2_loss_matches_pp1():
    ref, _ = _one_step(1, pp=1)
    pipe, _ = _one_step(2, pp=2)
    assert np.isfinite(pipe)
    np.testing.assert_allclose(pipe, ref, rtol=2e-5)


def test_pp_stages_hold_layer_shards():
    """Each stage's HBM holds L/pp layers — the weights are actually
    sharded on the leading layer axis."""
    mesh = make_mesh(2, pp=2)
    init_fn, _, _ = make_train_step(CFG, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    wq = state.params["layers"]["wq"]
    assert len(wq.sharding.device_set) == 2
    assert wq.sharding.shard_shape(wq.shape)[0] == CFG.n_layers // 2


def test_pp_more_microbatches_and_learning():
    """M=4 microbatches over pp=2 stages: loss still matches, and two
    steps decrease it (gradients flow through ppermute's transpose)."""
    ref, _ = _one_step(1, pp=1)
    mesh = make_mesh(2, pp=2)
    init_fn, step_fn, shard_batch = make_train_step(CFG, mesh, n_microbatch=4)
    state = init_fn(jax.random.PRNGKey(0))
    toks = shard_batch(jnp.asarray(TOKENS))
    state, l1 = step_fn(state, toks)
    np.testing.assert_allclose(float(l1), ref, rtol=2e-5)
    state, l2 = step_fn(state, toks)
    assert float(l2) < float(l1)


def test_pp_composes_with_dp_mesh_axis():
    """dp=2 × pp=2: microbatch tokens are genuinely dp-sharded (the loss()
    wrapper pins the mb axis onto dp) and the loss still matches."""
    ref, _ = _one_step(1, pp=1)
    pipe, _ = _one_step(4, pp=2)  # dp=2 × pp=2
    np.testing.assert_allclose(pipe, ref, rtol=2e-5)


def test_pp_composes_with_tp_mesh_axis():
    """tp=2 × pp=2: Megatron widths under GSPMD inside the partial-manual
    shard_map; loss matches the unstaged run and a step still learns."""
    ref, _ = _one_step(1, pp=1)
    mesh = make_mesh(4, tp=2, pp=2)
    init_fn, step_fn, shard_batch = make_train_step(CFG, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    # widths actually sharded: wq [L/pp, D, H*hd] halves its layer AND
    # width axes per device (device_set alone would pass when replicated)
    wq = state.params["layers"]["wq"]
    assert wq.sharding.shard_shape(wq.shape)[0] == CFG.n_layers // 2
    assert wq.sharding.shard_shape(wq.shape)[2] == wq.shape[2] // 2
    toks = shard_batch(jnp.asarray(TOKENS))
    state, l1 = step_fn(state, toks)
    np.testing.assert_allclose(float(l1), ref, rtol=2e-5)
    state, l2 = step_fn(state, toks)
    assert float(l2) < float(l1)


def test_pp_dp_tp_all_compose():
    """dp=2 × tp=2 × pp=2 on the full 8-device mesh."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    ref, _ = _one_step(1, pp=1)
    mesh = make_mesh(8, tp=2, pp=2)  # dp=2 absorbs the rest
    init_fn, step_fn, shard_batch = make_train_step(CFG, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    state, loss = step_fn(state, shard_batch(jnp.asarray(TOKENS)))
    np.testing.assert_allclose(float(loss), ref, rtol=2e-5)


def test_pp_stage_owns_vocab_shards():
    """embed and lm_head are vocab-sharded over pp — no stage holds the
    full vocab matrices (stage ownership, VERDICT r2 weak #3)."""
    mesh = make_mesh(2, pp=2)
    init_fn, _, _ = make_train_step(CFG, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    emb = state.params["embed"]
    assert emb.sharding.shard_shape(emb.shape)[0] == CFG.vocab_size // 2
    head = state.params["lm_head"]
    assert head.sharding.shard_shape(head.shape)[1] == CFG.vocab_size // 2


def test_pp_rejects_non_dividing_layers():
    mesh = make_mesh(4, pp=4)  # tiny has 2 layers
    with pytest.raises(ValueError, match="must divide n_layers"):
        make_train_step(CFG, mesh)
