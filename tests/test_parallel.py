"""SPMD tests on the 8-virtual-device CPU mesh.

The TPU-world analogue of multi-node tests the reference never had
(SURVEY.md §4): tensor-parallel forward must equal the single-device
forward; shardings must actually partition (not silently replicate); and
the layout rule (``plan_layout``) as a table, no engine built.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from agentainer_tpu.models.configs import get_config
from agentainer_tpu.models.llama import forward, init_params
from agentainer_tpu.parallel.mesh import make_mesh, pick_tp, plan_layout
from agentainer_tpu.parallel.sharding import param_shardings, shard_params


@pytest.fixture(scope="module")
def eight_devices():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return jax.devices()[:8]


def test_pick_tp():
    cfg = get_config("tiny")  # 4 heads, 2 kv heads
    assert pick_tp(cfg, 8) == 2
    assert pick_tp(cfg, 4) == 2
    assert pick_tp(cfg, 3) == 1
    big = get_config("llama3-8b")  # 32/8 heads
    assert pick_tp(big, 8) == 8


def test_tp_forward_matches_single_device(eight_devices):
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(8), (4, 8))

    ref_logits, _ = forward(params, cfg, tokens, positions, use_flash=False)

    mesh = make_mesh(tp=pick_tp(cfg, 8))
    sharded = shard_params(params, mesh)
    replicated = NamedSharding(mesh, P())

    fwd = jax.jit(lambda p, t, pos: forward(p, cfg, t, pos, use_flash=False)[0])
    tp_logits = fwd(
        sharded, jax.device_put(tokens, replicated), jax.device_put(positions, replicated)
    )
    np.testing.assert_allclose(np.asarray(tp_logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4)


def test_params_actually_partitioned(eight_devices):
    cfg = get_config("tiny")
    mesh = make_mesh(tp=2)
    params = shard_params(init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32), mesh)
    wq = params["layers"]["wq"]  # sharded over tp on last axis
    shard_shapes = {s.data.shape for s in wq.addressable_shards}
    full = wq.shape
    assert shard_shapes == {(full[0], full[1], full[2] // 2)}
    # replicated leaf: every shard is the full array
    norm = params["final_norm"]
    assert {s.data.shape for s in norm.addressable_shards} == {norm.shape}


# (tp, ep) of every registered configuration on an assignment of 1, 2, 4 and
# 8 chips with no tp/ep option: what LLMEngine.create derived at the commit
# before the rule moved into plan_layout (PR 28), written down from it.
ASSIGNED = {
    "llama3-8b": [(1, 1), (2, 1), (4, 1), (8, 1)],
    "mixtral-8x7b": [(1, 1), (1, 2), (1, 4), (1, 8)],
    "olmoe-1b-7b": [(1, 1), (1, 2), (1, 4), (1, 8)],
    "tiny": [(1, 1), (2, 1), (2, 1), (2, 1)],
    "tiny-moe": [(1, 1), (1, 2), (1, 4), (2, 4)],
    "tiny-olmoe": [(1, 1), (1, 2), (1, 4), (1, 8)],
    "bench-1b": [(1, 1), (2, 1), (4, 1), (8, 1)],
    # the hybrid block is served on one chip whatever is assigned (PR 30)
    "kimi-linear-48b": [(1, 1), (1, 1), (1, 1), (1, 1)],
    "tiny-kimi-linear": [(1, 1), (1, 1), (1, 1), (1, 1)],
    "olmo-hybrid-7b": [(1, 1), (1, 1), (1, 1), (1, 1)],
    "tiny-olmo-hybrid": [(1, 1), (1, 1), (1, 1), (1, 1)],
    # and so is a windowed cache: the ring's kernels are the one-chip ones (PR 37)
    "smallthinker-21b": [(1, 1), (1, 1), (1, 1), (1, 1)],
    "tiny-smallthinker": [(1, 1), (1, 1), (1, 1), (1, 1)],
    # the hybrid block with latent attention alone is the hybrid block (PR 40)
    "mistral-small-4-119b": [(1, 1), (1, 1), (1, 1), (1, 1)],
    "tiny-mistral4": [(1, 1), (1, 1), (1, 1), (1, 1)],
    # and with two kinds of attention and a ring in its cache (PR 50)
    "laguna-xs.2": [(1, 1), (1, 1), (1, 1), (1, 1)],
    "tiny-laguna": [(1, 1), (1, 1), (1, 1), (1, 1)],
    # and with chosen key blocks beside a conv-less linear state (PR 54)
    "minicpm-sala": [(1, 1), (1, 1), (1, 1), (1, 1)],
    "tiny-minicpm-sala": [(1, 1), (1, 1), (1, 1), (1, 1)],
    # and with K/V rows beside a KDA state, the plan chosen a kind at a time (PR 57)
    "solar-open2": [(1, 1), (1, 1), (1, 1), (1, 1)],
    "tiny-solar-open2": [(1, 1), (1, 1), (1, 1), (1, 1)],
}


def test_layout_table_covers_every_registered_configuration():
    from agentainer_tpu.models.configs import list_configs

    assert sorted(ASSIGNED) == sorted(list_configs())


@pytest.mark.parametrize("n_chips", [1, 2, 4, 8])
@pytest.mark.parametrize("config", sorted(ASSIGNED))
def test_plan_layout_spans_an_assignment(config, n_chips):
    want = ASSIGNED[config][(1, 2, 4, 8).index(n_chips)]
    assert plan_layout(get_config(config), n_chips, 8) == want


# config, chips assigned (0 = standalone), tp asked, ep asked → (tp, ep), and
# whether the span is narrower than asked or assigned (the log line)
ASKED = [
    # an assignment is the budget; options only narrow it
    ("tiny-moe", 8, 2, 2, (2, 2), True),
    ("tiny-moe", 8, 0, 2, (1, 2), True),
    ("mixtral-8x7b", 8, 2, 0, (2, 4), False),
    ("mixtral-8x7b", 8, 0, 4, (1, 4), True),
    ("olmoe-1b-7b", 8, 4, 0, (4, 2), False),
    ("llama3-8b", 8, 4, 0, (4, 1), True),
    ("tiny", 2, 1, 0, (1, 1), True),
    # standalone: exactly what the options ask for, at most what is visible
    ("tiny", 0, 0, 0, (1, 1), False),
    ("tiny", 0, 2, 0, (2, 1), False),
    ("tiny-moe", 0, 2, 2, (2, 2), False),
    ("tiny-moe", 0, 0, 4, (1, 4), False),
    ("mixtral-8x7b", 0, 16, 64, (1, 8), False),
    # asked beyond the heads: clamped to a divisor
    ("tiny", 0, 16, 0, (2, 1), True),
    # ep on a dense model is no axis at all
    ("llama3-8b", 0, 0, 2, (1, 1), True),
    # counts that divide nothing
    ("tiny", 3, 0, 0, (1, 1), True),
    ("tiny-moe", 3, 0, 0, (1, 2), True),
    ("llama3-8b", 6, 0, 0, (2, 1), True),
    ("tiny-moe", 4, 3, 3, (2, 2), False),
]


@pytest.mark.parametrize(
    "config,n_assigned,tp_asked,ep_asked,want,narrowed",
    ASKED,
    ids=[f"{c}-chips{n}-tp{t}-ep{e}" for c, n, t, e, _, _ in ASKED],
)
def test_plan_layout_options_narrow(
    config, n_assigned, tp_asked, ep_asked, want, narrowed, capsys
):
    got = plan_layout(get_config(config), n_assigned, 8, tp_asked, ep_asked)
    assert got == want
    assert ("parallelism narrowed" in capsys.readouterr().out) == narrowed
