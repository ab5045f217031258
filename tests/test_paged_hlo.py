"""Paged attention under a tp mesh: verify the COLLECTIVE SHAPE (mirrors
tests/test_spec_verify_hlo.py for the dense verify step).

The paged serving path scatters this step's K/V through the block table
into pool pages, gathers the lane's page view, and attends with the
position mask. Under tp the pool is sharded on the KV-HEAD axis while the
page axis stays whole — so the block-table gather must be SHARD-LOCAL:
each chip gathers its own head-slice of every page. An all-gather of the
pool (or of the gathered view) would scale the verify/decode ICI traffic
with the whole arena and erase paged serving's point. These tests compile
the real paged attention body under a tp mesh and assert on the HLO text.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from agentainer_tpu.analysis.hlo_contracts import NoLargeAllGather, check
from agentainer_tpu.ops.attention import (
    attention_reference,
    cache_mask,
    gather_pages,
    scatter_paged_kv,
)
from agentainer_tpu.parallel.mesh import make_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs the virtual multi-device mesh"
)

B, KV, G, HD = 2, 2, 2, 16
H = KV * G
PS = 16  # page size (tokens)
NB = 4  # blocks per lane
POOL = B * NB + 2  # physical pages
S = NB * PS
T = 5  # verify-shaped call: t = K+1 tokens per lane
SHARD_ELEMS = POOL * PS * (KV // 2) * HD  # one chip's pool shard


def _paged_attention(q, k_new, v_new, pool_k, pool_v, bt, positions):
    """The paged serving step's attention body: write the new rows through
    the block table, gather the page view, attend with the position mask."""
    pool_k, pool_v = scatter_paged_kv(pool_k, pool_v, k_new, v_new, bt, positions)
    ck, cv = gather_pages(pool_k, pool_v, bt)
    return attention_reference(q, ck, cv, mask=cache_mask(positions, S))


def _inputs():
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    pool_k = jax.random.normal(ks[0], (POOL, KV, PS, HD), jnp.float32)
    pool_v = jax.random.normal(ks[1], (POOL, KV, PS, HD), jnp.float32)
    q = jax.random.normal(ks[2], (B, T, H, HD), jnp.float32)
    k_new = jax.random.normal(ks[3], (B, T, KV, HD), jnp.float32)
    v_new = jax.random.normal(ks[4], (B, T, KV, HD), jnp.float32)
    bt = jnp.asarray(np.arange(B * NB, dtype=np.int32).reshape(B, NB))
    pos = jnp.broadcast_to(jnp.arange(40, 40 + T, dtype=jnp.int32), (B, T))
    return q, k_new, v_new, pool_k, pool_v, bt, pos


def _device_put_tp(args, mesh):
    head = NamedSharding(mesh, P(None, None, "tp", None))
    pool = NamedSharding(mesh, P(None, "tp", None, None))
    repl = NamedSharding(mesh, P())
    q, k_new, v_new, pool_k, pool_v, bt, pos = args
    return (
        jax.device_put(q, head),
        jax.device_put(k_new, head),
        jax.device_put(v_new, head),
        jax.device_put(pool_k, pool),
        jax.device_put(pool_v, pool),
        jax.device_put(bt, repl),
        jax.device_put(pos, repl),
    )


def test_tp_paged_gather_keeps_pool_shard_local():
    mesh = make_mesh(tp=2)
    args = _device_put_tp(_inputs(), mesh)
    hlo = jax.jit(_paged_attention).lower(*args).compile().as_text()
    check(hlo, NoLargeAllGather(SHARD_ELEMS, what="the paged KV pool shard"))


def test_tp_paged_numerics_match_unsharded():
    args = _inputs()
    want = _paged_attention(*args)
    mesh = make_mesh(tp=2)
    got = jax.jit(_paged_attention)(*_device_put_tp(args, mesh))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
