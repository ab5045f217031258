"""Speculative verify under a tp mesh: verify the COLLECTIVE SHAPE
(mirrors tests/test_sp_decode_hlo.py for the sequence-parallel decode).

The k-token verify step is one prefill-shaped attention call (t = k+1 per
lane, per-lane absolute positions) over the head-sharded KV arena. Under
tp, heads are embarrassingly parallel: the verify forward must keep each
chip on its own KV-head shard — NOT all-gather the cache shard, which
would scale verify's ICI traffic with the arena and erase the point of
batching the verification. These tests compile the real verify attention
computation (scatter the k+1 new KV rows, attend with the position mask)
under a tp mesh and assert on the HLO text.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from agentainer_tpu.analysis.hlo_contracts import NoLargeAllGather, check
from agentainer_tpu.ops.attention import attention_reference, cache_mask
from agentainer_tpu.parallel.mesh import make_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs the virtual multi-device mesh"
)

B, S, KV, G, HD = 2, 64, 2, 2, 16
H = KV * G
K = 4  # draft bucket: verify feeds t = K+1 tokens per lane
SHARD_ELEMS = B * S * (KV // 2) * HD  # one chip's cache shard


def _verify_attention(q, k_new, v_new, ck, cv, positions):
    """The verify step's attention body: scatter the k+1 freshly-projected
    KV rows at per-lane positions, then attend over the arena with the
    position mask (row j sees slot i iff i <= positions[b, j])."""
    batch_idx = jnp.arange(B)[:, None]
    ck = ck.at[batch_idx, positions].set(k_new)
    cv = cv.at[batch_idx, positions].set(v_new)
    return attention_reference(q, ck, cv, mask=cache_mask(positions, S))


def _compile_verify(tp: int) -> str:
    mesh = make_mesh(tp=tp)
    head_sh = NamedSharding(mesh, P(None, None, "tp", None))
    repl = NamedSharding(mesh, P())
    ck = jax.device_put(jnp.ones((B, S, KV, HD), jnp.float32), head_sh)
    cv = jax.device_put(jnp.ones((B, S, KV, HD), jnp.float32), head_sh)
    q = jax.device_put(jnp.ones((B, K + 1, H, HD), jnp.float32), head_sh)
    k_new = jax.device_put(jnp.ones((B, K + 1, KV, HD), jnp.float32), head_sh)
    v_new = jax.device_put(jnp.ones((B, K + 1, KV, HD), jnp.float32), head_sh)
    pos = jax.device_put(
        jnp.broadcast_to(jnp.arange(40, 40 + K + 1, dtype=jnp.int32), (B, K + 1)),
        repl,
    )
    lowered = jax.jit(_verify_attention).lower(q, k_new, v_new, ck, cv, pos)
    return lowered.compile().as_text()


def test_tp_verify_keeps_kv_shard_local():
    hlo = _compile_verify(2)
    check(hlo, NoLargeAllGather(SHARD_ELEMS, what="the tp verify KV shard"))


def test_tp_verify_numerics_match_unsharded():
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    ck = jax.random.normal(ks[0], (B, S, KV, HD), jnp.float32)
    cv = jax.random.normal(ks[1], (B, S, KV, HD), jnp.float32)
    q = jax.random.normal(ks[2], (B, K + 1, H, HD), jnp.float32)
    k_new = jax.random.normal(ks[3], (B, K + 1, KV, HD), jnp.float32)
    v_new = jax.random.normal(ks[4], (B, K + 1, KV, HD), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(40, 40 + K + 1, dtype=jnp.int32), (B, K + 1))
    want = _verify_attention(q, k_new, v_new, ck, cv, pos)

    mesh = make_mesh(tp=2)
    head_sh = NamedSharding(mesh, P(None, None, "tp", None))
    repl = NamedSharding(mesh, P())
    got = jax.jit(_verify_attention)(
        jax.device_put(q, head_sh),
        jax.device_put(k_new, head_sh),
        jax.device_put(v_new, head_sh),
        jax.device_put(ck, head_sh),
        jax.device_put(cv, head_sh),
        jax.device_put(pos, repl),
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
