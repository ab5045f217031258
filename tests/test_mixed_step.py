"""The mixed step (ISSUE 31): ONE launch for a prefill chunk and the decode
lanes' step beside it.

``jit_prefill_with_decode`` runs a chunk's ``T`` rows and the ``B`` lanes'
rows together through every layer (``models/llama.forward``'s ``lanes``), so
a tick that used to launch ``jit_prefill`` and then the one-step
``jit_decode_n`` streams the weights once. Checked here on the CPU, on a
dense, a Mixtral-shaped and an OLMoE-shaped tiny model, (ISSUE 41) on the
hybrid block where it has no linear mixer (``tiny-mistral4``: latent rows and
the two per-lane controls) and (ISSUE 48) where it has one (``tiny-kimi-linear``:
KDA beside MLA; ``tiny-olmo-hybrid``: GDN beside full attention; a float32
state and a conv window a lane, which the other group's rows must not reach):
the program against the two it stands in for, the scheduler's rule (``_riders``) and the engines that have no such program, the
counters, the two failpoints, and a session whose turns rode mixed launches
resumed in a new engine.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu import faults
from agentainer_tpu.engine.llm import GenRequest, LLMEngine
from agentainer_tpu.models.llama import moe_sorts

# dense, Mixtral-shaped, OLMoE-shaped; the hybrid block with latent attention
# in every layer, with KDA beside it, and with GDN beside full attention
LINEAR = ["tiny-kimi-linear", "tiny-olmo-hybrid"]
FAMILIES = ["tiny", "tiny-moe", "tiny-olmoe", "tiny-mistral4", *LINEAR]
OPTS = {"max_batch": 4, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 32,
        "speculative": False, "skip_warmup": True}
LONG = "a document of many words that takes several prefill chunks to read "  # 68 bytes


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


# ---------------------------------------------------------------------------
# the program: one launch against the two it replaces


def _rows_of(cache) -> list:
    """The positional leaves of either block's cache: ``k`` and ``v``, or
    what the hybrid block keeps (``latent`` alone for ``tiny-mistral4``)."""
    return list(cache.rows()) if hasattr(cache, "rows") else [cache.k, cache.v]


def _lane_leaves_of(cache) -> list:
    """The per-lane leaves: the hybrid block's ``state`` and ``conv`` where
    it has a linear mixer, none anywhere else."""
    return [a for a in (getattr(cache, "state", None), getattr(cache, "conv", None)) if a is not None]


@pytest.mark.parametrize("weights", ["float", "int8"])
@pytest.mark.parametrize("model", FAMILIES)
def test_a_mixed_launch_is_the_chunk_then_the_one_step_decode(model, weights):
    """Same arena rows, same last-row logits, same carry and the same greedy
    tokens as ``jit_prefill`` followed by a one-step ``jit_decode_n``, from
    the same state, to the tolerance the sorted MoE path is held to against
    the einsum (tests/test_moe_sorted.py: 2e-5 at float32). ``float``: 128 +
    4 rows stay under this CPU's cut and both sides run the all-experts
    einsum (the matmuls' shapes differ, so a few sums round differently:
    2e-7). ``int8``: 132 rows are over the 121-row cut, so the launch's lanes
    go through the sorted grouped FFN where the decode step runs the einsum:
    the same sum in another order. A lane that does not step is left out of
    the comparison where the FFN sorts: its row is routed to no expert there
    (ISSUE 33: alone at an expert it would stream that expert for one tile),
    so its token and the ONE row it rewrites, where it stands, are its own;
    nobody reads either. On the K/V block that is the lane parked at the
    arena's last row. The hybrid block reads it from the cache's controls as
    its ``T = 1`` step does: the chunk's own lane (open, but parked past its
    ``stop``), and a lane fed its EOS, which closes (``stop = 0``) in both.
    Where the block has a linear mixer, every lane's state and conv window are
    compared too, the lanes that do not step included: nothing of them moves
    in either.

    One case is held wider, Olmo-Hybrid with int8 weights (1e-4), and by
    LLVM's doing, not the program's (PR 51; the next test). XLA:CPU
    marks a reduce's adds ``reassoc``, and LLVM at -O2 orders them by the
    loop it finds around them. Since PR 51 the gated delta rule's projections
    and their int8 scale stay inside the kind's loop, and in ``jit_prefill``
    alone (128 rows) LLVM then sums ``_l2norm``'s twelve squares of a key as
    three 4-wide partial sums where it had added them one after another
    (``add_rsqrt_fusion.1`` in ``--xla_dump_to``'s ``ir-with-opt.ll``;
    ``jit_decode_n`` and the mixed step keep the order, and with float
    weights all three do): the chunk's keys move by an ulp (1.2e-7 in layer
    0), and eight layers whose residual adds a sublayer's NORMED output carry
    that to the last full layer's V rows. Read on this host, mixed against
    chunk-then-step, the largest difference of any leaf, under
    ``--xla_cpu_max_isa=`` SSE4_2 / AVX / AVX2 / AVX512: int8 4.02e-5 /
    3.66e-5 / 3.58e-5 / 3.58e-5 (3.3e-6 at the parent); float 3.3e-6 / 3.1e-6
    / 4.6e-6 / 4.6e-6, which 2e-5 holds as before. Kimi-Linear, whose chunk
    reorders the same sum, reads 8.6e-6 at most and stays at 2e-5 too."""
    _mixed_against_chunk_then_step(model, weights, 1e-4 if (model, weights) == ("tiny-olmo-hybrid", "int8") else 2e-5)


def test_olmo_hybrid_int8_meets_the_others_tolerance_where_llvm_may_not_reorder(where_llvm_may_not_reorder):
    """The one case the test above holds wider, under ``conftest``'s flags:
    the same programs, the same barriers, 2e-5 (5.9e-6 read, PR 51, which is
    what the parent's programs read under these flags)."""
    where_llvm_may_not_reorder(
        "from tests.test_mixed_step import _mixed_against_chunk_then_step as check\n"
        "check('tiny-olmo-hybrid', 'int8', 2e-5)\n"
    )


def _mixed_against_chunk_then_step(model: str, weights: str, atol: float):
    quant = {"quant": "int8"} if weights == "int8" else {}
    eng = LLMEngine.create(model, options={**OPTS, "prefill_chunk": 128, **quant})
    try:
        B, S, T, n_real, lane = eng.max_batch, eng.max_seq, 128, 100, 2
        hybrid = eng.cfg.is_hybrid
        weights_of = eng.params if hybrid else eng.params["layers"]
        if eng.cfg.is_moe:
            assert moe_sorts(eng.cfg, weights_of, T + B) is (weights == "int8")
            assert not moe_sorts(eng.cfg, weights_of, B)
        rng = np.random.default_rng(7)
        draw = lambda *shape: jnp.asarray(rng.integers(1, eng.cfg.vocab_size, shape), jnp.int32)  # noqa: E731
        # three lanes hold a context of their own; lane 2 takes the chunk
        cache, context = eng.cache, {0: 21, 1: 9, 3: 30}
        for idx, n in context.items():
            _, cache = eng._prefill(
                eng.params, cache, jnp.int32(idx), draw(1, 32), jnp.arange(32, dtype=jnp.int32)[None], jnp.int32(n)
            )
        tokens, positions = draw(1, T), (40 + jnp.arange(T, dtype=jnp.int32))[None]
        lane_tok = draw(B)
        lane_pos = jnp.asarray([context.get(i, S - 1) for i in range(B)], jnp.int32)  # lane 2 parked at scratch
        idle = {lane: S - 1}  # the lanes that do not step, and the row each rewrites
        if hybrid:
            # every lane admitted, as the engine does it: open up to the end of
            # its reply; lane 1 is fed the token that closes it
            for idx in range(B):
                eos = int(lane_tok[1]) if idx == 1 else -1
                cache = eng._admit_state(cache, jnp.int32(idx), jnp.bool_(False), jnp.int32(200), jnp.int32(eos))
            idle[1] = context[1]
        temps, topk, topp = jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.float32)
        keys = jax.random.split(jax.random.PRNGKey(3), 1)

        last_a, cache_a = eng._prefill(eng.params, _copy(cache), jnp.int32(lane), tokens, positions, jnp.int32(n_real))
        toks_a, tok_a, pos_a, cache_a = eng._decode_n(
            eng.params, cache_a, _copy(lane_tok), _copy(lane_pos), temps, topk, topp, keys
        )
        last_m, toks_m, tok_m, pos_m, cache_m = eng._prefill_with_decode(
            eng.params, _copy(cache), jnp.int32(lane), tokens, positions, jnp.int32(n_real),
            _copy(lane_tok), _copy(lane_pos), temps, topk, topp, keys,
        )
        assert toks_m.shape == toks_a.shape == (1, B)
        stepping = sorted(set(context) - set(idle))

        def rows(arena):  # all but the rows the idle lanes rewrite
            for idx, at in idle.items():
                arena = arena.at[:, idx, at].set(0)
            return np.asarray(arena)

        np.testing.assert_allclose(np.asarray(last_m), np.asarray(last_a), atol=atol, rtol=0)
        for got, want in zip(_rows_of(cache_m), _rows_of(cache_a)):
            np.testing.assert_allclose(rows(got), rows(want), atol=atol, rtol=0)
            assert np.isfinite(np.asarray(got)).all()
        assert len(_lane_leaves_of(cache_m)) == (2 if model in LINEAR else 0)
        for got, want, before in zip(_lane_leaves_of(cache_m), _lane_leaves_of(cache_a), _lane_leaves_of(cache)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol, rtol=0)
            for idx in stepping + [lane]:  # the lanes that step and the chunk's own moved
                assert not np.array_equal(np.asarray(got[:, idx]), np.asarray(before[:, idx]))
            assert np.array_equal(np.asarray(got[:, 1]), np.asarray(before[:, 1]))  # the lane fed its EOS did not
        assert np.asarray(toks_m)[:, stepping].tolist() == np.asarray(toks_a)[:, stepping].tolist()
        assert np.asarray(tok_m)[stepping].tolist() == np.asarray(tok_a)[stepping].tolist()
        assert np.asarray(pos_m).tolist() == np.asarray(pos_a).tolist()
        if hybrid:  # the controls moved alike: the fed EOS closed lane 1 and no other
            assert np.asarray(cache_m.stop).tolist() == np.asarray(cache_a.stop).tolist() == [200, 0, 200, 200]
            assert np.asarray(cache_m.eos).tolist() == np.asarray(cache_a.eos).tolist()
        first = _rows_of(cache_m)[0]
        if eng.cfg.is_moe and weights == "int8":  # an idle lane's row went through no expert
            want = _rows_of(cache_a)[0]
            for idx, at in idle.items():
                assert not np.allclose(np.asarray(first[1:, idx, at]), np.asarray(want[1:, idx, at]), atol=atol)
        # the chunk's rows were written at lane 2 and the lanes' at their own positions
        assert float(jnp.abs(first[:, lane, 40:40 + T]).max()) > 0
        assert float(jnp.abs(first[:, 0, context[0]]).max()) > 0
    finally:
        eng.shutdown()


def _hybrid_case(model: str):
    """(configuration, float32 parameters, a cache of 4 lanes of 64 rows with
    three contexts in it and every lane admitted) for the forward-level
    checks: ``tiny-mistral4``, ``full-only`` (``tiny-olmo-hybrid``'s softmax
    attention over K/V rows in every layer: the hybrid block's other
    positional kind, no linear mixer either) or a model that has one
    (``LINEAR``)."""
    import dataclasses

    from agentainer_tpu.models import hybrid
    from agentainer_tpu.models.configs import get_config

    if model == "full-only":
        cfg = dataclasses.replace(get_config("tiny-olmo-hybrid"), layer_kinds=("full",) * 3, n_layers=3, n_dense_layers=3, name=model)
    else:
        cfg = get_config(model)
    params = hybrid.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cache = hybrid.init_cache(cfg, 4, 64, jnp.float32, live=False)
    rng = np.random.default_rng(11)
    draw = lambda *shape: jnp.asarray(rng.integers(1, cfg.vocab_size, shape), jnp.int32)  # noqa: E731
    plan = hybrid.plan_hybrid(cfg, use_pallas=False)
    for idx, n in {0: 12, 1: 5, 3: 20}.items():
        valid = jnp.arange(24)[None] < n
        _, cache = hybrid.forward(params, cfg, draw(1, 24), jnp.arange(24)[None], cache, plan=plan, slot=idx, valid=valid)
    for idx in range(4):
        cache = hybrid.admit_lane(cache, idx, False, 60, -1)
    return cfg, params, cache, plan, draw


@pytest.mark.parametrize("model", ["tiny-mistral4", "full-only", *LINEAR])
def test_the_hybrid_forward_with_lanes_is_its_two_calls(model):
    """``hybrid.forward(lanes=...)`` against the chunk's call and then the
    ``T = 1`` call, for both positional kinds alone and each beside its linear
    kind: the head's ``1 + B`` rows, every positional leaf (but the row of the
    lane that does not step), the state and conv where there are any, and
    ``stop``. Lane 3 stands at its ``stop``: closed, it writes the row where it
    stands and nothing else."""
    from agentainer_tpu.models import hybrid

    cfg, params, cache, plan, draw = _hybrid_case(model)
    cache = hybrid.admit_lane(cache, 3, False, 20, -1)
    T, n_real, slot = 16, 11, 2
    tokens, positions = draw(1, T), (7 + jnp.arange(T, dtype=jnp.int32))[None]
    valid = jnp.arange(T)[None] < n_real
    lane_tok, lane_pos = draw(4, 1), jnp.asarray([12, 5, 63, 20], jnp.int32)[:, None]
    kw = {"plan": plan, "slot": slot, "valid": valid}
    chunk, two = hybrid.forward(params, cfg, tokens, positions, cache, **kw)
    step, two = hybrid.forward(params, cfg, lane_tok, lane_pos, two, plan=plan)
    logits, one = hybrid.forward(params, cfg, tokens, positions, cache, lanes=(lane_tok, lane_pos), last=n_real - 1, **kw)
    assert logits.shape == (1 + 4, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(chunk[0, n_real - 1]), atol=2e-5, rtol=0)
    for i in (0, 1):  # the lanes that step
        np.testing.assert_allclose(np.asarray(logits[1 + i]), np.asarray(step[i, 0]), atol=2e-5, rtol=0)
    assert np.asarray(one.stop).tolist() == np.asarray(two.stop).tolist()
    for got, want, before in zip(one.rows(), two.rows(), cache.rows()):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=0)
        changed = np.argwhere(np.abs(np.asarray(got) - np.asarray(before)).reshape(*got.shape[:3], -1).max(-1) > 0)
        assert {(int(b), int(p)) for _, b, p in changed if b != slot} == {(0, 12), (1, 5), (3, 20)}
        assert {int(p) for _, b, p in changed if b == slot} == set(range(7, 7 + T)) | {63}
    for got, want, before in zip(_lane_leaves_of(one), _lane_leaves_of(two), _lane_leaves_of(cache)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=0)
        moved = [bool((np.asarray(got[:, i]) != np.asarray(before[:, i])).any()) for i in range(4)]
        assert moved == [True, True, True, False]  # lanes 0 and 1 stepped, lane 2 took the chunk, lane 3 is closed


# ---------------------------------------------------------------------------
# a linear mixer's per-lane leaves: neither group's rows reach the other's


def _mixer_case(model: str, T: int = 16, slot: int = 2):
    """One linear layer of ``model`` taken alone, so that both sides of a
    comparison read the same normed rows and a difference can only be the
    mixer's: ``(mixer(h, state, conv, slot, valid, n_lanes) -> (o, state,
    conv), h [1, T + 4, d], the chunk's valid [1, T], a cache with three
    contexts in its state and conv)``."""
    from agentainer_tpu.models import hybrid

    cfg, params, cache, plan, _ = _hybrid_case(model)
    fn = hybrid.kda_mixer if cfg.linear_kind == "kda" else hybrid.gdn_mixer
    lp = hybrid._layer_of(params[cfg.linear_kind], 1)

    def mixer(h, state, conv, slot, valid, n_lanes=0):
        return fn(h, lp, cfg, state, conv, 1, slot, valid, plan, n_lanes)

    h = jax.random.normal(jax.random.PRNGKey(5), (1, T + 4, cfg.dim), jnp.float32)
    return mixer, h, jnp.arange(T)[None] < T - 5, cache


def _lanes_first(fn, n_lanes, slot, stack, *arrays):
    """``hybrid._stack_by_group`` with the two groups in the other order."""
    from agentainer_tpu.models import hybrid

    if not n_lanes:
        return fn(*arrays, slot, stack)
    chunk, lanes = zip(*hybrid._groups(n_lanes, *arrays))
    o_l, stack = fn(*lanes, None, stack)
    o_c, stack = fn(*chunk, slot, stack)
    return jnp.concatenate([o_c[0], o_l[:, 0]], axis=0)[None], stack


@pytest.mark.parametrize("order", ["chunk_first", "lanes_first"])
@pytest.mark.parametrize("model", LINEAR)
def test_the_chunks_own_lane_is_not_stepped(model, order, monkeypatch):
    """The chunk's own lane is one of the B lanes of the step and its row is
    not valid (the engine parks it at or past its ``stop``): after a mixed
    launch its state and conv window are BITWISE the chunk-alone launch's, and
    every other lane's the step-alone launch's, whichever group the delta
    rule runs first (the step of a lane that is not valid writes back what it
    read; the conv's B windows go back in one write, the chunk's among them). At
    the forward, where ``valid`` comes from the cache's controls, the token
    the carry happens to hold for that lane changes nothing of it."""
    from agentainer_tpu.models import hybrid

    if order == "lanes_first":
        monkeypatch.setattr(hybrid, "_stack_by_group", _lanes_first)
    T, slot = 16, 2
    mixer, h, valid_c, cache = _mixer_case(model, T, slot)
    valid_l = jnp.asarray([[True, True, False, True]])
    _, s_chunk, c_chunk = mixer(h[:, :T], cache.state, cache.conv, slot, valid_c)
    _, s_step, c_step = mixer(h[0, T:, None], cache.state, cache.conv, None, valid_l.T)
    _, s_mixed, c_mixed = mixer(h, cache.state, cache.conv, slot, jnp.concatenate([valid_c, valid_l], axis=1), 4)
    for mixed, chunk, step, before in ((s_mixed, s_chunk, s_step, cache.state), (c_mixed, c_chunk, c_step, cache.conv)):
        assert np.array_equal(np.asarray(mixed[:, slot]), np.asarray(chunk[:, slot]))
        assert not np.array_equal(np.asarray(mixed[1, slot]), np.asarray(before[1, slot]))
        for lane in (0, 1, 3):
            assert np.array_equal(np.asarray(mixed[:, lane]), np.asarray(step[:, lane]))
            assert not np.array_equal(np.asarray(mixed[1, lane]), np.asarray(before[1, lane]))

    cfg, params, cache, plan, draw = _hybrid_case(model)
    tokens, positions = draw(1, T), (7 + jnp.arange(T, dtype=jnp.int32))[None]
    lane_pos = jnp.asarray([12, 5, 63, 20], jnp.int32)[:, None]  # lane 2 parked past its stop of 60
    kw = {"plan": plan, "slot": slot, "valid": valid_c, "last": T - 6}
    lane_tok = draw(4, 1)
    _, one = hybrid.forward(params, cfg, tokens, positions, cache, lanes=(lane_tok, lane_pos), **kw)
    _, other = hybrid.forward(params, cfg, tokens, positions, cache, lanes=(lane_tok.at[slot].add(1), lane_pos), **kw)
    for a, b in zip(_lane_leaves_of(one), _lane_leaves_of(other)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("model", LINEAR)
def test_a_lanes_conv_window_never_slides_over_the_chunks_rows(model):
    """Two mixed launches whose chunks differ (their last rows too: what a
    window slid over ``[1, T + B]`` rows as one sequence would hold) and whose
    lanes' rows are the same: every stepping lane's output, state and conv
    window are bitwise the same in both, and the one-step launch's."""
    T, slot = 16, 2
    mixer, h, valid_c, cache = _mixer_case(model, T, slot)
    valid_l = jnp.asarray([[True, True, False, True]])
    valid = jnp.concatenate([valid_c | True, valid_l], axis=1)  # every row of the chunk real, its last ones too
    other = h.at[:, :T].multiply(-1.5)
    o_step, s_step, c_step = mixer(h[0, T:, None], cache.state, cache.conv, None, valid_l.T)
    o_a, s_a, c_a = mixer(h, cache.state, cache.conv, slot, valid, 4)
    o_b, s_b, c_b = mixer(other, cache.state, cache.conv, slot, valid, 4)
    assert not np.array_equal(np.asarray(c_a[1, slot]), np.asarray(c_b[1, slot]))  # the chunk's own window differs
    for lane in (0, 1, 3):
        for a, b, step in ((s_a, s_b, s_step), (c_a, c_b, c_step)):
            assert np.array_equal(np.asarray(a[:, lane]), np.asarray(b[:, lane]))
            assert np.array_equal(np.asarray(a[:, lane]), np.asarray(step[:, lane]))
        assert np.array_equal(np.asarray(o_a[0, T + lane]), np.asarray(o_b[0, T + lane]))
        assert np.array_equal(np.asarray(o_a[0, T + lane]), np.asarray(o_step[lane, 0]))


@pytest.mark.parametrize("model", LINEAR)
def test_a_closed_lane_riding_a_chunk_keeps_its_state(model):
    """Lane 1 is closed (``stop = 0``: its request ended, its session's state
    is what the next turn resumes from) and lane 0 is fed the token that
    closes it: a mixed launch leaves the state and conv window of both bitwise
    as they were and closes lane 0, as the one-step launch does; lane 3 steps."""
    from agentainer_tpu.models import hybrid

    cfg, params, cache, plan, draw = _hybrid_case(model)
    T, slot = 16, 2
    lane_tok, lane_pos = draw(4, 1), jnp.asarray([12, 5, 63, 20], jnp.int32)[:, None]
    cache = hybrid.admit_lane(cache, 0, False, 60, int(lane_tok[0, 0]))
    cache = cache._replace(stop=cache.stop.at[1].set(0))
    tokens, positions = draw(1, T), (7 + jnp.arange(T, dtype=jnp.int32))[None]
    _, after = hybrid.forward(
        params, cfg, tokens, positions, cache, plan=plan, slot=slot, valid=jnp.arange(T)[None] < 11,
        lanes=(lane_tok, lane_pos), last=10,
    )
    assert np.asarray(after.stop).tolist() == [0, 0, 60, 60]
    for got, before in zip(_lane_leaves_of(after), _lane_leaves_of(cache)):
        for lane in (0, 1):
            assert np.array_equal(np.asarray(got[:, lane]), np.asarray(before[:, lane]))
        for lane in (slot, 3):
            assert not np.array_equal(np.asarray(got[:, lane]), np.asarray(before[:, lane]))


# ---------------------------------------------------------------------------
# the scheduler: the served path takes it, counts it, and gives the same tokens


async def _traffic(eng, n_long=3, reply=12):
    """One steady generation, then ``n_long`` multi-chunk prompts beside it:
    a chunk is pending while lanes decode for most of the run."""
    steady = asyncio.ensure_future(eng.generate("steady reply", max_tokens=40, ignore_eos=True, session="steady"))
    for _ in range(4000):
        await asyncio.sleep(0.002)
        idx = eng.sessions.get("steady")
        if idx is not None and eng.slots[idx].decoding:
            break
    longs = await asyncio.gather(
        *(eng.generate(LONG * (2 + i), max_tokens=reply, ignore_eos=True, session=f"doc{i}") for i in range(n_long))
    )
    return [await steady] + list(longs)


@pytest.fixture(scope="module", params=FAMILIES)
def served(request):
    """(engine, the replies under contention, the same requests one at a
    time on the same engine, its /metrics after the contended run)."""
    eng = LLMEngine.create(request.param, options=dict(OPTS))
    try:
        contended = asyncio.run(_traffic(eng))
        m = eng.metrics()
        eng.clear_sessions()

        async def alone():
            out = [await eng.generate("steady reply", max_tokens=40, ignore_eos=True)]
            for i in range(3):
                out.append(await eng.generate(LONG * (2 + i), max_tokens=12, ignore_eos=True))
            return out

        before = eng.mixed_launches
        one_by_one = asyncio.run(alone())
        assert eng.mixed_launches == before  # a lone request never has a chunk beside a decoding lane
        yield eng, contended, one_by_one, m
    finally:
        eng.shutdown()


def test_contended_ticks_ride_and_give_the_two_launch_tokens(served):
    _, contended, one_by_one, m = served
    assert m["mixed_launches"] > 0 and m["worker_errors"] == 0
    assert [r["tokens"] for r in contended] == [r["tokens"] for r in one_by_one]


def test_a_mixed_launch_counts_as_a_prefill_and_a_decode_step_and_no_decode_chunk(served):
    """``decode_chunk_hist`` is ``jit_decode_n``'s launches alone (the
    benchmark's decode roofline multiplies it by that module's launches in a
    trace): a mixed launch moves ``prefill_launches``, ``decode_steps`` and
    ``mixed_launches``, the occupancy sum, and the spans of both paths."""
    _, _, _, m = served
    p = m["phases"]
    assert m["mixed_launches"] == p["engine.mixed_dispatch"]["n"]
    assert m["prefill_launches"] == p["engine.prefill_dispatch"]["n"] == p["engine.prefill_tick"]["n"]
    assert sum(m["decode_chunk_hist"].values()) == p["engine.decode_dispatch"]["n"]
    assert m["decode_steps"] == m["mixed_launches"] + sum(m["decode_chunk_hist"].values())
    assert m["mixed_launches"] <= m["mixed_decode_lanes"] <= m["mixed_launches"] * (m["max_batch"] - 1)
    assert 0 < m["batch_occupancy"] <= 1
    # the mixed span nests in the prefill dispatch: a leaf
    assert p["engine.mixed_dispatch"]["self_s"] == p["engine.mixed_dispatch"]["total_s"]
    assert p["engine.prefill_dispatch"]["total_s"] >= p["engine.mixed_dispatch"]["total_s"]


def test_the_moe_counters_count_a_mixed_launch_as_one_pass_of_its_rows():
    """``_count_moe_rows(bucket + max_batch)``: one pass, ``T + B`` rows."""
    eng = LLMEngine.create("tiny-moe", options=dict(OPTS))
    try:
        eng.shutdown()  # the worker is gone; the counters are host arithmetic
        k, e = eng.cfg.experts_per_token, eng.cfg.n_experts
        before = dict(eng.moe)
        eng._count_moe_rows(32 + eng.max_batch)
        assert eng.moe["assignments"] - before["assignments"] == 36 * k
        assert eng.moe["rows_all_experts"] - before["rows_all_experts"] == 36 * e  # under this CPU's cut
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# the rule, case by case, on an engine whose worker has stopped


def _request(max_tokens=16, dispatched=1):
    r = GenRequest(id="r", session="", prompt_ids=[1], max_tokens=max_tokens, temperature=0.0, loop=None, future=None)
    r.dispatched = dispatched
    return r


def _decoding(eng, idx, **kw):
    s = eng.slots[idx]
    s.request, s.decoding, s.pending_prompt, s.dev_position = _request(**kw), True, [], 10 + idx
    return s


def _prefilling(eng, idx, n_tokens):
    s = eng.slots[idx]
    s.request, s.decoding, s.pending_prompt = _request(), False, [5] * n_tokens
    return s


RULE = {
    # name: (decoding lanes, pending prompts' lengths, a queued request, want riders)
    "a_chunk_with_more_to_come_beside_two_lanes": ([0, 1], [70], False, [0, 1]),
    "two_prompts_of_one_chunk_each": ([0], [20, 20], False, [0]),
    "the_only_prompts_last_chunk_and_a_waiter_a_free_slot_could_take": ([0], [20], True, [0]),
    "the_only_prompts_last_chunk_keeps_the_lanes_longer_rung_or_verify_round": ([0, 1], [20], False, None),
    "nothing_pending": ([0, 1], [], False, None),
    "nothing_decoding": ([], [70], False, None),
}


@pytest.fixture(scope="module")
def stopped():
    eng = LLMEngine.create("tiny", options=dict(OPTS))
    eng.shutdown()
    return eng


@pytest.mark.parametrize("case", sorted(RULE))
def test_a_tick_rides_exactly_when_its_decode_would_be_the_plain_one_step(stopped, case):
    eng = stopped
    lanes, prompts, waiter, want = RULE[case]
    for s in eng.slots:
        eng._reset_slot(s)
    eng._waiting = []
    for idx in lanes:
        _decoding(eng, idx)
    for j, n in enumerate(prompts):
        _prefilling(eng, len(lanes) + j, n)
    if waiter:
        eng._waiting.append(_request())
    got = eng._riders()
    if want is None:
        assert got is None
    else:
        assert [(s.idx, p) for s, _, p in got] == [(i, 10 + i) for i in want]


def test_lanes_whose_budget_is_all_in_flight_do_not_ride(stopped):
    eng = stopped
    for s in eng.slots:
        eng._reset_slot(s)
    _decoding(eng, 0, max_tokens=8, dispatched=8)
    _prefilling(eng, 1, 70)
    assert eng._riders() is None
    _decoding(eng, 2, max_tokens=8, dispatched=3)
    assert [s.idx for s, _, _ in eng._riders()] == [0, 2]  # one launch steps every live lane, as decode_n does


LADDER = {
    # name: (model, options, the mixed step's rungs)
    "the_two_largest_of_a_256_row_chunk": ("tiny", {"quant": "int8", "prefill_chunk": 256}, (128, 256)),
    "whatever_the_weights_are_stored_as": ("tiny", {"prefill_chunk": 256}, (128, 256)),
    "a_chunk_of_the_smallest_bucket_has_one": ("tiny", {"quant": "int8", "prefill_chunk": 32}, (32,)),
    "a_linear_mixer_changes_nothing_of_it": ("tiny-olmo-hybrid", {"quant": "int8", "prefill_chunk": 256}, (128, 256)),
}


@pytest.mark.parametrize("case", sorted(LADDER))
def test_the_mixed_ladder_is_the_two_largest_buckets_a_chunk_can_take(case, monkeypatch):
    """A trade for the boot, stated as one: each rung of the mixed step is a
    program traced, lowered and read back at every start, so it has the two
    largest prefill buckets a chunk can take and no smaller rung. A ridden
    chunk of 20 tokens takes the first of them, a plain one its own bucket,
    and warm-up builds exactly those rungs."""
    model, extra, want = LADDER[case]
    eng = LLMEngine.create(model, options={**OPTS, **extra, "max_seq": 512})
    try:
        eng.shutdown()
        assert eng._mixed_buckets == want
        for s in eng.slots:
            eng._reset_slot(s)
        riders = [(s, s.request, s.dev_position) for s in (_decoding(eng, 0),)]
        slot, seen = _prefilling(eng, 1, 20 + 2 * eng.prefill_chunk), []
        logits, toks = jnp.zeros((eng.cfg.vocab_size,), jnp.float32), jnp.zeros((1, eng.max_batch), jnp.int32)
        monkeypatch.setattr(eng, "_launch_with_decode", lambda idx, tokens, pos, n: seen.append((tokens.shape, n)) or (logits, toks))
        monkeypatch.setattr(eng, "_prefill", lambda params, cache, idx, tokens, pos, n: seen.append((tokens.shape, int(n))) or (logits, cache))
        eng._prefill_chunk(slot, riders)  # a whole chunk, ridden
        eng._prefill_chunk(slot)  # a whole chunk alone
        eng._prefill_chunk(slot, riders)  # the 20 tokens left, ridden
        chunk = eng.prefill_chunk
        assert seen == [((1, chunk), chunk), ((1, chunk), chunk), ((1, want[0]), 20)]
        slot.pending_prompt = [5] * 20
        eng._prefill_chunk(slot)  # and alone: the plain ladder's own bucket
        assert seen[-1] == ((1, 32), 20)
    finally:
        eng.shutdown()


NO_PROGRAM = {
    "paged": ("tiny", {"paged_kv": True}),
    "fused": ("tiny", {"paged_kv": True, "fused_decode": True}),
    "meshed": ("tiny", {"tp": 2}),
    "routed": ("tiny-moe", {"routed": True}),
    "no_one_step_rung": ("tiny", {"adaptive_decode": False}),
}


@pytest.mark.parametrize("kind", sorted(NO_PROGRAM))
def test_engines_without_the_program_keep_two_launches(kind):
    """Decided at build by what the engine is, never by a model's name: the
    page pool, the fused loop, a mesh, the ``routed`` dispatch and a ladder
    without the one-step rung have no mixed program; under the traffic that
    makes the dense engine ride they launch none."""
    model, extra = NO_PROGRAM[kind]
    eng = LLMEngine.create(model, options={**OPTS, **extra})
    try:
        assert eng._prefill_with_decode is None and eng._riders() is None
        replies = asyncio.run(_traffic(eng, n_long=2, reply=6))
        assert [r["completion_tokens"] for r in replies] == [40, 6, 6]
        m = eng.metrics()
        assert m["mixed_launches"] == 0 and m["mixed_decode_lanes"] == 0
        assert "engine.mixed_dispatch" not in m["phases"]
        assert m["decode_steps"] == sum(m["decode_chunk_hist"].values())
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# failpoints: the prompt's seam first, then the lanes'


@pytest.fixture()
def armed():
    yield faults
    faults.disarm_all()


async def _until_decoding(eng, session):
    for _ in range(4000):
        await asyncio.sleep(0.002)
        idx = eng.sessions.get(session)
        if idx is not None and eng.slots[idx].decoding and eng.slots[idx].request.generated:
            return
    raise AssertionError(f"{session} never started decoding")


def test_a_poisoned_prompt_fails_alone_on_a_tick_that_would_have_ridden(armed):
    """``engine.prefill`` fires before ``engine.decode_step`` and before the
    launch: the culprit fails, the lanes it would have carried take their
    plain step in the same tick, and their replies are the undisturbed ones."""
    eng = LLMEngine.create("tiny", options=dict(OPTS))
    try:
        want = asyncio.run(eng.generate("steady reply", max_tokens=48, ignore_eos=True))["tokens"]

        async def scenario():
            steady = asyncio.ensure_future(
                eng.generate("steady reply", max_tokens=48, ignore_eos=True, session="a", request_id="steady-1")
            )
            await _until_decoding(eng, "a")
            armed.arm("engine.prefill", error="RuntimeError", count=1)
            with pytest.raises(Exception, match="engine.prefill|injected|RuntimeError"):
                await eng.generate(LONG * 3, max_tokens=4, request_id="poisoned-1")
            after = await eng.generate(LONG * 3, max_tokens=4, ignore_eos=True, request_id="after-1")
            return (await steady)["tokens"], after

        got, after = asyncio.run(scenario())
        assert got == want and after["completion_tokens"] == 4
        m = eng.metrics()
        assert m["worker_errors"] == 1 and m["cache_resets"] == 0
    finally:
        eng.shutdown()


def test_both_seams_fire_before_a_mixed_launch_the_prompts_first(stopped, armed):
    """On a stopped engine, ``_prefill_chunk`` with riders: the prompt's seam
    raises the injected error itself (the worker fails that request alone);
    the lanes' seam raises it wrapped as ``RidersFault`` (the worker fails the
    batch); with both armed the prompt's comes first; nothing was launched."""
    from agentainer_tpu.engine.llm import RidersFault

    eng = stopped
    for s in eng.slots:
        eng._reset_slot(s)
    riders = [(s, s.request, s.dev_position) for s in (_decoding(eng, 0),)]
    slot = _prefilling(eng, 1, 70)
    launches = eng.prefill_launches
    armed.arm("engine.decode_step", error="TimeoutError", count=1)
    with pytest.raises(RidersFault) as caught:
        eng._prefill_chunk(slot, riders)
    assert isinstance(caught.value.__cause__, TimeoutError)
    armed.arm("engine.decode_step", error="TimeoutError", count=1)
    armed.arm("engine.prefill", error="ConnectionError", count=1)
    with pytest.raises(ConnectionError):
        eng._prefill_chunk(slot, riders)
    owed = {f["name"]: f["count"] for f in armed.active()}
    assert owed["engine.decode_step"] == 1 and owed["engine.prefill"] == 0  # the lanes' seam was not reached
    assert eng.prefill_launches == launches and len(slot.pending_prompt) == 70


def test_a_decode_fault_while_chunks_ride_is_batch_wide(armed):
    """``engine.decode_step`` armed while prompts prefill beside a decoding
    lane (the seam of the next mixed launch takes it, or the plain dispatch's
    on a tick that does not ride): every in-flight request fails (one
    compiled call covers every lane), and the engine serves on."""
    eng = LLMEngine.create("tiny", options=dict(OPTS))
    try:
        async def scenario():
            steady = asyncio.ensure_future(
                eng.generate("steady reply", max_tokens=200, ignore_eos=True, session="a", request_id="steady-2")
            )
            await _until_decoding(eng, "a")
            before = eng.mixed_launches
            # two multi-chunk prompts beside the steady lane: their ticks ride
            docs = [
                asyncio.ensure_future(eng.generate(LONG * 3, max_tokens=4, request_id=f"doc-{i}")) for i in range(2)
            ]
            for _ in range(4000):
                await asyncio.sleep(0.001)
                if eng.mixed_launches > before:
                    break
            assert eng.mixed_launches > before
            armed.arm("engine.decode_step", error="RuntimeError", count=1)
            return await asyncio.gather(steady, *docs, return_exceptions=True)

        results = asyncio.run(scenario())
        assert isinstance(results[0], Exception), results[0]
        assert any(isinstance(r, Exception) for r in results[1:])
        assert eng.metrics()["worker_errors"] == 1
        ok = asyncio.run(eng.generate("after the fault", max_tokens=4, ignore_eos=True))
        assert ok["completion_tokens"] == 4
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# a journaled turn replayed after a kill


@pytest.mark.parametrize("model", FAMILIES)
def test_a_turn_killed_mid_decode_replays_token_identically_after_mixed_launches(model):
    """Turn one of a session decodes while other prompts prefill, so its KV
    rows were written by mixed launches; the engine dies mid-decode of turn
    two (the journal then replays the request); a new engine restores the
    snapshot taken after turn one and gives turn two the tokens of an engine
    that never saw another request and never stopped."""

    async def uninterrupted():
        eng = LLMEngine.create(model, options=dict(OPTS))
        try:
            a = await eng.chat("s", "turn one", max_tokens=24, ignore_eos=True)
            b = await eng.chat("s", "turn two", max_tokens=24, ignore_eos=True)
            assert eng.mixed_launches == 0
            return a["tokens"], b["tokens"]
        finally:
            eng.shutdown()

    async def interrupted():
        eng1 = LLMEngine.create(model, options=dict(OPTS))
        try:
            one = asyncio.ensure_future(eng1.chat("s", "turn one", max_tokens=24, ignore_eos=True))
            await _until_decoding(eng1, "s")
            docs = [asyncio.ensure_future(eng1.generate(LONG * 3, max_tokens=4, ignore_eos=True)) for _ in range(2)]
            a = await one
            await asyncio.gather(*docs)
            rode = eng1.mixed_decode_lanes
            assert rode > 0  # turn one's lane was a rider: the only lane decoding beside the chunks
            blob = await eng1.snapshot_session("s")
            two = asyncio.ensure_future(eng1.chat("s", "turn two", max_tokens=24, ignore_eos=True))
            await _until_decoding(eng1, "s")
        finally:
            eng1.shutdown()  # the kill, mid-decode of turn two
        with pytest.raises(Exception):
            await two
        eng2 = LLMEngine.create(model, options=dict(OPTS))
        try:
            assert await eng2.restore_session("s", blob) is True
            b = await eng2.chat("s", "turn two", max_tokens=24, ignore_eos=True)  # the replayed request
            return a["tokens"], b["tokens"]
        finally:
            eng2.shutdown()

    assert asyncio.run(interrupted()) == asyncio.run(uninterrupted())
