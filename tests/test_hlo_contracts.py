"""HLO contracts (analysis/hlo_contracts.py): donation aliasing and the
recompile budget, checked against the REAL tiny engine on CPU.

Two invariants that only exist in compiler output:

- ``donate_argnums`` is a permission, not a guarantee — XLA silently
  copies when it can't alias, doubling KV HBM. The contract reads the
  compiled module's ``input_output_alias`` table.
- warmup's promise is that a steady mixed workload (decode ladder x
  verify buckets x paged dispatch) compiles NOTHING new; a stray
  non-bucketed dimension reaching a jit signature breaks that silently.
  ``recompile_budget`` counts compiled variants across the engine's
  compile-key families before/after a scripted workload.

The never-all-gather contracts are covered where they always were —
tests/test_sp_decode_hlo.py / test_spec_verify_hlo.py / test_paged_hlo.py
now consume the same module instead of three copies of the scan.
"""

import asyncio
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from agentainer_tpu.analysis.hlo_contracts import (
    ArenaRidesInCarry,
    ContractViolation,
    DonationAliased,
    ExpertsSeeOnlyTheirRows,
    HasCrossReduction,
    MixedStepOverStacks,
    NoLargeAllGather,
    check,
    compile_count,
    donated_params,
    engine_jit_fns,
    op_result_elems,
    recompile_budget,
)
from agentainer_tpu.engine.llm import PREFILL_BUCKETS, LLMEngine
from agentainer_tpu.utils.compile_cache import enable_compile_cache


@pytest.fixture(scope="module")
def engine():
    """One shared paged+speculative+fused tiny engine: the configuration
    whose compile-key space is the largest (block tables, verify ladder,
    CoW, fused decode-loop rungs)."""
    eng = LLMEngine.create(
        "tiny",
        options={
            "max_batch": 4,
            "max_seq": 256,
            "decode_chunk": 8,
            "prefill_chunk": 32,
            "paged_kv": True,
            "speculative": True,
            "fused_decode": True,
        },
    )
    yield eng
    eng.shutdown()


def _gen(engine, prompt, n=6, session=""):
    async def go():
        return await engine.generate(prompt, max_tokens=n, session=session)

    return asyncio.run(go())


# ---------------------------------------------------------------------------
# unit-level: the text scanners


def test_op_result_elems_parses_shapes():
    assert op_result_elems("  %ag = f32[2,64,2,16]{3,2,1,0} all-gather(...)") == 2 * 64 * 2 * 16
    assert op_result_elems("  %t = pred[] compare(...)") == 0
    assert op_result_elems("no shape here") == 0


def test_no_large_all_gather_flags_only_big_ops():
    hlo = "\n".join(
        [
            "%small = f32[8]{0} all-gather(%x)",
            "%big = f32[2,64,2,16]{3,2,1,0} all-gather(%y)",
        ]
    )
    assert NoLargeAllGather(min_elems=4096).failures(hlo)
    assert not NoLargeAllGather(min_elems=10_000).failures(hlo)
    with pytest.raises(ContractViolation):
        check(hlo, NoLargeAllGather(min_elems=4096))


def test_has_cross_reduction_contract():
    assert HasCrossReduction().failures("%x = f32[4]{0} add(%a, %b)")
    assert not HasCrossReduction().failures("%r = f32[4]{0} all-reduce(%a)")


# ---------------------------------------------------------------------------
# donation aliasing


def test_donated_buffer_aliases_in_simple_jit():
    f = jax.jit(lambda c: c * 2.0, donate_argnums=(0,))
    hlo = f.lower(jnp.ones((64, 64), jnp.float32)).compile().as_text()
    assert donated_params(hlo), "same-shape donation should alias"
    check(hlo, DonationAliased(min_count=1))


def test_donation_contract_catches_silent_copy():
    """dtype-narrowing donation CANNOT alias (4-byte f32 rows into 2-byte
    bf16 rows) — XLA copies silently; the contract must fail loudly."""
    f = jax.jit(lambda c: c.astype(jnp.bfloat16), donate_argnums=(0,))
    hlo = f.lower(jnp.ones((64, 64), jnp.float32)).compile().as_text()
    assert not donated_params(hlo)
    with pytest.raises(ContractViolation, match="donated"):
        check(hlo, DonationAliased(min_count=1))


def test_engine_prefill_donation_actually_aliases(engine):
    """The serving prefill donates the KV cache (donate_argnums=(1,)):
    both pool leaves (k and v) must alias outputs in the compiled module,
    or every prefill pays a full arena copy in HBM."""
    b = 8  # smallest prefill bucket
    tokens = jnp.zeros((1, b), jnp.int32)
    pos = jnp.zeros((1, b), jnp.int32)
    hlo = (
        engine._prefill.lower(
            engine.params,
            engine.cache,
            jnp.asarray(engine._bt[0:1]),
            tokens,
            pos,
            jnp.int32(4),
        )
        .compile()
        .as_text()
    )
    check(hlo, DonationAliased(min_count=2))


def test_fused_loop_donation_survives_while_carry(engine):
    """The fused decode loop donates (cache, tok, pos, sampler params,
    spec history) THROUGH the while_loop carry — including the in-loop
    speculation cond branch: both KV pool leaves must alias compiled
    outputs, or every fused dispatch pays a full arena copy — silently
    erasing the loop's entire HBM win."""
    B = engine.max_batch
    live = jnp.zeros((B,), jnp.bool_)
    budgets = jnp.zeros((B,), jnp.int32)
    ign = jnp.zeros((B,), jnp.bool_)
    armed = jnp.zeros((B,), jnp.bool_)
    keys = jax.random.split(jax.random.PRNGKey(0), engine._fused_cap)
    hlo = (
        engine._fused_fn()
        .lower(
            engine.params,
            engine.cache,
            jnp.asarray(engine._bt),
            engine._dtok,
            engine._dpos,
            engine._dtemps,
            engine._dtopk,
            engine._dtopp,
            engine._dhist,
            engine._dhlen,
            engine._stok,
            engine._spos,
            engine._stemps,
            engine._stopk,
            engine._stopp,
            engine._shist,
            engine._shlen,
            armed,
            live,
            budgets,
            ign,
            keys,
            jnp.int32(8),
        )
        .compile()
        .as_text()
    )
    check(hlo, DonationAliased(min_count=2))


# ---------------------------------------------------------------------------
# the arena stays where it lies: carried through the loops, only the new
# rows written, the donated buffer aliased (ISSUE 27)


@pytest.fixture(scope="module")
def dense_engine():
    eng = LLMEngine.create(
        "tiny",
        options={
            "max_batch": 4, "max_seq": 256, "decode_chunk": 8,
            "prefill_chunk": 32, "skip_warmup": True,
        },
    )
    yield eng
    eng.shutdown()


def _step_lowering(eng, step: str):
    """(lowered program, new-row count T, loops that carry the arena)."""
    B = eng.max_batch
    z = lambda dt: jnp.zeros((B,), dt)  # noqa: E731
    lanes = (z(jnp.int32), z(jnp.int32), z(jnp.float32), z(jnp.int32), z(jnp.float32))
    if step == "jit_decode_n":
        keys = jax.random.split(jax.random.PRNGKey(0), 8)
        return eng._decode_n.lower(eng.params, eng.cache, *lanes, keys), (B, 1), 2
    if step == "jit_verify":
        K = 4
        drafts = jnp.zeros((B, K), jnp.int32)
        lowered = eng._verify_fn(K).lower(
            eng.params, eng.cache, *lanes, drafts, z(jnp.int32), jax.random.PRNGKey(0)
        )
        return lowered, (B, K + 1), 1
    t = 32
    tokens = jnp.zeros((1, t), jnp.int32)
    if step == "jit_prefill_with_decode":
        return _mixed_lowering(eng, t), ((1, t), (B, 1)), 1
    lowered = eng._prefill.lower(
        eng.params, eng.cache, jnp.int32(1), tokens, tokens, jnp.int32(4)
    )
    return lowered, (1, t), 1


def _mixed_lowering(eng, t: int):
    """The mixed step (ISSUE 31) for a chunk of bucket ``t``: the chunk at
    arena row 1 and one decode step of every lane, in one program."""
    return eng._prefill_with_decode.lower(*_mixed_args(eng, t))


def _mixed_args(eng, t: int):
    B = eng.max_batch
    z = lambda dt: jnp.zeros((B,), dt)  # noqa: E731
    tokens = jnp.zeros((1, t), jnp.int32)
    return (
        eng.params, eng.cache, jnp.int32(1), tokens, tokens, jnp.int32(4),
        z(jnp.int32), z(jnp.int32), z(jnp.float32), z(jnp.int32), z(jnp.float32),
        jax.random.split(jax.random.PRNGKey(0), 1),
    )


@pytest.mark.parametrize("step", ["jit_decode_n", "jit_verify", "jit_prefill", "jit_prefill_with_decode"])
def test_arena_rides_in_the_layer_loop_carry(dense_engine, step):
    """The stacked arena is a carried value of the layer loop (and of the
    step scan around it), never a stacked scan output; a step writes B × T
    new rows into it and nothing the size of a layer; and the donated arena
    aliases the output through both loops, so no second arena exists. The
    mixed step writes two groups of rows (the chunk's ``[1, T]`` and the
    lanes' ``[B, 1]``) inside exactly ONE loop over the layers (the weights
    are read once for both), and its donated carry (token, position) aliases
    beside the arena."""
    lowered, rows, loops = _step_lowering(dense_engine, step)
    assert f"module @{step}" in lowered.as_text()  # the names the readers find
    arena = tuple(dense_engine.cache.k.shape)
    mixed = step == "jit_prefill_with_decode"
    groups = tuple(g + arena[3:] for g in rows) if mixed else rows + arena[3:]
    check(
        lowered.as_text(),
        ArenaRidesInCarry(arena=arena, rows=groups, loops=loops),
    )
    check(lowered.compile().as_text(), DonationAliased(min_count=4 if mixed else 2))


def test_arena_contract_counts_the_loops_and_the_groups_of_a_mixed_step(dense_engine):
    """What the mixed step's contract refuses: a chunk and a decode step as
    two forwards in one program (two loops over the layers: the weights are
    read twice), and a write that is neither group's rows."""
    from agentainer_tpu.models.llama import forward

    eng, B, t = dense_engine, dense_engine.max_batch, 32
    arena = tuple(eng.cache.k.shape)
    groups = ((1, t) + arena[3:], (B, 1) + arena[3:])

    def two_forwards(params, cache, slot, tokens, positions, lane_tok, lane_pos):
        _, cache = forward(params, eng.cfg, tokens, positions, cache, slot=slot)
        return forward(params, eng.cfg, lane_tok[:, None], lane_pos[:, None], cache)

    tokens = jnp.zeros((1, t), jnp.int32)
    z = jnp.zeros((B,), jnp.int32)
    text = jax.jit(two_forwards).lower(eng.params, eng.cache, jnp.int32(1), tokens, tokens, z, z).as_text()
    contract = ArenaRidesInCarry(arena=arena, rows=groups)
    assert any("reads the weights again" in p for p in contract.failures(text))
    mixed = _mixed_lowering(eng, t).as_text()
    one_group = ArenaRidesInCarry(arena=arena, rows=(1, t) + arena[3:])
    assert any("every write must be" in p for p in one_group.failures(mixed))


def test_arena_contract_catches_the_xs_ys_scan():
    """The form ISSUE 27 deleted: the stacks fed to ``lax.scan`` as ``xs``
    and taken back as ``ys``. It computes the same cache, donation still
    aliases — and every layer-step slices a whole layer out and writes a
    whole layer back. The contract has to refuse it."""
    L, B, S, KV, HD = 2, 4, 64, 2, 16

    def old_form(ck, cv, rows, positions):
        def layer(x, kv):
            k, v = kv
            b = jnp.arange(B)[:, None]
            return x, (k.at[b, positions].set(rows), v.at[b, positions].set(rows))

        _, (ck, cv) = jax.lax.scan(layer, 0.0, (ck, cv))
        return ck, cv

    def new_form(ck, cv, rows, positions):
        def layer(carry, l):
            k, v = carry
            b = jnp.arange(B)[:, None]
            return (k.at[l, b, positions].set(rows), v.at[l, b, positions].set(rows)), None

        (ck, cv), _ = jax.lax.scan(layer, (ck, cv), jnp.arange(L))
        return ck, cv

    stack = jnp.zeros((L, B, S, KV, HD), jnp.float32)
    args = (stack, stack, jnp.ones((B, 1, KV, HD)), jnp.zeros((B, 1), jnp.int32))
    contract = ArenaRidesInCarry(arena=stack.shape, rows=(B, 1, KV, HD))
    old = jax.jit(old_form, donate_argnums=(0, 1)).lower(*args)
    check(old.compile().as_text(), DonationAliased(min_count=2))  # donation is blind to it
    problems = contract.failures(old.as_text())
    assert any("a layer is" in p for p in problems), problems
    assert any("stacked scan output" in p for p in problems), problems
    with pytest.raises(ContractViolation, match="new rows"):
        check(old.as_text(), contract)
    check(jax.jit(new_form, donate_argnums=(0, 1)).lower(*args).as_text(), contract)


# ---------------------------------------------------------------------------
# the MoE FFN splits at the chip's ridge (ISSUE 29): over it a prefill chunk
# computes only the routed pairs, under it every step program is the parent's


MOE_OPTIONS = {
    "max_batch": 4, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 256, "skip_warmup": True,
}
PARENT_PROGRAMS = json.load(
    open(os.path.join(os.path.dirname(__file__), "data", "step_programs_parent_pr28.json"))
)


@pytest.fixture(scope="module")
def moe_engine():
    """``moe_engine(model, weights)``: one engine per pair for the module,
    built on first use. ``float`` is this CPU's float32 experts (cut 482
    rows), ``int8`` the served kind (cut 121)."""
    built = {}

    def get(model: str, weights: str):
        if (model, weights) not in built:
            quant = {"quant": "int8"} if weights == "int8" else {}
            built[model, weights] = LLMEngine.create(model, options={**MOE_OPTIONS, **quant})
        return built[model, weights]

    yield get
    for eng in built.values():
        eng.shutdown()


def _prefill_args(eng, t: int):
    tokens = jnp.zeros((1, t), jnp.int32)
    return eng.params, eng.cache, jnp.int32(1), tokens, tokens, jnp.int32(4)


def _moe_step_lowering(eng, program: str):
    if program.startswith("jit_prefill."):
        return eng._prefill.lower(*_prefill_args(eng, int(program.split(".")[1])))
    return _step_lowering(eng, program)[0]


@pytest.mark.parametrize("key", sorted(PARENT_PROGRAMS))
def test_steps_under_the_cut_lower_to_the_parents_programs(moe_engine, key):
    """``jit_decode_n``, ``jit_verify`` and every ``jit_prefill`` bucket under
    the cut lower to the StableHLO the parent commit (4711f7f, PR 28) lowered
    on this backend, byte for byte: sha256 of the location-free text, taken
    there with this file's own lowering calls. A later PR that changes a
    step program on purpose regenerates the file and says why: PR 33 did for
    the four ``jit_decode_n`` entries, whose one-token rows now see a lane at
    the arena's last row at row 0 (``models/llama._seen``: one select a layer
    on the positions handed to the attention; the scatter is the parent's);
    ``jit_verify`` and every ``jit_prefill`` bucket are still PR 28's."""
    model, weights, program = key.split(".", 2)
    text = _moe_step_lowering(moe_engine(model, weights), program).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_PROGRAMS[key]


@pytest.mark.parametrize("model", ["tiny-moe", "tiny-olmoe"])
def test_prefill_over_the_cut_computes_only_the_routed_pairs(moe_engine, model):
    """Bucket 256 of an int8 MoE engine: no ``[256, E, F]`` activation, a
    grouped matmul in the traced program instead; bucket 64 of the same
    engine still holds the all-experts form, so the contract can tell the
    two apart."""
    eng = moe_engine(model, "int8")
    contract = lambda t: ExpertsSeeOnlyTheirRows(t, eng.cfg.n_experts, eng.cfg.ffn_dim)  # noqa: E731
    over = _moe_step_lowering(eng, "jit_prefill.256").as_text()
    assert "module @jit_prefill" in over
    check(over, contract(256))
    assert "ragged_dot" in str(eng._prefill.trace(*_prefill_args(eng, 256)).jaxpr)
    under = _moe_step_lowering(eng, "jit_prefill.64").as_text()
    with pytest.raises(ContractViolation, match="every row goes through every expert"):
        check(under, contract(64))
    assert "ragged_dot" not in str(eng._prefill.trace(*_prefill_args(eng, 64)).jaxpr)


@pytest.mark.parametrize("model", ["tiny-moe", "tiny-olmoe"])
def test_a_mixed_step_over_the_cut_sorts_the_lanes_rows_with_the_chunks(moe_engine, model):
    """256 + 4 rows of an int8 MoE engine: the launch's FFN is the sorted
    grouped one for both groups (no ``[260, E, F]`` activation and none of
    the lanes' ``[4, E, F]`` either: one grouped matmul), the experts stay out
    of the layer loop's slices, and the arena rides in its one loop; 64 + 4
    rows stay under the cut and keep the all-experts form."""
    eng = moe_engine(model, "int8")
    B, arena = eng.max_batch, tuple(eng.cache.k.shape)
    over = _mixed_lowering(eng, 256).as_text()
    assert "module @jit_prefill_with_decode" in over
    for rows in (256 + B, 256, B):
        check(over, ExpertsSeeOnlyTheirRows(rows, eng.cfg.n_experts, eng.cfg.ffn_dim))
    check(over, ArenaRidesInCarry(
        arena=arena, rows=((1, 256) + arena[3:], (B, 1) + arena[3:]),
    ))
    grouped = lambda fn, args: str(fn.trace(*args).jaxpr).count("ragged_dot")  # noqa: E731
    # gate, up, down: ONE grouped FFN for the T + B rows, as many as the plain chunk's
    assert grouped(eng._prefill_with_decode, _mixed_args(eng, 256)) == grouped(eng._prefill, _prefill_args(eng, 256)) > 0
    under = _mixed_lowering(eng, 64).as_text()
    with pytest.raises(ContractViolation, match="every row goes through every expert"):
        check(under, ExpertsSeeOnlyTheirRows(64 + B, eng.cfg.n_experts, eng.cfg.ffn_dim))


# ---------------------------------------------------------------------------
# the hybrid block's mixed step, without a linear mixer (ISSUE 41) and with
# one (ISSUE 48), and every program without lanes that is what the parent lowered


HYBRID_OPTIONS = {
    "max_batch": 4, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 128, "skip_warmup": True,
}
HYBRID_PARENT_PROGRAMS = json.load(
    open(os.path.join(os.path.dirname(__file__), "data", "hybrid_step_programs_parent_pr40.json"))
)
# the two-kind engines' programs since PR 51 (the parent's and a barrier in each kind's loop)
HYBRID_MOVED_PROGRAMS = json.load(
    open(os.path.join(os.path.dirname(__file__), "data", "step_programs_pr51.json"))
)["without_lanes"]


@pytest.fixture(scope="module")
def hybrid_engine():
    """``hybrid_engine(model, weights)``, built on first use, as ``moe_engine``."""
    built = {}

    def get(model: str, weights: str):
        if (model, weights) not in built:
            quant = {"quant": "int8"} if weights == "int8" else {}
            built[model, weights] = LLMEngine.create(model, options={**HYBRID_OPTIONS, **quant})
        return built[model, weights]

    yield get
    for eng in built.values():
        eng.shutdown()


@pytest.mark.parametrize("key", sorted(HYBRID_PARENT_PROGRAMS))
def test_hybrid_steps_without_lanes_lower_to_the_parents_programs(hybrid_engine, key):
    """``jit_decode_n`` and two buckets of ``jit_prefill`` of the three hybrid
    engines (KDA + MLA, GDN + full attention, MLA alone), float and int8,
    lower to the StableHLO the parent commit (e076b59, PR 40) lowered on this
    backend, byte for byte (sha256 of the text, taken there with these
    lowering calls): ``hybrid.forward`` took ``lanes`` (PR 41) and the linear
    mixers a second group of rows (PR 48: ``_short_conv`` keeps a call
    without them in the order it had, the window put back after the state's
    update), and a call without them traces what it traced. On the chip an
    edit above a Pallas call still re-compiles every program that holds one
    (PERF.md section 7): that is a first start's cost, not this one's.

    Since PR 51 the two engines with TWO kinds of mixer (12 of the 18) compare
    with ``tests/data/step_programs_pr51.json``, taken on that PR's tree: each
    kind's 0-or-1-trip loop gained an ``optimization_barrier`` (``models/hybrid.py``
    ``of_kind``), and nothing else, as the next test holds. ``tiny-mistral4``
    (one kind, no such loop) is still PR 40's."""
    model, weights, program = key.split(".", 2)
    text = _moe_step_lowering(hybrid_engine(model, weights), program).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == {**HYBRID_PARENT_PROGRAMS, **HYBRID_MOVED_PROGRAMS}[key]


@pytest.mark.parametrize("key", sorted(HYBRID_MOVED_PROGRAMS))
def test_a_two_kind_step_less_its_barriers_lowers_to_the_parents_program(hybrid_engine, barrier_is_identity, key):
    """What PR 51 did to a two-kind step program is the barriers and nothing
    besides: traced with ``lax.optimization_barrier`` as the identity (which
    is what it computes), each of the 12 moved programs is PR 40's text byte
    for byte. So every value is the parent's; where the work runs is the
    compiler's, and ``tests/test_tpu_compile.py`` reads that."""
    model, weights, program = key.split(".", 2)
    text = _moe_step_lowering(hybrid_engine(model, weights), program).as_text()
    assert "optimization_barrier" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == HYBRID_PARENT_PROGRAMS[key]


@pytest.mark.parametrize("weights", ["float", "int8"])
def test_hybrid_mixed_step_rides_one_layer_loop_and_heads_few_rows(hybrid_engine, weights):
    """``tiny-mistral4``'s ``jit_prefill_with_decode``: the latent stack in
    the one layer loop's carry, two writes into it (the chunk's ``T`` rows and
    the lanes' ``B``), no logits of ``T`` or ``T + B`` rows, and the donated
    cache (latent, ``stop``) and carry (token, position) aliased. ``int8``:
    128 + 4 rows are over the 121-row cut, so ONE grouped FFN takes both
    groups' rows, as many grouped matmuls as the plain chunk's; the held
    experts never appear as an all-experts activation of the lanes' rows."""
    eng = hybrid_engine("tiny-mistral4", weights)
    B, t, c = eng.max_batch, 128, eng.cache
    assert eng._prefill_with_decode is not None and c.state is None and c.conv is None
    lowered = _mixed_lowering(eng, t)
    text = lowered.as_text()
    assert "module @jit_prefill_with_decode" in text
    check(text, MixedStepOverStacks({"latent": c.latent.shape}, chunk=t, lanes=B, vocab=eng.cfg.vocab_size))
    check(lowered.compile().as_text(), DonationAliased(min_count=4))
    if weights == "int8":
        for rows in (t + B, t, B):
            check(text, ExpertsSeeOnlyTheirRows(rows, eng.cfg.n_experts, eng.cfg.ffn_dim))
        grouped = lambda fn, args: str(fn.trace(*args).jaxpr).count("ragged_dot")  # noqa: E731
        assert grouped(eng._prefill_with_decode, _mixed_args(eng, t)) == grouped(eng._prefill, _prefill_args(eng, t)) > 0


def test_hybrid_mixed_contract_refuses_two_forwards_and_a_head_on_every_row(hybrid_engine):
    """What ``MixedStepOverStacks`` is for: a chunk's forward and a step's in
    one program carry the stack through two loops over the layers (the weights
    read twice) and head all ``T`` rows; the plain chunk's program writes one
    group."""
    eng = hybrid_engine("tiny-mistral4", "float")
    B, t, shape = eng.max_batch, 128, eng.cache.latent.shape
    contract = MixedStepOverStacks({"latent": shape}, chunk=t, lanes=B, vocab=eng.cfg.vocab_size)
    run = eng._run_forward  # ``forward`` closed over the engine's plan

    def two_forwards(params, cache, slot, tokens, positions, lane_tok, lane_pos):
        logits, cache = run(params, tokens, positions, cache, slot=slot)
        return logits, run(params, lane_tok[:, None], lane_pos[:, None], cache)

    tokens, z = jnp.zeros((1, t), jnp.int32), jnp.zeros((B,), jnp.int32)
    text = jax.jit(two_forwards).lower(eng.params, eng.cache, jnp.int32(1), tokens, tokens, z, z).as_text()
    problems = contract.failures(text)
    assert any("reads the weights again" in p for p in problems), problems
    assert any("rows nobody reads" in p for p in problems), problems
    chunk_alone = _moe_step_lowering(eng, f"jit_prefill.{t}").as_text()
    assert any("want the chunk's rows and the lanes'" in p for p in contract.failures(chunk_alone))


@pytest.mark.parametrize("model", ["tiny-kimi-linear", "tiny-olmo-hybrid"])
def test_hybrid_mixed_step_with_a_linear_mixer_steps_each_stack_a_group_at_a_time(hybrid_engine, model):
    """``jit_prefill_with_decode`` where a KDA or a GDN layer keeps a per-lane
    state (ISSUE 48): every stack (latent or K and V, state, conv) rides in
    the layer loop's carry and in the 0-or-1-trip loop round its own mixer and
    in no third loop (the weights are read once), a positional stack takes the
    chunk's rows and the lanes', the state the chunk's ONE lane and the
    step's B, the conv the B windows in one write (the chunk's new window
    among them), the head runs on ``1 + B`` rows, and the donated cache
    and carry alias the outputs. The engine's gate reads nothing of the
    mixer kinds: these engines are built as ``tiny-mistral4``'s is."""
    eng = hybrid_engine(model, "float")
    B, t, c = eng.max_batch, 128, eng.cache
    assert eng.cfg.linear_kind is not None and c.state is not None
    assert "_prefill_with_decode" in engine_jit_fns(eng)
    lowered = _mixed_lowering(eng, t)
    text = lowered.as_text()
    assert "module @jit_prefill_with_decode" in text
    rows = {n: a.shape for n, a in c.leaves().items() if n in c.POSITIONAL}
    contract = MixedStepOverStacks(
        rows, chunk=t, lanes=B, vocab=eng.cfg.vocab_size,
        per_lane={"state": c.state.shape, "conv": c.conv.shape}, loops=2, joined=("conv",),
    )
    check(text, contract)
    check(lowered.compile().as_text(), DonationAliased(min_count=4 + len(c.leaves())))
    chunk_alone = _moe_step_lowering(eng, f"jit_prefill.{t}").as_text()
    assert any("want the chunk's lane and the step's lanes" in p for p in contract.failures(chunk_alone))


@pytest.mark.parametrize("weights", ["float", "int8"])
def test_hybrid_mixed_step_over_two_kinds_of_attention_carries_four_leaves(hybrid_engine, weights):
    """``tiny-laguna``'s ``jit_prefill_with_decode`` (ISSUE 50: full attention
    beside sliding-window attention, no linear mixer): ``k`` and ``v`` and the
    ring's ``wk`` and ``wv`` ride in the layer loop's carry and in the
    0-or-1-trip loop round their own kind and in no third loop (the weights,
    the gate's and both kinds' ``wq`` / ``wo`` stacks among them, are read
    once); each leaf takes the chunk's rows and the lanes' (the ring's at ``p
    mod R``, a parked lane's nowhere), the head runs on ``1 + B`` rows, and the
    donated cache and carry alias the outputs. ``int8``: 128 + 4 rows are over
    the 121-row cut, so ONE grouped FFN takes both groups' rows."""
    eng = hybrid_engine("tiny-laguna", weights)
    B, t, c = eng.max_batch, 128, eng.cache
    assert eng._prefill_with_decode is not None and c.state is None and c.latent is None
    assert c.wk.shape[2] == eng.cfg.window + HYBRID_OPTIONS["prefill_chunk"] < c.k.shape[2]
    lowered = _mixed_lowering(eng, t)
    text = lowered.as_text()
    assert "module @jit_prefill_with_decode" in text
    stacks = {n: a.shape for n, a in c.leaves().items()}
    assert sorted(stacks) == ["k", "v", "wk", "wv"]
    contract = MixedStepOverStacks(stacks, chunk=t, lanes=B, vocab=eng.cfg.vocab_size, loops=2)
    check(text, contract)
    check(lowered.compile().as_text(), DonationAliased(min_count=4 + len(stacks)))
    chunk_alone = _moe_step_lowering(eng, f"jit_prefill.{t}").as_text()
    assert contract.failures(chunk_alone)  # the contract tells the mixed step from a chunk alone
    if weights == "int8":
        grouped = lambda fn, args: str(fn.trace(*args).jaxpr).count("ragged_dot")  # noqa: E731
        assert grouped(eng._prefill_with_decode, _mixed_args(eng, t)) == grouped(eng._prefill, _prefill_args(eng, t)) > 0


# ---------------------------------------------------------------------------
# recompile budget over the scripted mixed workload


JSON_LOOP = '{"tool": "search", "args": {"q": "w", "n": 5}}\n' * 4
PERSONA = "You are a terse assistant. Answer in one word. " * 4


def test_recompile_budget_mixed_workload(engine):
    """decode ladder x verify buckets x paged dispatch, zero new compiles.

    Warmup compiled every reachable signature; this scripted workload
    re-exercises them all through the public API. Any positive delta in
    the engine's compile caches is a shape-key regression.
    """
    # settle any lazily-keyed fns the fixture's first use could create
    _gen(engine, "hello", n=2)

    families = lambda: engine_jit_fns(engine)  # noqa: E731
    with recompile_budget(families, budget=0):
        # prefill buckets: prompts landing in buckets 8/16/32
        for words in (2, 9, 20):
            _gen(engine, "tok " * words, n=2)
        # decode ladder rungs: max_tokens = c+1 picks rung c
        for c in (1, 2, 4, 8):
            _gen(engine, "ladder probe", n=c + 1)
        # verify buckets: repetitive JSON drives prompt-lookup speculation
        _gen(engine, JSON_LOOP, n=24)
        # paged prefix sharing + CoW tail: two sessions, same persona
        _gen(engine, PERSONA + "What is two plus two?", n=4, session="hc-a")
        _gen(engine, PERSONA + "Name a color.", n=4, session="hc-b")
        # multi-turn on a resident paged session (block-table growth path)
        _gen(engine, "and another thing", n=4, session="hc-a")

        # lane injection armed against a RUNNING fused loop: the staging
        # merge is an operand (armed mask) of the same fused executable,
        # and the fallback path reuses the jitted inject — zero compiles
        # either way the race resolves
        async def _staggered():
            t1 = asyncio.ensure_future(
                engine.generate(JSON_LOOP, max_tokens=24)
            )
            await asyncio.sleep(0.05)
            t2 = asyncio.ensure_future(
                engine.generate("late lane", max_tokens=6)
            )
            return await asyncio.gather(t1, t2)

        asyncio.run(_staggered())

    # sanity: the families we budget over actually exist on this engine
    counts = compile_count(engine_jit_fns(engine))
    assert any(k.startswith("_verify_fns") for k in counts), counts
    assert "_prefill" in counts and "_decode_n" in counts


# ---------------------------------------------------------------------------
# nothing is traced, lowered or compiled while a warmed engine serves


@pytest.fixture(scope="module")
def compile_stats():
    """One more listener pair on this process: JAX's own compile events and
    durations, whichever function or eager primitive they come from."""
    return enable_compile_cache()


@pytest.fixture(scope="module", params=["dense", "paged", "fused", "meshed", "latent", "recurrent"])
def warmed(request):
    """``latent``: the hybrid block with no linear mixer (``tiny-mistral4``),
    whose warm-up compiles the mixed step's rungs like the dense engine's;
    ``recurrent``: with one (``tiny-olmo-hybrid``: a state and a conv window a
    lane, ISSUE 48), likewise."""
    extra = {
        "dense": {},
        "paged": {"paged_kv": True},
        "fused": {"paged_kv": True, "fused_decode": True},
        "meshed": {"tp": 2},
        "latent": {},
        "recurrent": {},
    }[request.param]
    eng = LLMEngine.create(
        {"latent": "tiny-mistral4", "recurrent": "tiny-olmo-hybrid"}.get(request.param, "tiny"),
        options={"max_batch": 4, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 32, **extra},
    )
    yield eng
    eng.shutdown()


def test_serving_window_lowers_nothing(warmed, compile_stats):
    """The recompile budget above counts the engine's NAMED jitted
    functions, so an eager control-flow call on the worker (the first-token
    sampler's ``lax.cond``, re-lowered on every request until ISSUE 24)
    passed it for as long as it existed: an eager primitive has no family
    to count. This check is on any lowering at all in the serving window."""

    async def serve(**kw):
        return await warmed.generate(max_tokens=4, ignore_eos=True, **kw)

    before = compile_stats.as_dict()
    for kw in (
        {"prompt": "greedy first"},
        {"prompt": "warm draw", "temperature": 0.8},
        {"prompt": "top-k draw", "temperature": 0.8, "top_k": 5},
        {"prompt": "top-p draw", "temperature": 0.8, "top_p": 0.9},
        {"prompt": "both filters", "temperature": 1.2, "top_k": 40, "top_p": 0.95},
        {"prompt": "first turn of a session", "session": "lw"},
        {"prompt": "and its second turn", "session": "lw", "temperature": 0.7},
        {"prompt": "longer than one prefill chunk " * 3},
    ):
        assert len(asyncio.run(serve(**kw))["tokens"]) == 4
    after = compile_stats.as_dict()
    grew = {
        k: (before[k], after[k])
        for k in ("requests", "misses", "trace_s", "lower_s", "compile_s")
        if after[k] != before[k]
    }
    assert not grew, grew
    assert "_first_token" in engine_jit_fns(warmed)
    assert warmed._first_token._cache_size() == 1


def test_serving_window_with_mixed_launches_lowers_nothing(warmed, compile_stats):
    """Warm-up serves one synthetic request at a time and so never has a
    chunk pending beside a decoding lane: it runs the mixed step
    (``jit_prefill_with_decode``, ISSUE 31) explicitly, once per bucket a
    chunk can take. Multi-chunk prompts beside a steady generation then ride
    on the dense engine and on the hybrid block's, with a per-lane state
    (ISSUE 48) or without one (ISSUE 41), without a lowering; the page pool, the fused loop and
    the mesh have no such program, launch none, and lower nothing either."""

    async def contended():
        steady = asyncio.ensure_future(warmed.generate("steady reply", max_tokens=40, ignore_eos=True))
        await asyncio.sleep(0.05)
        docs = [
            warmed.generate("several chunks of prompt to read " * n, max_tokens=6, ignore_eos=True)
            for n in (2, 3, 4)
        ]
        return await asyncio.gather(steady, *docs)

    rides = warmed._prefill_with_decode is not None
    assert rides is not (warmed.paged or warmed.fused_decode or warmed.mesh is not None)
    buckets = warmed._mixed_buckets  # one program each: the two largest prefill buckets a chunk can take
    assert (list(buckets) == [b for b in PREFILL_BUCKETS if b <= warmed.prefill_chunk][-2:]) is rides
    if rides:
        assert warmed._prefill_with_decode._cache_size() == len(buckets) >= 1
    before, launched = compile_stats.as_dict(), warmed.mixed_launches
    assert [len(r["tokens"]) for r in asyncio.run(contended())] == [40, 6, 6, 6]
    after = compile_stats.as_dict()
    grew = {
        k: (before[k], after[k])
        for k in ("requests", "misses", "trace_s", "lower_s", "compile_s")
        if after[k] != before[k]
    }
    assert not grew, grew
    assert (warmed.mixed_launches > launched) is rides
    if rides:
        assert "_prefill_with_decode" in engine_jit_fns(warmed)
        assert warmed._prefill_with_decode._cache_size() == len(buckets)
