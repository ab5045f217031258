"""The hybrid block's two-kind dispatch changes WHERE a kind's work runs,
not one number (ISSUE 51): a small configuration of each two-kind family
(full + window, KDA + MLA, gated delta rule + full), seeded float32 weights and
the same weights as served (int8), three contexts prefilled, then a decode
step, a prefill chunk and the mixed step, each through ``jax.jit`` as the
engine runs them.

``tests/data/hybrid_values_parent_pr50.json`` holds what the parent commit
(c905795, PR 50) computed on this CPU backend: sha256 of the logits' and of
every cache leaf's bytes, written by :func:`record` BEFORE ``mixer`` was
touched. What PR 51 added is ``lax.optimization_barrier`` in each kind's loop,
which computes the identity, so:

- traced with the barrier AS the identity, every step reproduces the parent's
  values bit for bit (the program is the parent's: ``tests/test_hlo_contracts.py``
  and ``tests/test_laguna.py`` hold the text). The pins are bytes that one
  compiler made for one host, so the file says under what they were taken
  (``taken_under``: jax, jaxlib, the machine and the instruction sets XLA:CPU
  compiles for) and the cases skip, with that said, on a host that differs;
- with the barrier in place, as served, the values are the same arithmetic on
  the same operands, and XLA:CPU is free to round it two ways. Both were
  traced to the instruction (PR 51, after the review): (a) inside a fusion
  LLVM contracts ``a * b + c`` to one rounding, and the barrier moves the
  fusions' boundaries (``--xla_cpu_max_isa=SSE4_2`` has no such instruction:
  Olmo-Hybrid's float32 steps, and every ``decode`` and ``mixed`` program of
  the three families given the same cache, come out bit-equal under it);
  (b) XLA:CPU marks a reduce's adds ``reassoc`` and LLVM's -O2 orders them by
  the loop it finds around them. In the prefill chunk's program, with the
  linear kind's projections now inside its loop, it sums ``_l2norm``'s squares
  of a key or query differently: Kimi-Linear (sixteen a head, float32 and
  int8) two interleaved 4-wide accumulators before, one 4-wide chain now;
  Olmo-Hybrid int8 (twelve) one after another before, three 4-wide partial
  sums now (``add_rsqrt_fusion`` / ``.1`` of ``jit_prefill`` in
  ``--xla_dump_to``'s ``ir-with-opt.ll``, both ways). An ulp there, carried
  through the layers: at most 9.0e-6 of an array's largest value as served,
  held here to 3e-5. Laguna's came out bit-equal either way;
- with both freedoms taken away (``conftest``'s ``where_llvm_may_not_reorder``:
  SSE4.2 and LLVM's -O0, a process of its own), the barrier in place and the
  barrier as the identity compute every logit and every leaf of the 18 steps
  bit for bit alike.

The families' tests against their float32 references are what says the
numbers are right; this says they did not move."""

import hashlib
import json
import os
import platform
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.engine.quant import quantize_params
from agentainer_tpu.models import hybrid
from agentainer_tpu.models.configs import get_config

PINS = os.path.join(os.path.dirname(__file__), "data", "hybrid_values_parent_pr50.json")
MODELS = ("tiny-laguna", "tiny-kimi-linear", "tiny-olmo-hybrid")
WEIGHTS = ("float", "int8")
STEPS = ("decode", "prefill", "mixed")
LANES, SEQ, T = 4, 64, 16
ISAS = ("sse4_2", "avx", "avx2", "fma", "avx512f")


def host() -> dict:
    """What decides the bytes XLA:CPU computes here: the compiler's version
    and which of the instruction sets it chooses among this host has."""
    import jaxlib

    try:
        with open("/proc/cpuinfo") as f:
            flags = set(re.search(r"^flags\s*:(.*)$", f.read(), re.M).group(1).split())
    except (OSError, AttributeError):
        flags = set()
    return {
        "jax": jax.__version__, "jaxlib": jaxlib.__version__, "machine": platform.machine(),
        "isas": sorted(flags & set(ISAS)),
    }


def _sha(a: np.ndarray) -> str:
    return hashlib.sha256(str((a.dtype, a.shape)).encode() + np.ascontiguousarray(a).tobytes()).hexdigest()


def values(model: str, weights: str) -> dict:
    """``{step: {"logits": array, leaf: array, ..., "stop": array}}`` of one
    family: lanes 0, 1 and 3 hold contexts of 12, 5 and 20 tokens; ``decode``
    steps the four lanes (lane 2 is empty and steps from zero state, lane 3
    stands at its ``stop``), ``prefill`` puts a chunk of 16 rows (11 real) at
    position 7 of lane 2, ``mixed`` does both in one call."""
    cfg = get_config(model)
    params = hybrid.init_params(cfg, jax.random.PRNGKey(7), jnp.float32)
    if weights == "int8":
        params = quantize_params(params, jnp.float32)
    ring = {"launch_rows": 24} if cfg.n_window else {}
    cache = hybrid.init_cache(cfg, LANES, SEQ, jnp.float32, live=False, **ring)
    plan = hybrid.plan_hybrid(cfg, use_pallas=False)
    rng = np.random.default_rng(11)
    draw = lambda *shape: jnp.asarray(rng.integers(1, cfg.vocab_size, shape), jnp.int32)  # noqa: E731

    @jax.jit
    def prefill(params, cache, slot, tokens, positions, n_real):
        valid = jnp.arange(tokens.shape[1])[None] < n_real
        return hybrid.forward(params, cfg, tokens, positions, cache, plan=plan, slot=slot, valid=valid)

    @jax.jit
    def decode(params, cache, tokens, positions):
        return hybrid.forward(params, cfg, tokens, positions, cache, plan=plan)

    @jax.jit
    def mixed(params, cache, slot, tokens, positions, n_real, lane_tok, lane_pos):
        valid = jnp.arange(tokens.shape[1])[None] < n_real
        return hybrid.forward(
            params, cfg, tokens, positions, cache, plan=plan, slot=slot, valid=valid,
            lanes=(lane_tok, lane_pos), last=n_real - 1,
        )

    for lane, n in {0: 12, 1: 5, 3: 20}.items():
        _, cache = prefill(params, cache, jnp.int32(lane), draw(1, 24), jnp.arange(24, dtype=jnp.int32)[None], jnp.int32(n))
    for lane, stop in enumerate((60, 60, 60, 20)):
        cache = hybrid.admit_lane(cache, lane, False, stop, -1)
    tokens, positions = draw(1, T), (7 + jnp.arange(T, dtype=jnp.int32))[None]
    lane_tok, lane_pos = draw(LANES, 1), jnp.asarray([12, 5, SEQ - 1, 20], jnp.int32)[:, None]
    slot, n_real = jnp.int32(2), jnp.int32(11)
    outs = {
        "decode": decode(params, cache, lane_tok, lane_pos),
        "prefill": prefill(params, cache, slot, tokens, positions, n_real),
        "mixed": mixed(params, cache, slot, tokens, positions, n_real, lane_tok, lane_pos),
    }
    return {
        step: {k: np.asarray(a) for k, a in {"logits": logits, "stop": after.stop, **after.leaves()}.items()}
        for step, (logits, after) in outs.items()
    }


@pytest.fixture(scope="module")
def computed():
    """``computed(model, weights, barrier)``: a family's values with the
    barrier in place, or as the identity (``values`` builds its jitted steps
    anew on every call, so neither trace is the other's)."""
    done = {}

    def get(model, weights, barrier: bool):
        if (model, weights, barrier) not in done:
            with pytest.MonkeyPatch.context() as patch:
                if not barrier:
                    patch.setattr(jax.lax, "optimization_barrier", lambda x: x)
                done[model, weights, barrier] = values(model, weights)
        return done[model, weights, barrier]

    return get


CASES = [(m, w, s) for m in MODELS for w in WEIGHTS for s in STEPS]


@pytest.mark.parametrize("model, weights, step", CASES)
def test_a_two_kind_step_less_its_barriers_computes_the_parents_values_bit_for_bit(computed, model, weights, step):
    with open(PINS) as f:
        pins = json.load(f)
    if pins["taken_under"] != host():
        pytest.skip(f"the pins are one host's bytes, taken under {pins['taken_under']}; this is {host()}")
    want = pins[f"{model}.{weights}.{step}"]
    got = {k: _sha(a) for k, a in computed(model, weights, False)[step].items()}
    assert set(got) == set(want) and len(got) >= 5  # the logits, the controls and three or four leaves
    assert {k for k in got if got[k] != want[k]} == set()


@pytest.mark.parametrize("model, weights, step", CASES)
def test_a_two_kind_step_as_served_computes_the_parents_values_to_float32_rounding(computed, model, weights, step):
    got, want = computed(model, weights, True)[step], computed(model, weights, False)[step]
    assert np.array_equal(got["stop"], want["stop"])
    for name in set(want) - {"stop"}:
        scale = np.abs(want[name]).max()
        assert scale > 0 and np.abs(got[name] - want[name]).max() <= 3e-5 * scale, name


def as_served_is_the_program_less_its_barriers(model: str):
    """Every array of the family's six steps, barrier in place against
    barrier as the identity: bit for bit (run where LLVM may not reorder)."""
    for weights in WEIGHTS:
        got = values(model, weights)
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax.lax, "optimization_barrier", lambda x: x)
            want = values(model, weights)
        moved = [(weights, step, k) for step in want for k in want[step] if not np.array_equal(got[step][k], want[step][k])]
        assert not moved and len(want) == len(STEPS), moved


@pytest.mark.parametrize("model", MODELS)
def test_a_two_kind_step_as_served_is_bit_for_bit_the_parents_where_llvm_may_not_reorder(where_llvm_may_not_reorder, model):
    where_llvm_may_not_reorder(
        f"from tests.test_hybrid_values import as_served_is_the_program_less_its_barriers as check\ncheck({model!r})\n"
    )


def record():
    """``python -c "import tests.conftest, tests.test_hybrid_values as t; t.record()"``
    (the suite's own platform and flags), on the tree whose values are to be kept."""
    pins = {
        f"{m}.{w}.{s}": {k: _sha(a) for k, a in v.items()} for m in MODELS for w in WEIGHTS for s, v in values(m, w).items()
    }
    with open(PINS, "w") as f:
        json.dump({**pins, "taken_under": host()}, f, indent=1, sort_keys=True)
    print(f"{len(pins)} entries -> {PINS}")
