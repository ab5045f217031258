"""Olmo-Hybrid's block through ``models/hybrid.py`` (a gated delta rule with
one decay a head, negative eigenvalues and a rectangular state beside full
softmax attention with QK-norm and no rotary embedding; a dense SwiGLU in
every layer; the OLMo-2 norm placement) against the benchmark's plain float32
reference (``benchmark/families/olmo_hybrid_reference.py``, which imports
nothing of the program), on the CPU with ``tiny-olmo-hybrid`` (6 heads: no
multiple of 8; keys of 12 beside values of 24) and seeded weights — and the
cache manager's moves on a slot that is K/V rows up to a position AND a
recurrent state.

Weights are sharpened as in ``test_kimi_linear.py`` (the 0.02-std init makes
every gate near ½ and attention a near-uniform average; the post-norm block
reads the residual stream itself, so ``init_params`` draws its embedding at an
RMS of one: ``hybrid.embed_rms``). Tolerance: both sides compute in float32 and differ by the
order of summation and the chunked against the token-by-token recurrence: the
rms difference over the logits' standard deviation stays under 1e-3 (it reads
3e-5); every omission has to read over 2e-2 (a reference that overflows
without a step counts as far off).
"""

import asyncio
import importlib.util
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.models import hybrid
from agentainer_tpu.models.configs import get_config
from agentainer_tpu.models.llama import forward, init_cache, init_params
from agentainer_tpu.ops import attention as attn_ops
from agentainer_tpu.ops import kda

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-3
WRONG = 2e-2
CFG = get_config("tiny-olmo-hybrid")


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "olmo_hybrid_reference", os.path.join(REPO, "benchmark", "families", "olmo_hybrid_reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()


def sharp_params(cfg, seed=3):
    """Seeded float32 weights under which the block's own steps matter."""
    p = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    scale = {"gdn": {"wqkv": 10.0, "w_a": 15.0, "w_beta": 30.0, "w_g": 20.0}, "full": {"wq": 15.0, "wk": 15.0}}
    out = dict(p)
    for group, factors in scale.items():
        out[group] = {k: v * factors.get(k, 1.0) for k, v in p[group].items()}
    uniform = lambda a, lo, hi: jax.random.uniform(next(keys), a.shape, jnp.float32, lo, hi)  # noqa: E731
    out["gdn"]["o_norm"] = uniform(p["gdn"]["o_norm"], 0.25, 4.0)
    out["full"]["q_norm"] = uniform(p["full"]["q_norm"], 0.5, 6.0)
    out["full"]["k_norm"] = uniform(p["full"]["k_norm"], 0.5, 6.0)
    out["layers"] = {k: uniform(v, 0.5, 2.0) for k, v in p["layers"].items()}
    out["lm_head"] = p["lm_head"] * 10.0
    return out


def reference_weights(params, cfg):
    """The program's per-kind stacks as the reference's list of layers (the
    merged q|k|v projection and conv filters split into the published three)."""
    ck = cfg.kda_heads * cfg.kda_head_dim
    layers, seen = [], {"gdn": 0, "full": 0}
    for i, kind in enumerate(cfg.layer_kinds):
        lp = {k: v[i] for k, v in params["layers"].items()}
        mixer = {k: v[seen[kind]] for k, v in params[kind].items()}
        seen[kind] += 1
        if kind == "gdn":
            for name, part in zip("qkv", jnp.split(mixer.pop("wqkv"), [ck, 2 * ck], axis=-1)):
                lp["w" + name] = part
            for name, part in zip("qkv", jnp.split(mixer.pop("conv"), [ck, 2 * ck], axis=-1)):
                lp["conv_" + name] = part
        lp.update(mixer)
        lp.update({k: v[i] for k, v in params["dense"].items()})
        layers.append(lp)
    return {"embed": params["embed"], "layers": layers, "final_norm": params["final_norm"], "lm_head": params["lm_head"]}


def reference_logits(params, cfg, tokens, **over):
    kw = dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, lin_heads=cfg.kda_heads, lin_key_dim=cfg.kda_head_dim,
        lin_value_dim=cfg.kda_v_dim, norm_eps=cfg.norm_eps, neg_eigval=cfg.delta_neg_eigval,
    )
    return ref.forward(reference_weights(params, cfg), tokens, **{**kw, **over})


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.median(np.sqrt(np.mean((got - want) ** 2, -1)) / np.std(want, -1)))


@pytest.fixture(scope="module")
def case():
    params = sharp_params(CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (150,), 3, CFG.vocab_size)
    return params, tokens, reference_logits(params, CFG, tokens)


def program_full(params, tokens):
    pos = jnp.arange(tokens.shape[0])[None]
    return forward(params, CFG, tokens[None], pos)[0][0]


def program_cached(params, tokens, chunks=(70, 66)):
    """Prefill in two chunks (the second in a bucket with padding rows, state
    and K/V rows carried from one launch to the next), then one-token decode
    steps through the cache; logits, not tokens, against the reference's full
    forward."""
    cache = init_cache(CFG, 1, 192, dtype=jnp.float32)
    rows, at = [], 0
    for n, bucket in zip(chunks, (70, 96)):
        toks = jnp.pad(tokens[at : at + n], (0, bucket - n))[None]
        pos = (at + jnp.arange(bucket))[None]
        logits, cache = forward(params, CFG, toks, pos, cache, valid=(jnp.arange(bucket) < n)[None])
        rows.append(logits[0, :n])
        at += n
    for i in range(at, tokens.shape[0]):
        step, cache = forward(params, CFG, tokens[None, i : i + 1], jnp.full((1, 1), i), cache)
        rows.append(step[0])
    return jnp.concatenate(rows)


@pytest.mark.parametrize("program", [program_full, program_cached], ids=["full_forward", "two_chunks_then_decode"])
def test_program_matches_the_plain_reference(case, program):
    params, tokens, want = case
    assert rel(program(params, tokens), want) < TOL


def rotary(q, k, positions, theta=10_000.0):
    def rope(x):  # [T, H, hd]; rotate-half
        r = x.shape[-1]
        inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
        ang = positions.astype(jnp.float32)[:, None, None] * inv
        x1, x2 = x[..., : r // 2], x[..., r // 2 :]
        return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)

    return rope(q), rope(k)


OMISSIONS = {
    "no_conv": ("short_conv", lambda x, w: x),
    "alpha_one": ("log_decay", lambda x, lp, act: jnp.zeros((x.shape[0], lp["w_a"].shape[-1]), jnp.float32)),
    "beta_without_the_factor_two": ("beta_of", lambda x, lp, act, neg: jax.nn.sigmoid(x @ lp["w_beta"])),
    "no_l2norm": ("l2norm", lambda x: x),
    "no_output_gate": ("output_gate", lambda x, lp, heads, dv, act: jnp.ones((x.shape[0], heads, dv), jnp.float32)),
    "no_qk_norm": ("qk_norm", lambda q, k, lp, eps: (q, k)),
    "rotary_added": ("position_embed", rotary),
    "pre_norm_in_place_of_the_familys": ("sublayer", lambda x, f, w, eps: x + f(ref.rms_norm(x, w, eps))),
}


@pytest.mark.parametrize("name", sorted(OMISSIONS))
def test_program_fails_a_reference_that_omits(case, name):
    """The check is not blind: against a reference with one step of the
    block left out or done another way, the same program is far off."""
    params, tokens, _ = case
    attr, wrong = OMISSIONS[name]
    with mock.patch.object(ref, attr, wrong):
        other = reference_logits(params, CFG, tokens)
    assert rel(program_full(params, tokens), other) > WRONG


# -- the mechanisms one by one ---------------------------------------------------


def gdn_inputs(seed=0, b=2, t=150, h=3, dk=12, dv=24, fastest=3.0):
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(b, t, h, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    raw = (rng.normal(size=(b, t, h, dk)), k, rng.normal(size=(b, t, h, dv)),
           -rng.uniform(0.0, fastest, size=(b, t, h, 1)), 2.0 * rng.uniform(size=(b, t, h)),
           rng.normal(size=(b, h, dk, dv)))
    return [jnp.asarray(x, jnp.float32) for x in raw]


def test_chunked_is_recurrent_is_step_with_beta_up_to_two_and_a_chunk_past_minus_88():
    """One decay a head, β in (0, 2), keys narrower than values: 150 tokens
    (two whole chunks and a ragged third) whose summed log-decay over a chunk
    is about −96, past float32's exp underflow at −88. The scalar-gate chunk
    (one matmul and a mask), the per-channel chunk given the same decay on
    every channel, the token-by-token scan and a loop of ``kda_step`` agree."""
    q, k, v, g, beta, s0 = gdn_inputs()
    assert float(jnp.sum(g[0, :64, 0, 0])) < -88.0 and float(beta.max()) > 1.9
    o_ref, s_ref = kda.kda_recurrent(q, k, v, g, beta, s0)
    o, s = kda.kda_chunked(q, k, v, g, beta, s0)
    assert float(jnp.abs(o - o_ref).max()) < 2e-4 and float(jnp.abs(s - s_ref).max()) < 1e-5
    o_c, s_c = kda.kda_chunked(q, k, v, jnp.broadcast_to(g, q.shape), beta, s0)
    assert float(jnp.abs(o_c - o_ref).max()) < 2e-4 and float(jnp.abs(s_c - s_ref).max()) < 1e-5
    state, outs = s0, []
    for i in range(6):
        o_i, state = kda.kda_step(q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i], state)
        outs.append(o_i)
    assert float(jnp.abs(jnp.stack(outs, 1) - o_ref[:, :6]).max()) < 1e-5
    # padding rows behind the real ones (β = 0, g = 0) leave the state where it was
    pad = lambda x: jnp.pad(x, [(0, 0), (0, 40)] + [(0, 0)] * (x.ndim - 2), constant_values=1.0)  # noqa: E731
    gp, bp = kda.mask_inputs(pad(g), pad(beta), jnp.broadcast_to(jnp.arange(190) < 150, (2, 190)))
    o2, s2 = kda.kda_chunked(pad(q), pad(k), pad(v), gp, bp, s0)
    assert float(jnp.abs(o2[:, :150] - o_ref).max()) < 2e-4 and float(jnp.abs(s2 - s_ref).max()) < 1e-5


def recurrent64(q, k, v, g, beta, s):
    """The recurrence of ``kda_step`` token by token in float64 (numpy)."""
    q, k, v, g, beta, s = (np.asarray(x, np.float64) for x in (q, k, v, g, beta, s))
    out = []
    for t in range(q.shape[1]):
        s = s * np.exp(g[:, t])[..., None]
        u = beta[:, t][..., None] * (v[:, t] - np.einsum("bhk,bhkv->bhv", k[:, t], s))
        s = s + k[:, t][..., None] * u[..., None, :]
        out.append(np.einsum("bhk,bhkv->bhv", q[:, t], s))
    return np.stack(out, 1), s


def solve_chunked(q, k, v, g, beta, s):
    """The chunked rule with one decay a head as it stood before the blocked
    inverse: ``lax.linalg.triangular_solve`` on each chunk of 64, float32."""
    mm = lambda eq, *xs: jnp.einsum(eq, *xs, precision=jax.lax.Precision.HIGHEST)  # noqa: E731
    tri, outs = jnp.tril(jnp.ones((64, 64), bool)), []
    for at in range(0, q.shape[1], 64):
        qc, kc, vc, gc, bc = (jnp.moveaxis(x[:, at : at + 64], 1, 2) for x in (q, k, v, g[..., 0], beta))
        big_g = jnp.cumsum(gc, axis=2)
        decay = jnp.where(tri, jnp.exp(jnp.minimum(big_g[..., :, None] - big_g[..., None, :], 0.0)), 0.0)
        gamma = jnp.exp(big_g)[..., None]
        lower = mm("bhtk,bhsk->bhts", kc, kc) * decay * ~jnp.eye(64, dtype=bool) * bc[..., None]
        rhs = bc[..., None] * (vc - mm("bhtk,bhkv->bhtv", kc * gamma, s))
        u = jax.lax.linalg.triangular_solve(
            lower + jnp.eye(64), rhs, left_side=True, lower=True, unit_diagonal=True)
        outs.append(mm("bhtk,bhkv->bhtv", qc * gamma, s) + mm("bhts,bhsv->bhtv", mm("bhtk,bhsk->bhts", qc, kc) * decay, u))
        s = s * gamma[:, :, -1][..., None] + mm("bhsk,bhsv->bhkv", kc * jnp.exp(big_g[..., -1:] - big_g)[..., None], u)
    return jnp.moveaxis(jnp.concatenate(outs, axis=2), 2, 1), s


def hard_inputs(keys: str, beta, fastest: float, seed=1, b=1, t=128, h=2, dk=16, dv=24):
    """``keys``: ``random``, ``same`` (one unit key and a thousandth of noise:
    a repeated token) or ``rank4``; ``beta`` a constant or ``None`` for a
    draw over (0, 2); log-decays over ``(−fastest, 0)``, one a head."""
    rng = np.random.default_rng(seed)
    if keys == "same":
        k = rng.normal(size=(b, 1, h, dk)) + 1e-3 * rng.normal(size=(b, t, h, dk))
    elif keys == "rank4":
        k = np.einsum("btr,brhk->bthk", rng.normal(size=(b, t, 4)), rng.normal(size=(b, 4, h, dk)))
    else:
        k = rng.normal(size=(b, t, h, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    raw = (rng.normal(size=(b, t, h, dk)), k, rng.normal(size=(b, t, h, dv)),
           -rng.uniform(0.0, fastest, size=(b, t, h, 1)),
           2.0 * rng.uniform(size=(b, t, h)) if beta is None else np.full((b, t, h), beta),
           rng.normal(size=(b, h, dk, dv)))
    return [jnp.asarray(x, jnp.float32) for x in raw]


HARD = {  # name -> (keys, beta, fastest log-decay, tokens, held to the solve's error and not the absolute bounds)
    "random": ("random", None, 0.3, 128, False),
    "rank4": ("rank4", None, 0.3, 128, False),
    "past-88": ("random", None, 3.0, 128, False),
    "ragged": ("random", None, 0.3, 150, False),
    "same-b1": ("same", 1.0, 0.0, 128, True),
    "same-b1.5": ("same", 1.5, 0.0, 128, True),
    "same-b2": ("same", 2.0, 0.0, 128, True),
    "same-b1-decay": ("same", 1.0, 0.1, 128, True),
    "same-b1.5-decay": ("same", 1.5, 0.1, 128, True),
    "same-b2-decay": ("same", 2.0, 0.1, 128, True),
    "rank4-b2": ("rank4", 2.0, 0.0, 128, True),
}


@pytest.mark.parametrize("gate", ["head", "channel"])
@pytest.mark.parametrize("case", sorted(HARD))
def test_chunked_rule_with_the_blocked_inverse_against_float64(case, gate):
    """``kda_chunked`` (float32; the in-chunk triangle inverted in 16-token
    blocks and merged, ``ops/kda._unit_lower_inverse``) against the recurrence
    in float64, with one decay a head (``_scores_scalar``) and with the same
    decay handed over per channel (``_scores``). Keys drawn at random, of rank
    4, with a chunk whose summed log-decay passes −88 and with a ragged last
    chunk hold the bounds the chunked rule always had: 2e-4 on the outputs,
    1e-5 on the state. 128 near-identical unit keys (a repeated token) at
    β = 1, 1.5 and 2, with and without decay, and rank-4 keys at β = 2 are the
    inputs on which an inverse of ``I + diag(β) A`` is worst conditioned (the
    transitions ``I − β k kᵀ`` all flip or shrink the same direction): there
    the error over the largest value may be at most 4 × that of the chunked
    rule solved by ``lax.linalg.triangular_solve`` on the same inputs. Read on
    the CPU (PR 35) with one decay a head, outputs, blocked inverse /
    ``triangular_solve``: β = 1: 5.1e-7 / 4.1e-7, β = 1.5: 1.6e-6 / 1.2e-6,
    β = 2: 1.8e-5 / 1.6e-5; with decay 5.4e-7 / 3.9e-7, 8.8e-7 / 9.2e-7,
    4.3e-6 / 6.2e-6; rank 4 at β = 2: 6.1e-6 / 2.9e-6 (the widest, 2.1 ×; the
    states read alike). On the solve alone, such keys 96 wide against a
    float64 solve: 1.7e-6 / 3.0e-6 at β = 2. The product form ``(I − L)(I + L²)(I + L⁴)…`` reads 4.5e10 at
    β = 1 and 6.8e20 at β = 2 on such keys (the powers of ``L`` grow
    binomially and cancel), which is why it is not what the chunk uses."""
    keys, beta, fastest, t, by_solve = HARD[case]
    q, k, v, g, beta, s0 = hard_inputs(keys, beta, fastest, t=t)
    o_ref, s_ref = recurrent64(q, k, v, g, beta, s0)
    o, s = kda.kda_chunked(q, k, v, g if gate == "head" else jnp.broadcast_to(g, q.shape), beta, s0)
    over = lambda x, want: float(np.abs(np.asarray(x, np.float64) - want).max() / np.abs(want).max())  # noqa: E731
    if by_solve:
        o_ts, s_ts = solve_chunked(q, k, v, g, beta, s0)
        assert over(o, o_ref) <= 4.0 * over(o_ts, o_ref) and over(s, s_ref) <= 4.0 * over(s_ts, s_ref)
        assert over(o, o_ref) < 5e-5  # and small in itself
    else:
        assert float(np.abs(np.asarray(o) - o_ref).max()) < 2e-4 and float(np.abs(np.asarray(s) - s_ref).max()) < 1e-5


def test_negative_eigenvalues_are_entered():
    """With β = 2 and a unit key the transition ``I − β k kᵀ`` flips the
    state's component along k: the case ``sigmoid`` alone never reaches."""
    k = jnp.zeros((1, 1, 4)).at[0, 0, 0].set(1.0)
    s0 = jnp.ones((1, 1, 4, 3))
    _, s1 = kda.kda_step(k, k, jnp.zeros((1, 1, 3)), jnp.zeros((1, 1, 1)), jnp.full((1, 1), 2.0), s0)
    np.testing.assert_allclose(np.asarray(s1[0, 0, 0]), -1.0)
    np.testing.assert_allclose(np.asarray(s1[0, 0, 1:]), 1.0)


@pytest.mark.parametrize("b, h, dk, dv", [(3, 6, 16, 64), (2, 30, 96, 192)], ids=["small", "published"])
def test_gdn_decode_kernel_computes_what_kda_step_does(b, h, dk, dv):
    """Interpret mode: the GDN decode kernel on the state as stored, ``[n, B,
    dk, H·dv]``, against ``kda_step`` on the same tiles viewed a head at a
    time (one lane masked, the other layer of the stack untouched). 192-wide
    values pack two heads into a lane-aligned window; 30 heads go 10 a grid
    step."""
    from agentainer_tpu.ops.pallas_kda import gdn_blocking, gdn_decode

    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=(b, h, dk)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(b, h, dv)), jnp.float32)
    g = jnp.asarray(-rng.uniform(0, 2, size=(b, h)), jnp.float32).at[1].set(0.0)
    beta = jnp.asarray(2 * rng.uniform(size=(b, h)), jnp.float32).at[1].set(0.0)
    stack = jnp.asarray(rng.normal(size=(2, b, dk, h * dv)), jnp.float32)
    view = lambda s: jnp.swapaxes(s.reshape(b, dk, h, dv), 1, 2)  # noqa: E731
    o_want, s_want = kda.kda_step(q, k, v, g[..., None], beta, view(stack[1]))
    o, out = gdn_decode(q, k, v, g, beta, stack, 1, interpret=True)
    assert float(jnp.abs(o - o_want).max()) < 1e-3 and float(jnp.abs(view(out[1]) - s_want).max()) < 2e-4
    assert np.array_equal(np.asarray(out[0]), np.asarray(stack[0]))  # another layer
    assert np.array_equal(np.asarray(out[1, 1]), np.asarray(stack[1, 1]))  # the masked lane
    heads, pack = gdn_blocking(h, dk, dv)
    assert h % heads == 0 and heads % pack == 0 and (pack * dv) % 128 == 0


def test_flash_kernels_read_kv_rows_stored_with_padded_heads():
    """6 K/V heads are stored as 8 (``stored_kv_heads``): the dense flash
    kernels (interpret mode) over the padded rows, given a query padded with
    zero heads, give the reference attention over the model's 6 heads, for a
    prefill chunk at a slot and for a decode step at ragged positions."""
    assert [hybrid.stored_kv_heads(n) for n in (1, 2, 4, 6, 8, 30, 32, 40)] == [1, 2, 4, 8, 8, 32, 32, 40]
    rng = np.random.default_rng(2)
    nh, hd, s, lanes = 6, 128, 256, 3
    stored = hybrid.stored_kv_heads(nh)
    arena = lambda: jnp.asarray(rng.normal(size=(2, lanes, s, nh, hd)), jnp.float32)  # noqa: E731
    ck, cv = arena(), arena()
    padded = lambda a: jnp.pad(a, [(0, 0)] * 3 + [(0, stored - nh), (0, 0)])  # noqa: E731
    qpad = lambda q: jnp.pad(q, [(0, 0), (0, 0), (0, stored - nh), (0, 0)])  # noqa: E731
    q = jnp.asarray(rng.normal(size=(1, 40, nh, hd)), jnp.float32)
    pos = (100 + jnp.arange(40))[None]
    want = attn_ops._reference_dense(q, ck, cv, pos, None, 1, 2)
    got = attn_ops.pallas_dense(qpad(q), padded(ck), padded(cv), pos, None, 1, 2, interpret=True)[:, :, :nh]
    assert float(jnp.abs(got - want).max()) < 1e-4
    q1 = jnp.asarray(rng.normal(size=(lanes, 1, nh, hd)), jnp.float32)
    pos1 = jnp.asarray([[5], [255], [130]], jnp.int32)
    want = attn_ops._reference_dense(q1, ck, cv, pos1, None, 0, None)
    got = attn_ops.pallas_dense(qpad(q1), padded(ck), padded(cv), pos1, None, 0, None, interpret=True)[:, :, :nh]
    assert float(jnp.abs(got - want).max()) < 1e-4


def test_param_count_is_the_pytrees_size_and_the_published_models():
    params = init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    assert CFG.param_count() == sum(x.size for x in jax.tree.leaves(params))
    big = get_config("olmo-hybrid-7b")
    assert abs(big.param_count() / 7.43e9 - 1.0) < 0.002
    assert big.layer_kinds.count("gdn") == 24 and [i + 1 for i, k in enumerate(big.layer_kinds) if k == "full"] == list(range(4, 33, 4))
    assert big.active_param_count() == big.param_count() and not big.is_moe  # dense: every weight meets every token
    assert big.flops_per_token(2048) > 2.0 * big.param_count()
    cache = jax.eval_shape(lambda: init_cache(big, 8, 4096, jnp.bfloat16))
    assert cache.k.shape == (8, 8, 4096, 32, 128) and cache.state.shape == (24, 8, 96, 5760) and cache.latent is None
    assert cache.conv.shape == (24, 8, 3 * 11520)


def test_plan_takes_the_kernels_where_the_tiles_are_whole():
    big = get_config("olmo-hybrid-7b")
    plan = hybrid.plan_hybrid(big, use_pallas=True)
    assert (plan.gdn_decode, plan.full_decode, plan.full_prefill) == ("pallas_gdn_decode", "pallas:flash_decode", "pallas:flash_prefill")
    assert plan.kinds() == {"gdn": ("xla_chunked", "pallas_gdn_decode"), "full": ("pallas:flash_prefill", "pallas:flash_decode")}
    tiny = hybrid.plan_hybrid(CFG, use_pallas=True)  # a [12, 6 x 24] tile, heads of 16: neither kernel's shape
    assert tiny.gdn_decode == "xla_step" and tiny.full_decode == "xla:attention_reference" and "tiles" in tiny.reason
    assert hybrid.plan_hybrid(CFG, use_pallas=False).describe()["arena"] == "layer_slice"
    kimi = hybrid.plan_hybrid(get_config("tiny-kimi-linear"), use_pallas=False)
    assert set(kimi.describe()) == {"kda_decode", "kda_prefill", "mla_decode", "mla_prefill", "reason", "prefill", "decode", "arena"}
    # the other family's planner gives what it gave before Kimi's MLA prefill got a kernel (PR 38)
    assert plan == hybrid.HybridPlan(
        "", "", "", "", "tpu backend; state and K/V stacks read where they lie",
        gdn_decode="pallas_gdn_decode", gdn_prefill="xla_chunked",
        full_decode="pallas:flash_decode", full_prefill="pallas:flash_prefill",
    )
    assert set(plan.describe()) == {"gdn_decode", "gdn_prefill", "full_decode", "full_prefill", "reason", "prefill", "decode", "arena"}


# -- the cache manager on K/V rows and a recurrent state in one slot ----------------

ENGINE = {"max_batch": 2, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 32}
TURNS = [("turn one of a session that goes on for a while", 11), ("and a second turn", 9), ("a third", 7),
         ("the fourth turn brings a tool's output back", 8), ("and the fifth ends it", 6)]


def make_engine(name="tiny-olmo-hybrid", **over):
    from agentainer_tpu.engine.llm import LLMEngine

    return LLMEngine.create(name, options={**ENGINE, **over})


async def chat_all(eng, session="s", turns=TURNS):
    return [(await eng.chat(session, text, max_tokens=n))["tokens"] for text, n in turns]


@pytest.fixture(scope="module")
def uninterrupted():
    eng = make_engine()
    try:
        return asyncio.run(chat_all(eng))
    finally:
        eng.shutdown()


def test_five_turns_through_the_engine_are_a_plain_loop_over_forward(uninterrupted):
    eng = make_engine(skip_warmup=True)
    try:
        params, tok = eng.params, eng.tokenizer
    finally:
        eng.shutdown()
    cache = init_cache(CFG, 1, 256, dtype=jnp.float32)
    pos, pending, got = 0, [], []
    for text, n in TURNS:
        feed = pending + tok.encode(text)
        logits, cache = forward(params, CFG, jnp.asarray([feed], jnp.int32), (pos + jnp.arange(len(feed)))[None], cache)
        pos += len(feed)
        out = [int(jnp.argmax(logits[0, -1]))]
        while len(out) < n:
            logits, cache = forward(params, CFG, jnp.asarray([[out[-1]]], jnp.int32), jnp.full((1, 1), pos), cache)
            pos += 1
            out.append(int(jnp.argmax(logits[0, 0])))
        pending = [out[-1]]  # sampled, never fed: it leads the next turn's prompt
        got.append(out)
    assert got == uninterrupted


def test_kill_and_resume_after_every_turn_is_token_identical(uninterrupted):
    async def interrupted():
        out, blob = [], None
        for text, n in TURNS:
            eng = make_engine()
            try:
                if blob is not None:
                    assert await eng.restore_session("s", blob) is True
                out.append((await eng.chat("s", text, max_tokens=n))["tokens"])
                blob = await eng.snapshot_session("s")
                assert blob is not None
            finally:
                eng.shutdown()  # the crash
        return out

    assert asyncio.run(interrupted()) == uninterrupted


def test_four_sessions_over_two_lanes_evict_snapshot_restore_like_never_evicting():
    """Four sessions take turns round robin over two lanes: every turn but
    the first two finds its session evicted, restores its snapshot (K/V rows,
    state, conv) into a lane another session just left, and goes on. The
    tokens are those of four lanes, where nobody is ever evicted."""
    names = ["a", "b", "c", "d"]
    said = {n: [(f"{n} says: {text}", k) for text, k in TURNS[:3]] for n in names}

    async def run(lanes: int):
        eng = make_engine(max_batch=lanes)
        eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
        blobs, out = {}, {n: [] for n in names}
        try:
            for turn in range(3):
                for n in names:
                    if not eng.has_session(n) and n in blobs:
                        assert await eng.restore_session(n, blobs[n]) is True
                    text, k = said[n][turn]
                    out[n].append((await eng.chat(n, text, max_tokens=k))["tokens"])
                    blobs[n] = await eng.snapshot_session(n)
            return out, eng.metrics()
        finally:
            eng.shutdown()

    evicting, m = asyncio.run(run(2))
    roomy, m4 = asyncio.run(run(4))
    assert evicting == roomy
    assert m["cache"]["state_restores"] == 8 and m["session_evictions_total"] >= 8 and m4["cache"]["state_restores"] == 0
    assert m["phases"]["engine.restore"]["n"] == 8 and m["phases"]["engine.snapshot"]["n"] >= 12


def test_a_parked_sessions_rows_and_state_are_bit_identical_after_another_lanes_steps():
    async def run():
        eng = make_engine(max_batch=3)
        try:
            await eng.chat("a", "the first session says a few words", max_tokens=13)
            lane = eng.sessions["a"]
            n = eng.slots[lane].position

            def held():
                c = eng.cache
                return [np.asarray(x) for x in (c.k[:, lane, :n], c.v[:, lane, :n], c.state[:, lane], c.conv[:, lane],
                                                c.state[:, 2], c.conv[:, 2])]

            before = held()
            steps0 = eng.forward_passes
            await eng.chat("b", "the second session talks for much longer than the first did", max_tokens=60)
            assert eng.forward_passes - steps0 > 32
            return before, held()
        finally:
            eng.shutdown()

    before, after = asyncio.run(run())
    for x, y in zip(before, after):
        assert np.array_equal(x, y)
    assert before[2].any() and not before[4].any()  # a's state moved; the idle lane never left zero


def test_snapshots_are_refused_by_their_leaves_names_across_families_and_a_kimi_blob_still_restores():
    """``k`` and ``v`` BESIDE a state is not a K/V family's snapshot, and a
    latent is not K/V rows: each engine refuses the others' blobs by the
    names of their leaves (the caller prefills again), never misreads one; a
    version-4 blob of the Kimi family restores into a Kimi engine as before."""
    from agentainer_tpu.engine.checkpoint import SNAP_VERSION, deserialize_snapshot

    async def blob_of(eng):
        await eng.chat("s", "hello there", max_tokens=4)
        eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
        return await eng.snapshot_session("s")

    async def run():
        engines = {
            "olmo": make_engine(skip_warmup=True),
            "kimi": make_engine("tiny-kimi-linear", skip_warmup=True),
            "kv": make_engine("tiny", skip_warmup=True, max_batch=1, max_seq=128, speculative=False),
        }
        try:
            blobs = {n: await blob_of(e) for n, e in engines.items()}
            leaves = {n: set(deserialize_snapshot(b)[0]) for n, b in blobs.items()}
            versions = {deserialize_snapshot(b)[1]["version"] for b in blobs.values()}
            crossed = {(src, dst): await engines[dst].restore_session("t", blobs[src])
                       for src in engines for dst in engines if src != dst}
            own = await engines["kimi"].restore_session("u", blobs["kimi"])
            return leaves, versions, crossed, own
        finally:
            for e in engines.values():
                e.shutdown()

    leaves, versions, crossed, own = asyncio.run(run())
    assert leaves == {"olmo": {"k", "v", "state", "conv"}, "kimi": {"latent", "state", "conv"}, "kv": {"k", "v"}}
    assert versions == {SNAP_VERSION} == {4}
    assert not any(crossed.values()), crossed
    assert own is True


def test_a_snapshot_ships_the_models_kv_heads_not_the_stored_padding():
    from agentainer_tpu.engine.checkpoint import deserialize_snapshot

    async def run():
        eng = make_engine(skip_warmup=True)
        try:
            await eng.chat("s", "hello there", max_tokens=4)
            eng.snapshot_min_gap_s = eng.snapshot_busy_gap_s = 0.0
            return deserialize_snapshot(await eng.snapshot_session("s")), eng.cache.k.shape, eng.slots[eng.sessions["s"]].position
        finally:
            eng.shutdown()

    (leaves, header), arena, position = asyncio.run(run())
    assert arena[3] == 8 and leaves["k"].shape == (2, position, 6, 16) == leaves["v"].shape
    assert leaves["state"].shape == (6, 12, 6 * 24) and header["position"] == position


@pytest.mark.parametrize("option", ["speculative", "paged_kv", "kv_tiering", "fused_decode", "prefix_cache"])
def test_a_feature_the_state_cannot_hold_is_an_error_when_asked_for(option):
    with pytest.raises(ValueError, match=option):
        make_engine(skip_warmup=True, **{option: True})


def test_metrics_name_the_cache_kinds_the_plan_and_what_is_off():
    eng = make_engine(skip_warmup=True)
    try:
        m = eng.metrics()
    finally:
        eng.shutdown()
    cache = m["cache"]
    assert cache["kinds"] == ["k", "v", "state", "conv"]
    total = cache["k_bytes"] + cache["v_bytes"] + cache["state_bytes"] + cache["conv_bytes"]
    assert cache["bytes_per_lane"] * 2 == total == m["kv_arena_bytes"] - 16
    assert set(cache["off"]) == {"speculative", "prefix_cache", "paged_kv", "fused_decode", "kv_tiering", "mesh"}
    assert m["speculative"] is False and m["prefix_cache"] is False and m["mixed_launches"] == 0
    assert m["model_arch"]["layer_kinds"] == {"full": 2, "gdn": 6} and m["model_arch"]["dense_layers"] == 8
    att = m["attention"]
    assert (att["gdn_prefill"], att["gdn_decode"]) == ("xla_chunked", "xla_step") and att["reason"] == "no tpu backend"
    assert att["full_prefill"] == att["full_decode"] == att["decode"] == "xla:attention_reference"
    assert m["moe"]["impl"] == "none"


def test_the_gdn_kernels_name_is_the_one_the_benchmarks_reader_looks_for():
    import inspect
    import re

    from agentainer_tpu.ops import pallas_kda

    assert 'name="gdn_decode"' in inspect.getsource(pallas_kda)
    with open(os.path.join(REPO, "benchmark", "layer_metrics", "gdn_decode_roofline.py")) as f:
        assert re.search(r'^KERNEL = "gdn_decode"$', f.read(), re.M)
