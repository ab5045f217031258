"""The sorted grouped MoE FFN (ISSUE 29): prefill stops computing every expert.

Above the chip's ridge (``ops/moe.sorted_from_rows``) a call's MoE FFN sorts
its (token, chosen expert) assignments by expert and runs one grouped FFN
over the stacked expert weights, int8 as stored. Invariants held here, on
the CPU: it is the all-experts einsum's function (same router, exact top-k,
nothing dropped at any skew, padding rows harmless); the Pallas kernel
(interpret mode) and the ``ragged_dot`` form agree; the cut is one function
of static shapes; the engine's ``moe`` counters say how often it engages.
"""

import asyncio
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.lax import top_k as lax_top_k

from agentainer_tpu.engine.llm import LLMEngine
from agentainer_tpu.models.configs import get_config
from agentainer_tpu.models.llama import (
    _moe_mlp,
    _moe_mlp_routed,
    _moe_mlp_sorted,
    forward,
    init_params,
    moe_gates,
    moe_sorts,
)
from agentainer_tpu.ops.moe import (
    EXPERT_WEIGHTS,
    route,
    row_tile,
    sorted_from_rows,
    sorted_moe_ffn,
    sorted_rows,
    stacked_experts,
)
from agentainer_tpu.ops.quant import QTensor, dequant, quantize_array


def _layers(cfg, weights: str, scale: float = 1.0):
    """The stacked ``layers`` pytree with the experts stored as ``weights``
    (``float32``, ``bfloat16`` or ``int8`` QTensors), router in float32."""
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    layers = {k: params["layers"][k] * scale for k in ("router", *EXPERT_WEIGHTS)}
    for name in EXPERT_WEIGHTS:
        if weights == "int8":
            layers[name] = quantize_array(np.asarray(layers[name]), jnp.float32)
        else:
            layers[name] = layers[name].astype(weights)
    return layers


def _layer(layers, l):
    """Layer ``l`` as ``forward``'s block sees it: sliced and dequantised."""
    return {
        k: dequant(QTensor(v.q[l], v.scale[l]) if isinstance(v, QTensor) else v[l])
        for k, v in layers.items()
    }


def _sorted(x, layers, cfg, l, **how):
    lp = _layer(layers, l)
    xf = x.reshape(-1, x.shape[-1])
    gates, chosen = moe_gates(xf @ lp["router"], cfg, x.dtype)
    out = sorted_moe_ffn(xf, gates, chosen, stacked_experts(layers), jnp.int32(l), **how)
    return out.reshape(x.shape)


TOL = {"float32": 2e-6, "bfloat16": 2e-2, "int8": 2e-6}


@pytest.mark.parametrize("how", ["ragged_dot", "pallas_interpret"])
@pytest.mark.parametrize("weights", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("model", ["tiny-moe", "tiny-olmoe"])
def test_sorted_ffn_is_the_all_experts_einsum(model, weights, how):
    """Both forms of the grouped FFN against ``_moe_mlp`` on the layer's
    dequantised weights, at the second layer of the stack (the kernel finds
    it by index), Mixtral's routing (renormalised top-2 of 4) and OLMoE's
    (un-renormalised top-2 of 8)."""
    cfg = get_config(model)
    layers = _layers(cfg, weights, scale=8.0)
    act = jnp.bfloat16 if weights == "bfloat16" else jnp.float32
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 160, cfg.dim), jnp.float32).astype(act)
    layers["router"] = layers["router"].astype(act)
    want = _moe_mlp(x, _layer(layers, 1), cfg).astype(jnp.float32)
    kw = {"kernel": False} if how == "ragged_dot" else {"interpret": True}
    got = _sorted(x, layers, cfg, 1, **kw).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < TOL[weights], err


@pytest.mark.parametrize("model", ["tiny-moe", "tiny-olmoe"])
def test_every_row_on_the_same_experts_drops_nothing(model):
    """The dropless proof: a zero router ties every logit, so every row
    chooses experts 0 .. k-1 and each of them gets all N rows. The sorted FFN
    still equals ``_moe_mlp``; the capacity-bound dispatch at its default
    factor 2 loses rows and does not (with 2 of 4 experts a factor of 2 is
    already every row, so ``tiny-moe`` shows the loss at factor 1)."""
    cfg = get_config(model)
    layers = _layers(cfg, "float32", scale=8.0)
    layers["router"] = jnp.zeros_like(layers["router"])
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 128, cfg.dim), jnp.float32)
    lp = _layer(layers, 0)
    _, chosen = moe_gates(x[0] @ lp["router"], cfg, x.dtype)
    assert np.asarray(chosen).tolist() == [list(range(cfg.experts_per_token))] * 128
    want = _moe_mlp(x, lp, cfg)
    for kw in ({"kernel": False}, {"interpret": True}):
        got = _sorted(x, layers, cfg, 0, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5 * float(jnp.max(jnp.abs(want))))
    factor = 2.0 if cfg.n_experts > 2 * cfg.experts_per_token else 1.0
    dropped = _moe_mlp_routed(x, lp, cfg, capacity_factor=factor)
    assert not np.allclose(np.asarray(dropped), np.asarray(want), atol=1e-3 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("n, n_experts, k", [(128, 8, 2), (256, 8, 2), (256, 64, 8), (1024, 64, 8), (130, 4, 2)])
def test_route_places_every_assignment_once_in_its_experts_tiles(n, n_experts, k):
    """The layout the kernel relies on: every assignment has one row, a row
    tile holds one expert's rows only, used tiles come first, and the static
    buffer holds the worst routing (random, and all rows on the same k)."""
    tile, m = row_tile(n, n_experts, k), sorted_rows(n, n_experts, k)
    assert m % tile == 0 and tile in (32, 64, 128)
    rng = np.random.default_rng(n + n_experts)
    random = np.stack([rng.permutation(n_experts)[:k] for _ in range(n)])
    skewed = np.tile(np.arange(k), (n, 1))
    for chosen in (random, skewed):
        r = route(jnp.asarray(chosen, jnp.int32), n_experts, tile, m)
        row_of = np.asarray(r.row_of)
        n_active = int(r.n_active)
        assert len(set(row_of.tolist())) == n * k and row_of.max() < n_active * tile <= m
        tile_expert = np.asarray(r.tile_expert)
        assert (tile_expert[row_of // tile] == chosen.reshape(-1)).all()
        assert (tile_expert[n_active:] == tile_expert[n_active - 1]).all()
        used = np.bincount(tile_expert[:n_active], minlength=n_experts)
        counts = np.bincount(chosen.reshape(-1), minlength=n_experts)
        assert (used == -(-counts // tile)).all()


@pytest.mark.parametrize("model", ["tiny-moe", "tiny-olmoe"])
def test_rows_routed_nowhere_take_no_tile_and_change_no_other_row(model):
    """The mixed step's parked lanes (ISSUE 33): ``routed`` False rows choose
    no expert, so they get 0, take no buffer row and open no tile, and every
    other row's sum is the einsum's. Here the last three of 132 rows are the
    only ones that would choose the last expert (the router's column for it
    is 0 for every row, and theirs alone are negative everywhere else):
    routed like any row they cost that expert's whole stream for one tile."""
    cfg = get_config(model)
    layers = _layers(cfg, "float32", scale=8.0)
    e, k, n, parked = cfg.n_experts, cfg.experts_per_token, 132, 3
    layers["router"] = layers["router"].at[:, :, e - 1].set(0.0)
    x = jax.random.normal(jax.random.PRNGKey(11), (1, n, cfg.dim), jnp.float32)
    lp = _layer(layers, 1)
    logits = x[0] @ lp["router"]
    # the real rows like some other experts better than 0; the parked rows like nothing better
    logits = logits.at[: n - parked, :k].add(50.0).at[n - parked :, : e - 1].add(-50.0)
    _, chosen = moe_gates(logits, cfg, x.dtype)
    chosen = np.asarray(chosen)
    assert (chosen[: n - parked] != e - 1).all() and (chosen[n - parked :] == e - 1).any(axis=1).all()
    routed = jnp.arange(n) < n - parked
    experts = stacked_experts(layers)
    want = _moe_mlp(x, lp, cfg, logits=logits[None])
    got = _moe_mlp_sorted(x, lp, cfg, experts, jnp.int32(1), logits=logits[None], routed=routed)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got[0, : n - parked]), np.asarray(want[0, : n - parked]), atol=2e-6 * scale)
    assert not np.asarray(got[0, n - parked :]).any()
    # the layout: what sorted_moe_ffn hands ``route`` with and without the rule
    tile, m = row_tile(n, e, k), sorted_rows(n, e, k)
    all_rows = route(jnp.asarray(chosen), e, tile, m)
    live_rows = route(jnp.where(routed[:, None], jnp.asarray(chosen), e), e, tile, m)
    assert int(all_rows.n_active) > int(live_rows.n_active)  # a tile for each expert only they chose
    assert e - 1 in np.asarray(all_rows.tile_expert) and e - 1 not in np.asarray(live_rows.tile_expert)


@pytest.mark.parametrize("n_experts, k, bucket, lanes, tile", [
    (8, 2, 128, 8, 64), (8, 2, 256, 8, 128), (64, 8, 128, 16, 32), (64, 8, 256, 16, 64), (64, 8, 512, 16, 128),
], ids=["mixtral-128", "mixtral-256", "olmoe-128", "olmoe-256", "olmoe-512"])
def test_rows_just_over_a_bucket_keep_its_tile(n_experts, k, bucket, lanes, tile):
    """A launch that carries the decode lanes beside a prefill chunk (ISSUE
    31) has ``bucket + lanes`` rows: an expert's fair share is just over a
    power of two, and the tile stays the bucket's (twice the bucket's share,
    1.9 times this one's) — so do the row buffer's tiles an expert. At twice
    the share it took the next power of two and every expert's padding
    doubled for a sixteenth more rows."""
    assert row_tile(bucket, n_experts, k) == row_tile(bucket + lanes, n_experts, k) == tile
    grown = sorted_rows(bucket + lanes, n_experts, k) - sorted_rows(bucket, n_experts, k)
    assert 0 <= grown <= lanes * k + tile  # the lanes' own assignments, no expert's padding


def test_the_cut_is_the_devices_ridge():
    """One function of (E, k, stored dtype, device peaks): v5e int8 → 121
    rows (197e12 ÷ (2 × 819e9) = 120.3), twice that for bf16; a device with
    another ridge moves it; a CPU reads as the v5e; k = E never sorts."""
    assert sorted_from_rows(8, 2, jnp.int8, "TPU v5 lite") == 121
    assert sorted_from_rows(64, 8, jnp.int8, "TPU v5 lite") == 121
    assert sorted_from_rows(8, 2, jnp.bfloat16, "TPU v5 lite") == 241
    assert sorted_from_rows(8, 2, jnp.float32, "TPU v5 lite") == 482
    assert sorted_from_rows(8, 2, jnp.int8, "TPU v6 lite") == 280
    assert sorted_from_rows(8, 2, jnp.int8, "cpu") == 121
    assert sorted_from_rows(8, 2, jnp.int8) == 121  # this process: a CPU
    assert sorted_from_rows(4, 4, jnp.int8, "TPU v5 lite") is None


@pytest.mark.parametrize("rows, sorts", [(120, False), (121, True)])
def test_forward_takes_the_path_its_row_count_says(rows, sorts):
    """Just under and just over the cut, no option: the traced program holds
    the grouped matmul (``ragged_dot`` off the TPU) or the all-experts einsum,
    and gives the einsum's logits either way."""
    cfg = get_config("tiny-moe")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    for name in EXPERT_WEIGHTS:
        params["layers"][name] = quantize_array(np.asarray(params["layers"][name]), jnp.float32)
    assert moe_sorts(cfg, params["layers"], rows) is sorts
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, rows), 0, cfg.vocab_size)
    positions = jnp.arange(rows)[None]
    jaxpr = str(jax.make_jaxpr(lambda p: forward(p, cfg, tokens, positions)[0])(params))
    assert ("ragged_dot" in jaxpr) is sorts
    got, _ = forward(params, cfg, tokens, positions)
    want, _ = forward(params, cfg, tokens, positions, moe_impl=partial(_moe_mlp, cfg=cfg))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_a_buckets_padding_rows_change_no_real_row():
    """A 256-token bucket holding 200 real tokens: the padding rows are
    routed like any other row (they take buffer rows and tiles), and the
    real rows' logits are those of the 200 tokens alone."""
    cfg = get_config("tiny-olmoe")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    for name in EXPERT_WEIGHTS:
        params["layers"][name] = quantize_array(np.asarray(params["layers"][name]), jnp.float32)
    real = jax.random.randint(jax.random.PRNGKey(2), (1, 200), 1, cfg.vocab_size)
    padded = jnp.concatenate([real, jnp.zeros((1, 56), jnp.int32)], axis=1)
    assert moe_sorts(cfg, params["layers"], 256) and moe_sorts(cfg, params["layers"], 200)
    got, _ = forward(params, cfg, padded, jnp.arange(256)[None])
    want, _ = forward(params, cfg, real, jnp.arange(200)[None], moe_impl=partial(_moe_mlp, cfg=cfg))
    np.testing.assert_allclose(np.asarray(got[:, :200]), np.asarray(want), atol=2e-5)


def test_the_moe_counters_follow_the_launches():
    """``/metrics`` ``moe``: the cut, the path's name, and three cumulative
    counts taken from static shapes at each launch. A 401-token prompt is two
    prefill launches of bucket 256 (both sorted), a short one a bucket of 32
    (einsum); decode launches are ``max_batch`` rows a step."""
    cfg = get_config("tiny-moe")
    e, k, batch = cfg.n_experts, cfg.experts_per_token, 4
    eng = LLMEngine.create(
        "tiny-moe",
        options={"max_batch": batch, "max_seq": 1024, "decode_chunk": 8, "prefill_chunk": 256,
                 "quant": "int8", "speculative": False},
    )
    try:
        def snap():
            m = eng.metrics()
            return m["moe"], sum(int(c) * n for c, n in m["decode_chunk_hist"].items())

        moe0, steps0 = snap()
        assert moe0["impl"] == "all_experts_einsum" and moe0["prefill_impl"] == "sorted_grouped_ffn"
        assert moe0["routed_from_rows"] == 121
        asyncio.run(eng.generate("word " * 80, max_tokens=8, ignore_eos=True))  # 401 tokens
        moe1, steps1 = snap()
        asyncio.run(eng.generate("hi", max_tokens=8, ignore_eos=True))  # 3 tokens: bucket 32
        moe2, steps2 = snap()
    finally:
        eng.shutdown()
    d1 = {key: moe1[key] - moe0[key] for key in ("assignments", "rows_all_experts", "rows_routed")}
    decode1 = (steps1 - steps0) * batch
    assert d1 == {
        "assignments": (2 * 256 + decode1) * k,
        "rows_all_experts": decode1 * e,
        "rows_routed": 2 * sorted_rows(256, e, k),
    }
    d2 = {key: moe2[key] - moe1[key] for key in ("assignments", "rows_all_experts", "rows_routed")}
    decode2 = (steps2 - steps1) * batch
    assert d2 == {
        "assignments": (32 + decode2) * k, "rows_all_experts": (32 + decode2) * e, "rows_routed": 0,
    }
    # the routed share the counters give: near 1 for the long prompt's prefill
    share = d1["rows_routed"] / (d1["rows_routed"] + d1["rows_all_experts"] / (e / k))
    assert share > 0.9 or decode1 > 256
    assert moe2["rows_gathered_in_kernel"] == 0  # this backend's sorted calls take the plain path


@pytest.mark.parametrize("in_kernel", [False, True], ids=["plain_path", "kernel"])
def test_rows_gathered_in_kernel_counts_the_launches_over_the_cut(monkeypatch, in_kernel):
    """``moe.rows_gathered_in_kernel``: the rows of the launches over the cut
    where the sorted FFN's default is the kernel (a TPU backend), so that
    over (assignments ÷ k − rows_all_experts ÷ experts held) is the share of
    sorted rows whose trip happened in VMEM: 1 on the chip, 0 here.
    ``rows_routed`` stays the planned grid's rows either way."""
    import agentainer_tpu.engine.llm as llm

    monkeypatch.setattr(llm, "kernel_by_default", lambda: in_kernel)
    eng = LLMEngine.create(
        "tiny-moe",
        options={"max_batch": 4, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 256, "quant": "int8",
                 "skip_warmup": True},
    )
    try:
        e, k = eng.cfg.n_experts, eng.cfg.experts_per_token
        eng._count_moe_rows(256 + 4)  # a mixed launch: over the cut
        eng._count_moe_rows(4, 8)  # a decode chunk: under it
        moe = eng.metrics()["moe"]
    finally:
        eng.shutdown()
    assert moe["rows_routed"] == sorted_rows(260, e, k) and moe["rows_all_experts"] == 8 * 4 * e
    assert moe["rows_gathered_in_kernel"] == (260 if in_kernel else 0)
    over_the_cut = moe["assignments"] // k - moe["rows_all_experts"] // e
    assert over_the_cut == 260


def test_a_tp_mesh_keeps_the_einsum():
    """GSPMD cannot partition the grouped kernel: a meshed engine pins the
    all-experts einsum for every call and says so."""
    eng = LLMEngine.create(
        "tiny-moe",
        options={"max_batch": 4, "max_seq": 256, "decode_chunk": 8, "prefill_chunk": 256,
                 "quant": "int8", "tp": 2, "skip_warmup": True},
    )
    try:
        moe = eng.metrics()["moe"]
    finally:
        eng.shutdown()
    assert moe["impl"] == moe["prefill_impl"] == "all_experts_einsum"
    assert moe["routed_from_rows"] is None


# the (rows a launch, experts held, experts, top-k) of the benchmark's six MoE cells
CELL_SHAPES = {
    "laguna.docs": (264, 32, 256, 8), "kimi.decode": (320, 32, 256, 8), "mistral4.docs": (272, 32, 128, 4),
    "smallthinker.mixed": (264, 16, 64, 6), "olmoe.longprompt": (272, 64, 64, 8), "mixtral.longprompt": (264, 8, 8, 2),
}


def _served_experts(e_held: int, d: int, f: int, layers: int = 2) -> dict:
    """Stacked int8 experts with bfloat16 per-channel scales, as the engine stores them."""
    keys = jax.random.split(jax.random.PRNGKey(53), 6)
    out = {}
    for i, (name, (rows, cols)) in enumerate(zip(EXPERT_WEIGHTS, ((d, f), (d, f), (f, d)))):
        q = jax.random.randint(keys[i], (layers, e_held, rows, cols), -127, 128, jnp.int8)
        scale = jax.random.uniform(keys[3 + i], (layers, e_held, 1, cols), jnp.float32, 0.5, 1.5) / (127 * rows**0.5)
        out[name] = QTensor(q, scale.astype(jnp.bfloat16))
    return stacked_experts(out)


@pytest.mark.parametrize("routing", ["uniform", "one_expert", "none_here", "parked_lanes", "reglu"])
@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_the_kernel_gathers_and_sums_what_the_plain_path_does(cell, routing):
    """The grouped kernel (interpret mode) against the plain path — the row
    buffer, the 0/1 spread matrix and ``ragged_dot`` — at the six cells'
    launch shapes with small d and F, int8 experts with bfloat16 scales as
    stored, the second layer of the stack: the rows reach their tiles and a
    token's k terms come back summed inside the kernel, a stage of 128 rows at
    a time (every shape here has more than one). ``uniform``: random distinct
    choices; ``one_expert``: every row on the same k experts, so each gets all
    N rows in several tiles; ``none_here``: no choice lands in the held share
    (a whole share: every row routed nowhere), and the result is 0 exactly;
    ``parked_lanes``: the launch's last rows choose no expert; ``reglu``:
    SmallThinker's gate."""
    n, e_held, e, k = CELL_SHAPES[cell]
    d, f, lanes = 128, 256, n - 256
    experts = _served_experts(e_held, d, f)
    kx, kl = jax.random.split(jax.random.PRNGKey(n + e))
    x = jax.random.normal(kx, (n, d), jnp.float32)
    offset = 0 if e_held == e else e_held  # a share in the middle of the router's experts
    gates, chosen = lax_top_k(jax.nn.softmax(jax.random.normal(kl, (n, e))), k)
    held = (offset, e) if e_held != e else None
    if routing == "one_expert":
        chosen = jnp.broadcast_to(offset + jnp.arange(k) % e_held, (n, k)) if k <= e_held else chosen
    elif routing == "none_here":
        chosen = (offset + e_held + chosen % (e - e_held)) % e if e_held != e else jnp.full((n, k), -1)
        held = (offset, e)
    elif routing == "parked_lanes":
        chosen = jnp.where((jnp.arange(n) < n - lanes // 2)[:, None], chosen, -1)
        held = (offset, e)
    act = {"act": "relu"} if routing == "reglu" else {}
    want = sorted_moe_ffn(x, gates, chosen, experts, jnp.int32(1), kernel=False, held=held, **act)
    got = sorted_moe_ffn(x, gates, chosen, experts, jnp.int32(1), interpret=True, held=held, **act)
    scale = float(jnp.max(jnp.abs(want)))
    if routing == "none_here":
        assert scale == 0.0 and not np.asarray(got).any()
        return
    assert scale > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL["int8"] * scale)
    if routing == "parked_lanes":
        assert not np.asarray(got[n - lanes // 2 :]).any()


def test_the_kernel_sums_over_several_blocks_of_f_and_cuts_a_long_call(monkeypatch):
    """Two plans the served shapes reach only at published widths: an F cut
    into blocks (Mixtral's 14,336 columns; here a weight plan of 300 KiB cuts
    256 into two), where a stage's rows are gathered at its first tile's first
    block and combined at its last tile's last; and a call of more rows than
    the kernel keeps in VMEM (here a plan of 200 rows), cut into pieces."""
    from agentainer_tpu.ops import pallas_moe

    n, e, k, d, f = 300, 8, 2, 128, 256
    experts = _served_experts(e, d, f)
    x = jax.random.normal(jax.random.PRNGKey(1), (n, d), jnp.float32)
    gates, chosen = lax_top_k(jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (n, e))), k)
    want = sorted_moe_ffn(x, gates, chosen, experts, jnp.int32(0), kernel=False)
    monkeypatch.setattr(pallas_moe, "_WEIGHT_VMEM", 300 << 10)
    assert pallas_moe.ffn_block(d, f, 1, 4) == 128
    pallas_moe.grouped_ffn.clear_cache()
    got = sorted_moe_ffn(x, gates, chosen, experts, jnp.int32(0), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL["int8"] * float(jnp.max(jnp.abs(want))))
    monkeypatch.setattr(pallas_moe, "_ROWS_VMEM", 200 * d * 8)
    assert pallas_moe.resident_rows(d, 4) == 200
    got = sorted_moe_ffn(x, gates, chosen, experts, jnp.int32(0), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL["int8"] * float(jnp.max(jnp.abs(want))))
    pallas_moe.grouped_ffn.clear_cache()


def test_a_dropped_choice_adds_nothing_where_the_last_stage_outruns_the_plan(monkeypatch):
    """A choice with no row carries the plan's row count ``m`` as its row.
    136 rows, 4 of 16 experts held, top-2: ``m`` is 384, so the second stage of
    256 rows (the stage set to that here) outruns the plan by 128 — whose places in the stage still hold the
    first stage's results. Nearly every row chooses held experts 0 and 1 (ten
    tiles: both stages run); the last four choose absent ones and get 0."""
    from agentainer_tpu.ops import pallas_moe

    monkeypatch.setattr(pallas_moe, "_STAGE_ROWS", 256)
    pallas_moe.grouped_ffn.clear_cache()
    n, e_held, e, k, d, f = 136, 4, 16, 2, 128, 256
    assert row_tile(n, e, k) == 32 and sorted_rows(n, e_held, k, 32) == 384
    experts = _served_experts(e_held, d, f)
    x = jax.random.normal(jax.random.PRNGKey(3), (n, d), jnp.float32)
    chosen = jnp.where((jnp.arange(n) < n - 4)[:, None], jnp.array([0, 1]), jnp.array([9, 12]))
    gates = jnp.full((n, k), 0.5, jnp.float32)
    want = sorted_moe_ffn(x, gates, chosen, experts, jnp.int32(1), kernel=False, held=(0, e))
    got = sorted_moe_ffn(x, gates, chosen, experts, jnp.int32(1), interpret=True, held=(0, e))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL["int8"] * float(jnp.max(jnp.abs(want))))
    assert np.asarray(want[: n - 4]).any() and not np.asarray(got[n - 4 :]).any()
    pallas_moe.grouped_ffn.clear_cache()
