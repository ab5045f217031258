"""The chip's own compiler, asked from a sandbox that has no chip.

libtpu compiles for a TPU that is *described*, not attached
(``jax.experimental.topologies``), so the Mosaic lowering of every
attention kernel the serving path can pick is checked here at llama3-8b
widths — the shapes one v5e chip runs (32 query / 8 KV heads) and the
per-device shapes of a tp=4 split (8 / 2) — plus the shard_map wrapper on
the described 2x2 mesh. Interpret mode cannot see what this sees: a block
whose last two dims miss the (8, 128) tiling, or a kernel over its VMEM
budget, passes every interpret-mode parity test and is refused here.

Nothing runs: a passing compile says the chip would accept the program,
never that it is right or fast (parity lives in test_pallas_attention.py,
on-chip numerics in chip_smoke.py).
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from agentainer_tpu.models.configs import get_config
from agentainer_tpu.models.llama import KVCache, forward, init_params
from agentainer_tpu.ops.moe import row_tile, sorted_moe_ffn, sorted_rows
from agentainer_tpu.ops.quant import QTensor
from agentainer_tpu.ops.pallas_attention import (
    flash_decode,
    flash_prefill,
    fused_paged_flash_decode,
    fused_paged_flash_prefill,
)
from agentainer_tpu.parallel.flash_mesh import make_meshed_cache_attention

# llama3-8b serving shapes (models/configs.py, engine defaults)
B, S, HD = 8, 2048, 128  # max_batch, max_seq, head_dim
LAYERS = 4  # depth of the stacked arena the dense kernels index by layer
T = 256  # prefill chunk
PS = 64  # page size
NB = S // PS  # block-table width
POOL = B * NB + B  # data pages + one scratch page per lane
HEADS = {
    "one-chip": (32, 8),
    "tp4-per-device": (8, 2),
    "olmoe-mha": (16, 16),
    # the dense kernels' K/V block holds a run of positions with a BLOCK of
    # KV heads, and prefill's q tile every query head of that block: sized
    # from a VMEM plan (ops/pallas_attention._kv_block), so checked where
    # the plan has to cut — two and four head blocks of 16 (Llama-2-7B MHA
    # and a 64-head one), a count with no 16-head divisor (Llama-2-13B MHA,
    # shorter blocks), 64 query heads over 8 (Llama-70B GQA, a shorter q
    # tile) — and at the other end one KV head (MQA)
    "mha32": (32, 32),
    "mha64": (64, 64),
    "mha40": (40, 40),
    "gqa64x8": (64, 8),
    "mqa": (8, 1),
}


@pytest.fixture(scope="module")
def v5e():
    """The described v5e:2x2 topology, with the persistent compilation
    cache off around the module: a compile for a described device is
    written to the cache but cannot be read back without a chip, so the
    next run would warn and compile again anyway."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    # libtpu guards real chips with a one-process lockfile; nothing is
    # attached here, and parallel test workers each load the compiler
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to ask
        pytest.skip(f"cannot describe a v5e:2x2 topology: {type(e).__name__}: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _kernel_args(kernel: str, h: int, kv: int, where):
    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    # the dense kernels take the STACKED arena as the layer scan carries it,
    # with the layer (and the prefilling lane's slot) as scalars
    arena = s((LAYERS, B, S, kv, HD))
    scalar = s((), jnp.int32)
    pool = s((POOL, kv, PS, HD))
    return {
        "flash_prefill": (
            flash_prefill,
            (s((1, T, h, HD)), arena, arena, s((1, T), jnp.int32), scalar, scalar),
        ),
        "flash_decode": (
            flash_decode,
            (s((B, h, HD)), arena, arena, s((B,), jnp.int32), scalar, scalar),
        ),
        "fused_paged_flash_prefill": (
            fused_paged_flash_prefill,
            (s((1, T, h, HD)), pool, pool, s((1, NB), jnp.int32), s((1, T), jnp.int32)),
        ),
        "fused_paged_flash_decode": (
            fused_paged_flash_decode,
            (s((B, h, HD)), pool, pool, s((B, NB), jnp.int32), s((B,), jnp.int32)),
        ),
    }[kernel]


@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize(
    "kernel",
    [
        "flash_prefill",
        "flash_decode",
        "fused_paged_flash_prefill",
        "fused_paged_flash_decode",
    ],
)
def test_kernel_compiles_for_v5e(v5e, kernel, heads):
    h, kv = HEADS[heads]
    fn, args = _kernel_args(kernel, h, kv, SingleDeviceSharding(v5e.devices[0]))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if kernel in ("flash_prefill", "flash_decode"):
        # the stack is read where it lies: no slice, transpose or relayout
        # of a layer (2 MB a lane here) stands beside the kernel
        layer_bytes = B * S * kv * HD * 2
        assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes // 8


@pytest.mark.parametrize("t", [1, T], ids=["decode", "prefill"])
def test_meshed_flash_compiles_for_v5e_2x2(v5e, t):
    """The tp=4 engine's attention: the dense kernels per device under
    shard_map on the described 2x2 mesh, compiled (not interpreted)."""
    h, kv = HEADS["one-chip"]
    mesh = Mesh(np.array(v5e.devices).reshape(4, 1), axis_names=("tp", "ep"))
    heads = NamedSharding(mesh, P(None, None, "tp", None))
    stack = NamedSharding(mesh, P(None, None, None, "tp", None))
    b = B if t == 1 else 1
    args = (
        jax.ShapeDtypeStruct((b, t, h, HD), jnp.bfloat16, sharding=heads),
        jax.ShapeDtypeStruct((LAYERS, b, S, kv, HD), jnp.bfloat16, sharding=stack),
        jax.ShapeDtypeStruct((LAYERS, b, S, kv, HD), jnp.bfloat16, sharding=stack),
        jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=NamedSharding(mesh, P())),
        None,  # no block table
        jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
        None,  # no slot: the batch is the arena's rows
    )
    compiled = jax.jit(make_meshed_cache_attention(mesh)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# the sorted grouped MoE FFN (ISSUE 29) at the six published expert shapes the
# benchmark serves, int8 with bfloat16 scales as stored: (layers of the stack,
# experts held, experts, d, F, top-k, the gate, rows of the cell's mixed launch)
EXPERT_SHAPES = {
    "mixtral": (6, 8, 8, 4096, 14336, 2, "silu", 264),
    "olmoe": (16, 64, 64, 2048, 1024, 8, "silu", 272),
    "smallthinker": (8, 16, 64, 2560, 768, 6, "relu", 264),
    "mistral4": (4, 32, 128, 4096, 2048, 4, "silu", 272),
    "kimi": (8, 32, 256, 2304, 1024, 8, "silu", 320),
    "laguna": (8, 32, 256, 2048, 512, 8, "silu", 264),
}


@pytest.mark.parametrize("rows", [128, "launch", 512, 1024])
@pytest.mark.parametrize("shape", sorted(EXPERT_SHAPES))
def test_sorted_moe_ffn_compiles_for_v5e(v5e, shape, rows):
    """Routing and the Mosaic grouped FFN over the stacked int8 experts with
    the layer as a scalar, a chip's share of the experts where the cell serves
    one, at the prefill buckets and at the cell's own mixed launch (256 rows +
    its lanes: a row count that is no multiple of 128). The kernel is there
    with ``x [N, d]`` and the float32 ``[N, d]`` accumulator resident in its
    VMEM (the 1,024-row bucket at d = 4,096 included: 8 + 16 MB beside 40 MB of
    weight blocks), and what the program holds in HBM beside it is under ONE
    such accumulator plus the routing's tables: nothing with ``sorted_rows``
    rows (before PR 53 three row buffers of that many), no slice, relayout or
    dequantised copy of the experts, no scale stack converted or relaid."""
    layers, e, total, d, f, k, act, launch = EXPERT_SHAPES[shape]
    rows = launch if rows == "launch" else rows
    where = SingleDeviceSharding(v5e.devices[0])

    def s(sh, dt):
        return jax.ShapeDtypeStruct(sh, dt, sharding=where)

    experts = {
        name: (s((layers, e) + wsh, jnp.int8), s((layers, e, 1, wsh[1]), jnp.bfloat16))
        for name, wsh in (("w_gate", (d, f)), ("w_up", (d, f)), ("w_down", (f, d)))
    }
    held = None if e == total else (e, total)
    fn = lambda x, g, c, ex, l: sorted_moe_ffn(x, g, c, ex, l, kernel=True, held=held, act=act)  # noqa: E731
    compiled = (
        jax.jit(fn)
        .lower(s((rows, d), jnp.bfloat16), s((rows, k), jnp.bfloat16), s((rows, k), jnp.int32),
               experts, s((), jnp.int32))
        .compile()
    )
    text = compiled.as_text()
    assert "moe_grouped_ffn" in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < rows * d * 4 + (1 << 20), (temp, rows * d * 4)
    m = sorted_rows(rows, e, k, row_tile(rows, total, k))
    assert m > rows and _stack_or_buffer_sized(text, m) == []


def _stack_or_buffer_sized(text: str, buffer_rows: int, moe_layers: int = 0, held: int = 0) -> list:
    """Instructions of a compiled program that MAKE an array with
    ``buffer_rows`` rows (the row buffer the sorted MoE FFN laid out in HBM
    before PR 53, sized for the worst routing) or a whole expert scale stack
    (``[L, E held, 1, ·]`` or ``[L, E held, ·]`` in bfloat16 or float32: a
    convert, a copy into another layout or memory, a ``ConcatBitcast`` of
    slices), anywhere in it — in the layer body such an instruction runs in
    every layer. Passing a stack on (a parameter, a tuple's element, a
    bitcast) makes nothing. ``moe_layers = 0``: the buffer alone (a program
    with no layer loop may fetch a stack once, whole, and loses nothing)."""
    found = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(", ln)
        if not m or m.group(4) in ("parameter", "get-tuple-element", "bitcast"):
            continue
        name, dtype, dims, op = m.groups()
        dims = dims.split(",")
        stack = dtype in ("bf16", "f32") and dims[:2] == [str(moe_layers), str(held)] and len(dims) in (3, 4)
        if dims[0] == str(buffer_rows) or stack:
            found.append(f"{name} = {dtype}[{','.join(dims)}] {op}")
    return found


def _served_shapes(cfg, where):
    """(the K/V block's parameters as served — int8 matmul weights with bf16
    scales — as shapes placed on the described device, the placer)."""
    from agentainer_tpu.engine.quant import _QUANT_KEYS

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=where)

    def quantised(tree):
        out = {}
        for key, val in tree.items():
            if isinstance(val, dict):
                out[key] = quantised(val)
            elif key in _QUANT_KEYS:
                scale = val.shape[:-2] + (1, val.shape[-1])
                out[key] = QTensor(
                    jax.ShapeDtypeStruct(val.shape, jnp.int8, sharding=where),
                    jax.ShapeDtypeStruct(scale, jnp.bfloat16, sharding=where),
                )
            else:
                out[key] = place(val)
        return out

    return quantised(jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))), place


def test_prefill_over_the_cut_holds_no_expert_sized_temporary_on_v5e(v5e, monkeypatch):
    """A whole prefill step of 256 rows against the arena, OLMoE's block at
    published widths (two layers of it), int8, the program steered to its
    chip branch (the process's backend is the CPU; the kernels are chosen by
    ``jax.default_backend()``): the compiled step reads the expert stack in
    place, 1.2 MB of temporaries in all. The all-experts form of the same
    step writes what the traces showed as ``copy.53`` and
    ``constant_dynamic-slice_fusion.6`` — the layer's int8 ``w_down`` sliced
    out of the stack and relaid with its last two dims swapped — and a bf16
    copy of each of the three matrices beside its einsum: two layer
    matrices' worth of live temporaries (270 MB; 1.0 GB at Mixtral's
    shape)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import dataclasses
    from functools import partial

    from agentainer_tpu.models.llama import _moe_mlp

    cfg = dataclasses.replace(get_config("olmoe-1b-7b"), n_layers=2, name="olmoe-2l")
    where = SingleDeviceSharding(v5e.devices[0])

    params, place = _served_shapes(cfg, where)
    cache = jax.tree.map(place, jax.eval_shape(lambda: KVCache.create(cfg, 16, 2048)))
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32, sharding=where)
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=where)

    def step(moe_impl, params, cache, slot, tokens, positions):
        return forward(params, cfg, tokens, positions, cache, slot=slot, moe_impl=moe_impl)

    def temp_bytes(moe_impl):
        fn = jax.jit(partial(step, moe_impl), donate_argnums=(1,))
        return fn.lower(params, cache, slot, tokens, tokens).compile().memory_analysis().temp_size_in_bytes

    layer_matrix = cfg.n_experts * cfg.dim * cfg.ffn_dim  # one int8 matrix of a layer: 134 MB
    sorted_temp = temp_bytes(None)
    einsum_temp = temp_bytes(partial(_moe_mlp, cfg=cfg))
    assert sorted_temp < layer_matrix // 16, sorted_temp
    assert einsum_temp > layer_matrix, einsum_temp


@pytest.mark.parametrize("model, lanes", [("olmoe-1b-7b", 16), ("mixtral-8x7b", 8)])
def test_mixed_step_reads_weights_and_arena_in_place_on_v5e(v5e, monkeypatch, model, lanes):
    """The mixed step (ISSUE 31) at published widths, two layers, int8, the
    lanes the benchmark's configurations serve: a 256-row chunk at arena row
    ``slot`` and one row for every lane through ONE layer loop. The chip's
    compiler accepts it with the three kernels side by side (``flash_prefill``
    for the chunk, ``flash_decode`` for the lanes, one ``moe_grouped_ffn`` for
    all 256 + B rows: over the 121-row cut), adds no copy of the arena (four
    scatters into the donated stacks, both groups' before either read) and
    holds about 2 MB of temporaries: nothing the size of a layer's experts
    or of an arena layer."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import dataclasses

    cfg = dataclasses.replace(get_config(model), n_layers=2, name=model + "-2l")
    where = SingleDeviceSharding(v5e.devices[0])

    params, place = _served_shapes(cfg, where)
    cache = jax.tree.map(place, jax.eval_shape(lambda: KVCache.create(cfg, lanes, 2048)))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=where)  # noqa: E731

    def step(params, cache, slot, tokens, positions, last, lane_tok, lane_pos):
        return forward(
            params, cfg, tokens, positions, cache, slot=slot, lanes=(lane_tok, lane_pos), last=last
        )

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, ints(), ints(1, 256), ints(1, 256), ints(), ints(lanes, 1), ints(lanes, 1)
    ).compile()
    text = compiled.as_text()
    arena = ",".join(str(d) for d in cache.k.shape)
    copies = [ln for ln in text.splitlines() if re.search(rf"= \w+\[{arena}\]", ln) and " copy(" in ln]
    assert not copies, copies[:2]
    assert text.count("tpu_custom_call") == 3, text.count("tpu_custom_call")
    layer_matrix = cfg.n_experts * cfg.dim * cfg.ffn_dim  # one int8 expert matrix of a layer
    arena_layer = 2 * math.prod(cache.k.shape[1:])  # a layer of the bf16 K stack
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < min(layer_matrix, arena_layer) // 16, temp
    # ... nor of the grouped FFN's plan for the worst routing (6,208 rows for OLMoE's 272)
    assert _stack_or_buffer_sized(text, sorted_rows(256 + lanes, cfg.n_experts, cfg.experts_per_token)) == []


def test_decode_step_with_the_clamped_map_copies_no_arena_on_v5e(v5e, monkeypatch):
    """The decode step of ``olmoe-1b-7b-1chip`` (16 lanes of 2,048, int8, two
    layers of it) since ``flash_decode``'s K/V index map reads the lanes'
    positions (ISSUE 33) and a parked lane is seen at row 0: the chip's
    compiler takes the data-dependent map, the donated arena still rides in
    the layer loop's carry (one scatter a stack, no arena-sized copy, no
    layer sliced out) and the step holds no temporary the size of a layer."""
    from agentainer_tpu.analysis.hlo_contracts import ArenaRidesInCarry, check

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import dataclasses

    lanes = 16
    cfg = dataclasses.replace(get_config("olmoe-1b-7b"), n_layers=2, name="olmoe-2l")
    where = SingleDeviceSharding(v5e.devices[0])
    params, place = _served_shapes(cfg, where)
    cache = jax.tree.map(place, jax.eval_shape(lambda: KVCache.create(cfg, lanes, 2048)))
    rows = jax.ShapeDtypeStruct((lanes, 1), jnp.int32, sharding=where)

    def step(params, cache, tokens, positions):
        return forward(params, cfg, tokens, positions, cache)

    lowered = jax.jit(step, donate_argnums=(1,)).lower(params, cache, rows, rows)
    arena = tuple(cache.k.shape)
    check(lowered.as_text(), ArenaRidesInCarry(arena=arena, rows=(lanes, 1) + arena[3:], loops=1))
    compiled = lowered.compile()
    text = compiled.as_text()
    dims = ",".join(str(d) for d in arena)
    copies = [ln for ln in text.splitlines() if re.search(rf"= \w+\[{dims}\]", ln) and " copy(" in ln]
    assert not copies, copies[:2]
    assert text.count("tpu_custom_call") == 1, text.count("tpu_custom_call")
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * math.prod(arena[1:]) // 16


def _hybrid_case(model: str, where):
    """``kimi-5l`` (Kimi-Linear's dense layer and one period, K K K M K, 8
    lanes of 1024) or ``olmo-hybrid-4l`` (Olmo-Hybrid's one period, L L L F, 8
    lanes of 4096), the served shares whole (``mistral4-9l``, ``kimi-27l``,
    ``olmo-hybrid-32l``: their cells' layers, vocabulary and lanes), at
    published widths, int8 as served, the kernels on: the configuration, the
    abstract parameters and cache placed on ``where``, the plan, and the
    decode, prefill and mixed steps as the engine jits them."""
    import dataclasses

    from jax import lax

    from agentainer_tpu.engine.quant import synthetic_quantized_params
    from agentainer_tpu.models import hybrid
    from agentainer_tpu.models.configs import kimi_linear_kinds, olmo_hybrid_kinds, solar_open2_kinds
    from agentainer_tpu.models.llama import init_cache

    if model == "kimi-5l":
        lanes, seq = 8, 1024
        cfg = dataclasses.replace(
            get_config("kimi-linear-48b"), n_layers=5, layer_kinds=kimi_linear_kinds(27)[:5],
            experts_held=32, vocab_size=8192, name=model,
        )
    elif model == "kimi-27l":
        # the served share whole: 27 layers, 32 of 256 experts, the whole
        # vocabulary, 64 lanes of 4,096 (benchmark/configs/kimi-linear-48b-ep8-1chip.json)
        lanes, seq = 64, 4096
        cfg = dataclasses.replace(get_config("kimi-linear-48b"), experts_held=32, name=model)
    elif model == "olmo-hybrid-32l":
        # the served model whole: 8 lanes of 4,096 (benchmark/configs/olmo-hybrid-7b-1chip.json)
        lanes, seq = 8, 4096
        cfg = dataclasses.replace(get_config("olmo-hybrid-7b"), name=model)
    elif model == "mistral4-9l":
        # the served share whole: 9 layers, 32 of 128 experts, the whole
        # vocabulary, 16 lanes of 16,384 (benchmark/configs/mistral-small-4-119b-ep4-1chip.json)
        lanes, seq = 16, 16_384
        cfg = dataclasses.replace(
            get_config("mistral-small-4-119b"), n_layers=9, layer_kinds=("mla",) * 9, experts_held=32,
            max_seq_len=seq, name=model,
        )
    elif model == "sala-32l":
        # the served model whole: all 32 layers, the whole vocabulary, 8 lanes
        # of 49,152 (benchmark/configs/minicpm-sala-9b-1chip.json)
        lanes, seq = 8, 49_152
        cfg = dataclasses.replace(get_config("minicpm-sala"), max_seq_len=seq, name=model)
    elif model == "solar-8l":
        # the served share whole: layers 0-7 (G K K K G K K K), 40 of 320
        # experts, the whole vocabulary, 64 lanes of 4,096
        # (benchmark/configs/solar-open2-250b-ep8-1chip.json)
        lanes, seq = 64, 4096
        cfg = dataclasses.replace(
            get_config("solar-open2"), n_layers=8, layer_kinds=solar_open2_kinds(8), experts_held=40,
            max_seq_len=seq, name=model,
        )
    elif model == "laguna-40l":
        # the served share whole: 40 layers, 32 of 256 experts, the whole
        # vocabulary, 8 lanes of 16,384 (benchmark/configs/laguna-xs2-33b-ep8-1chip.json)
        lanes, seq = 8, 16_384
        cfg = dataclasses.replace(get_config("laguna-xs.2"), experts_held=32, max_seq_len=seq, name=model)
    else:
        lanes, seq = 8, 4096
        cfg = dataclasses.replace(
            get_config("olmo-hybrid-7b"), n_layers=4, layer_kinds=olmo_hybrid_kinds(4), n_dense_layers=4,
            vocab_size=8192, name=model,
        )
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=where)  # noqa: E731
    params = jax.tree.map(place, jax.eval_shape(lambda: synthetic_quantized_params(cfg, jnp.bfloat16)))
    ring = {}
    if cfg.n_window:
        # the engine's ring (``llama.ring_plan`` where the kernels serve): a
        # chunk of 256 on top of the window, whole K/V blocks
        from agentainer_tpu.ops.pallas_attention import ring_block

        ring = {"launch_rows": 256, "block": ring_block(cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16)}
    cache = jax.tree.map(place, jax.eval_shape(lambda: init_cache(cfg, lanes, seq, jnp.bfloat16, live=False, **ring)))
    plan = hybrid.plan_hybrid(cfg, use_pallas=True)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=where)  # noqa: E731

    def decode_n(params, cache, tokens, positions):
        def one(carry, _):
            tok, pos, cache = carry
            logits, cache = forward(params, cfg, tok[:, None], pos[:, None], cache, cache_attn_impl=plan)
            return (jnp.argmax(logits[:, 0], -1).astype(jnp.int32), jnp.minimum(pos + 1, seq - 1), cache), tok

        (tok, pos, cache), toks = lax.scan(one, (tokens, positions, cache), None, length=4)
        return toks, tok, pos, cache

    def prefill(params, cache, slot, tokens, positions, n_real):
        valid = jnp.arange(tokens.shape[1])[None, :] < n_real
        logits, cache = forward(params, cfg, tokens, positions, cache, cache_attn_impl=plan, slot=slot, valid=valid)
        return logits[0, -1], cache

    def mixed(params, cache, slot, tokens, positions, n_real, lane_tok, lane_pos):
        # ``jit_prefill_with_decode``'s forward: the chunk and one step of every lane
        valid = jnp.arange(tokens.shape[1])[None, :] < n_real
        logits, cache = forward(
            params, cfg, tokens, positions, cache, cache_attn_impl=plan, slot=slot, valid=valid,
            lanes=(lane_tok[:, None], lane_pos[:, None]), last=n_real - 1,
        )
        nxt = jnp.argmax(logits[1:], -1).astype(jnp.int32)
        return logits[0], nxt, jnp.minimum(lane_pos + 1, seq - 1), cache

    steps = {
        "decode": (jax.jit(decode_n, donate_argnums=(1, 2, 3)), (params, cache, i32(lanes), i32(lanes))),
        "prefill": (jax.jit(prefill, donate_argnums=(1,)), (params, cache, i32(), i32(1, 256), i32(1, 256), i32())),
    }
    steps["mixed"] = (
        jax.jit(mixed, donate_argnums=(1, 6, 7)),
        (params, cache, i32(), i32(1, 256), i32(1, 256), i32(), i32(lanes), i32(lanes)),
    )
    return cfg, cache, plan, steps


V5E_USABLE_BYTES = 15.75e9


def _check_mixed_with_a_linear_mixer(compiled, cfg, cache, lanes: int, kernels: list) -> None:
    """What the served-size mixed step of a block WITH a linear mixer (ISSUE
    48) has to show on the chip's compiler: ONE call of each kernel (the state
    kernel over the lanes, the decode attention beside the prefill attention,
    one grouped FFN where the model has experts), every stack carried by the
    layer loop and the 0-or-1-trip loop round its own mixer and by no third
    loop (a second pass over the layers reads the weights again), every leaf
    donated in place with no copy, relayout or padding of a whole stack, the
    head on ``1 + lanes`` rows, and live bytes (arguments + temporaries - what
    is aliased) that fit a v5e's 15.75 GB."""
    text = compiled.as_text()
    calls = re.findall(r'%([a-z_]+)[.\d]* = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert sorted(calls) == sorted(kernels), calls
    whiles = [ln for ln in text.splitlines() if re.search(r"\bwhile\(", ln)]
    stacks = cache.leaves()
    for name, s in stacks.items():
        shape = ",".join(map(str, s.shape))
        assert sum(f"[{shape}]" in ln for ln in whiles) == 2, (name, len(whiles))
        assert not re.search(rf"\[{shape}\][^ ]* (copy|transpose|pad)\(", text), name
    v = cfg.vocab_size
    assert not re.search(rf"\[(1,)?(256|{256 + lanes}),{v}\]", text) and re.search(rf"f32\[{1 + lanes},{v}\]", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(s.size * s.dtype.itemsize for s in stacks.values())
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    print(f"{cfg.name} mixed: args {mem.argument_size_in_bytes / 1e9:.2f} GB, temp {mem.temp_size_in_bytes / 1e9:.3f} GB, "
          f"aliased {mem.alias_size_in_bytes / 1e9:.2f} GB, live {live / 1e9:.2f} GB")
    assert mem.temp_size_in_bytes < 0.25e9 and 10.0e9 < live < V5E_USABLE_BYTES


@pytest.mark.parametrize("step", ["decode", "prefill", "mixed"])
def test_hybrid_step_holds_no_stack_sized_temporary_on_v5e(v5e, monkeypatch, step):
    """Kimi-Linear's block at published widths (the dense layer and one
    period, K K K M K, 8 lanes of 1024), int8 as served, its kernels on: the
    KDA decode kernel updates the layer of the float32 state stack in place
    and the MLA decode and prefill kernels read the latent stack where it lies
    (Mosaic accepts: a ``[bk, 640]`` row block, a ``[8, 128, 128]`` state tile,
    a ``[16 · 32, 640]`` query tile under the prefill kernel's VMEM plan),
    and through the layer scan, the mixers' 0-or-1-trip loops and the step
    scan no stack is copied. Three things this guards were all found by this
    compile and by nothing on the CPU (PR 30): ``lax.cond`` over the mixers
    copied the stack a branch only passed through, every layer (2.7 GB of
    state in each MLA layer at 64 lanes); a 576-wide latent row made the
    chip keep the arena position-minor and relayout it into and out of every
    launch; a conv state ``[.., 3, 12288]`` was padded 42-fold. ``mixed``
    (ISSUE 48): the served share WHOLE (27 layers, 32 of 256 experts, the whole
    vocabulary, 64 lanes of 4,096 as ``kimi.decode`` serves them), the chunk's
    256 rows and the 64 lanes' step in one launch: ``kda_decode`` over the
    lanes beside the chunked rule on the chunk's lane, ``mla_decode`` beside
    ``mla_prefill``, ONE ``moe_grouped_ffn`` for the 320 rows, the 2.7 GB
    state stack updated in place, and the head on 65 rows."""
    if step == "mixed":  # the grouped FFN's kernel is chosen by the backend, which is the CPU here
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    where = SingleDeviceSharding(v5e.devices[0])
    cfg, cache, plan, steps = _hybrid_case("kimi-27l" if step == "mixed" else "kimi-5l", where)
    assert (plan.kda_decode, plan.mla_decode, plan.mla_prefill) == ("pallas_kda_decode", "pallas_mla_decode", "pallas_mla_prefill")
    fn, args = steps[step]
    compiled = fn.lower(*args).compile()
    if step == "mixed":
        assert cache.state.shape == (20, 64, 32, 128, 128) and cache.latent.shape == (7, 64, 4096, 640)
        # the 320 rows reach their tiles inside the kernel: nothing has the 3,552 rows of the worst
        # routing's plan, and none of the 26 MoE layers' scale stacks is relaid, fetched or converted whole
        assert _stack_or_buffer_sized(compiled.as_text(), sorted_rows(320, 32, 8, row_tile(320, 256, 8)), 26, 32) == []
        return _check_mixed_with_a_linear_mixer(
            compiled, cfg, cache, 64, ["kda_decode", "mla_decode", "mla_prefill", "moe_grouped_ffn"]
        )
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "InvertDiagBlocks" not in text  # what a triangular_solve becomes here
    if step == "decode":
        assert "kda_decode" in text and "mla_decode" in text  # the names the roofline readers find
    else:
        # the chunk's scores stay in the kernel's VMEM: no float32 [heads, T, S]
        # buffer (134 MB a layer-chunk at 4,096 positions before PR 38)
        assert "mla_prefill" in text and not re.search(r"f32\[(1,)?32,256,1024\]", text)
    stacks = {name: getattr(cache, name) for name in ("latent", "state", "conv")}
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(s.size * s.dtype.itemsize for s in stacks.values())  # every leaf donated in place
    for name, s in stacks.items():  # and no copy or relayout of a whole stack anywhere in the program
        shape = ",".join(map(str, s.shape))
        assert not re.search(rf"\[{shape}\][^ ]* (copy|transpose)\(", text), name


@pytest.mark.parametrize("step", ["decode", "prefill", "mixed"])
def test_mistral4_step_fits_the_chip_and_reads_the_latent_stack_in_place_on_v5e(v5e, monkeypatch, step):
    """Mistral-Small-4's served share at its REAL size (9 layers, 32 of 128
    experts, the whole vocabulary, 16 lanes of 16,384, int8 as served), its
    kernels on: Mosaic accepts both latent kernels at a row of 320 values
    stored as 384 (``[bk, 384]`` row blocks, a ``[16 · 32, 384]`` query tile;
    Kimi-Linear's 640 is the only width they had run), the rotation in
    adjacent pairs and the query's low-rank pair lower, the latent stack (1.81
    GB) is donated in place and never copied or relaid out, a model with no
    linear kind carries no state through the layer scan, and the step's live
    bytes (arguments + temporaries - what is aliased) fit a v5e's 15.75 GB:
    the configuration file's memory claim. ``mixed`` (ISSUE 41): the chunk's
    256 rows and the 16 lanes' step through ONE layer loop with ONE call of
    each kernel in its body (``mla_prefill`` over the chunk's rows,
    ``mla_decode`` over the lanes', one ``moe_grouped_ffn`` for all 272 rows:
    the held experts are read once), no all-experts einsum of the lanes' rows
    left, and the head on 17 rows: no ``[256, V]`` or ``[272, V]`` logits."""
    if step == "mixed":  # the grouped FFN's kernel is chosen by the backend, which is the CPU here
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, cache, plan, steps = _hybrid_case("mistral4-9l", SingleDeviceSharding(v5e.devices[0]))
    assert (plan.kda_decode, plan.mla_decode, plan.mla_prefill) == ("", "pallas_mla_decode", "pallas_mla_prefill")
    assert cache.state is None and cache.conv is None and cache.latent.shape == (9, 16, 16_384, 384)
    fn, args = steps[step]
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("mla_decode" if step == "decode" else "mla_prefill") in text
    if step != "decode":
        assert not re.search(r"f32\[(1,)?32,256,16384\]", text)  # the chunk's scores stay in VMEM
    if step == "mixed":
        calls = re.findall(r'%([a-z_]+)[.\d]* = [^\n]*custom_call_target="tpu_custom_call"', text)
        assert sorted(calls) == ["mla_decode", "mla_prefill", "moe_grouped_ffn"], calls
        assert len(re.findall(r"\bwhile\(", text)) == 1  # the layer scan and nothing around or beside it
        v = cfg.vocab_size
        assert not re.search(rf"\[(1,)?(256|272),{v}\]", text) and re.search(rf"f32\[17,{v}\]", text)
        # the lanes' all-experts einsum ([16, 32 held, F] activations) is gone with jit_decode_n's body
        assert not re.search(rf"\[(1,)?16,(1,)?32,{cfg.ffn_dim}\]", text)
    mem = compiled.memory_analysis()
    latent = cache.latent.size * cache.latent.dtype.itemsize
    assert mem.alias_size_in_bytes >= latent
    shape = ",".join(map(str, cache.latent.shape))
    assert not re.search(rf"\[{shape}\][^ ]* (copy|transpose)\(", text)
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    print(f"mistral4-9l {step}: args {mem.argument_size_in_bytes / 1e9:.2f} GB, temp {mem.temp_size_in_bytes / 1e9:.2f} GB, "
          f"out {mem.output_size_in_bytes / 1e9:.2f} GB, aliased {mem.alias_size_in_bytes / 1e9:.2f} GB, live {live / 1e9:.2f} GB")
    assert 10.0e9 < live < V5E_USABLE_BYTES
    assert cfg.param_count() > 8.7e9  # the share, not a cut vocabulary


LAGUNA_CONFIG = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark", "configs", "laguna-xs2-33b-ep8-1chip.json")


@pytest.mark.parametrize("step, kernels", [("decode", 2), ("prefill", 3), ("mixed", 5)])
def test_laguna_step_fits_the_chip_and_keeps_every_leaf_in_place_on_v5e(v5e, monkeypatch, step, kernels):
    """Laguna-XS.2's served share at its REAL size (40 layers, 32 of 256
    experts, the whole vocabulary, 8 lanes of 16,384, int8 as served), its
    kernels on: Mosaic accepts ``flash_prefill`` / ``flash_decode`` at 48 / 8
    heads (GQA-6, the full layers' global leaf ``[10, 8, 16384, 8, 128]``) and
    at 64 / 8 with ``window=`` (GQA-8, the sliding layers' ring ``[30, 8, 1024,
    8, 128]``), both kinds in ONE layer loop, each as a loop of 0 or 1 trips
    over the leaf pair it updates; the four leaves (5.37 + 1.01 GB) are
    donated in place and none is copied or relaid out, no weight stack is
    copied, and the step's live bytes fit a v5e's 15.75 GB and are what the
    configuration file states (``compiled_live_bytes``). ``mixed``: the
    chunk's 256 rows and the 8 lanes' step through one layer loop holding one
    call of each kernel a kind (2 + 2) and ONE grouped FFN for all 264 rows;
    the head on 9 rows."""
    import json

    if step != "decode":  # the grouped FFN's kernel is chosen by the backend, which is the CPU here
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, cache, plan, steps = _hybrid_case("laguna-40l", SingleDeviceSharding(v5e.devices[0]))
    assert (plan.full_prefill, plan.swa_decode) == ("pallas:flash_prefill", "pallas:flash_decode")
    assert cache.k.shape == (10, 8, 16_384, 8, 128) and cache.wk.shape == (30, 8, 1024, 8, 128)
    assert cache.state is None and cache.conv is None and cache.latent is None
    fn, args = steps[step]
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    calls = re.findall(r'%([a-z_]+)[.\d]* = [^\n]*custom_call_target="tpu_custom_call"', text)
    want = {"decode": ["flash_decode"] * 2, "prefill": ["flash_prefill"] * 2 + ["moe_grouped_ffn"],
            "mixed": ["flash_decode"] * 2 + ["flash_prefill"] * 2 + ["moe_grouped_ffn"]}[step]
    assert sorted(calls) == want and len(calls) == kernels, calls
    mem = compiled.memory_analysis()
    leaves = {n: a for n, a in cache.leaves().items()}
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize for a in leaves.values())
    for name, a in leaves.items():
        shape = ",".join(map(str, a.shape))
        assert not re.search(rf"\[{shape}\][^ ]* (copy|transpose)\(", text), name
    # no weight stack copied either: the two kinds' projections and the held experts
    for shape in ("10,2048,6144", "30,2048,8192", "30,8192,2048", "39,32,2048,512", "39,32,512,2048"):
        assert not re.search(rf"\[{shape}\][^ ]* (copy|transpose)\(", text), shape
    if step == "mixed":
        v = cfg.vocab_size
        assert not re.search(rf"\[(1,)?(256|264),{v}\]", text) and re.search(rf"f32\[9,{v}\]", text)
        # the MoE branch of the layer body's conditional (PR 53): no array of the plan's 3,104 rows
        # (before: the 0/1 matrix ``[3104, 2112]``, ``x_rows`` and ``y``), and the three scale stacks
        # of the 39 MoE layers passed on as stored (before: three ``ConcatBitcast``s, 15 MB a layer)
        assert sorted_rows(264, 32, 8, row_tile(264, 256, 8)) == 3104
        assert _stack_or_buffer_sized(text, 3104, 39, 32) == []
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    print(f"laguna-40l {step}: args {mem.argument_size_in_bytes / 1e9:.2f} GB, temp {mem.temp_size_in_bytes / 1e9:.2f} GB, "
          f"out {mem.output_size_in_bytes / 1e9:.2f} GB, aliased {mem.alias_size_in_bytes / 1e9:.2f} GB, live {live / 1e9:.2f} GB")
    assert 12.0e9 < live < V5E_USABLE_BYTES
    with open(LAGUNA_CONFIG) as f:
        stated = json.load(f)["memory"]["compiled_live_bytes"][step]
    assert abs(live - stated) < 0.02 * stated, (live, stated)
    assert abs(cfg.param_count() - 5_961_517_056) == 0  # the share: 5.96 GB of int8 weights


SALA_CONFIG = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark", "configs", "minicpm-sala-9b-1chip.json")


@pytest.mark.parametrize("step", ["decode", "prefill", "mixed"])
def test_minicpm_sala_step_fits_the_chip_and_keeps_every_leaf_in_place_on_v5e(v5e, step):
    """MiniCPM-SALA whole at its REAL size (32 layers, the whole vocabulary, 8
    lanes of 49,152, int8 as served): the step programs compile for a v5e, the
    four leaves (``k``, ``v`` 3.22 GB, the pooled keys 0.10 GB, the lightning
    state 0.40 GB) are donated in place and none is copied or relaid out, no
    weight stack is copied, and the step's live bytes fit a v5e's 15.75 GB and
    are what the configuration file states (``compiled_live_bytes``). A
    lane's step takes ``flash_decode`` at 32 / 2 heads under ``dense_len`` and
    ``sparse_decode`` past it, which copies the listed blocks itself: no gather
    of the 65,536 chosen rows is left; a chunk's mask and lightning are XLA's."""
    import json

    cfg, cache, plan, steps = _hybrid_case("sala-32l", SingleDeviceSharding(v5e.devices[0]))
    assert plan.sparse_decode == "pallas:flash_decode+pallas:sparse_decode" and plan.lightning_decode == "xla_step"
    assert cache.k.shape == (8, 8, 49_152, 2, 128) and cache.ck.shape == (8, 8, 3072, 2, 128)
    assert cache.state.shape == (24, 8, 32, 128, 128) and cache.conv is None and cache.latent is None
    fn, args = steps[step]
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    calls = re.findall(r'%([a-z_]+)[.\d]* = [^\n]*custom_call_target="tpu_custom_call"', text)
    lanes_step = ["flash_decode", "sparse_decode"]
    assert sorted(calls) == {"decode": lanes_step, "prefill": [], "mixed": lanes_step}[step], calls
    assert "bf16[65536,128]" not in text and not re.search(r"bf16\[8,2,4096,128\][^ ]* gather\(", text)
    mem = compiled.memory_analysis()
    leaves = dict(cache.leaves())
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize for a in leaves.values())
    for name, a in leaves.items():
        shape = ",".join(map(str, a.shape))
        assert not re.search(rf"\[{shape}\][^ ]* (copy|transpose)\(", text), name
    for shape in ("8,4096,4096", "24,4096,4096", "32,4096,16384", "32,16384,4096"):
        assert not re.search(rf"\[{shape}\][^ ]* (copy|transpose)\(", text), shape
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    print(f"sala-32l {step}: args {mem.argument_size_in_bytes / 1e9:.2f} GB, temp {mem.temp_size_in_bytes / 1e9:.2f} GB, "
          f"out {mem.output_size_in_bytes / 1e9:.2f} GB, aliased {mem.alias_size_in_bytes / 1e9:.2f} GB, live {live / 1e9:.2f} GB")
    assert 13.0e9 < live < V5E_USABLE_BYTES
    if os.path.exists(SALA_CONFIG):
        with open(SALA_CONFIG) as f:
            stated = json.load(f)["memory"]["compiled_live_bytes"][step]
        assert abs(live - stated) < 0.02 * stated, (live, stated)
    assert cfg.param_count() == 9_476_833_280 + 373_504  # 9.48 GB of int8 weights


SOLAR_CONFIG = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark", "configs", "solar-open2-250b-ep8-1chip.json")


@pytest.mark.parametrize("step", ["decode", "prefill", "mixed"])
def test_solar_open2_step_fits_the_chip_and_keeps_every_leaf_in_place_on_v5e(v5e, monkeypatch, step):
    """Solar-Open2's served share at its REAL size (layers 0-7, 40 of 320
    experts, the whole vocabulary of 196,608, 64 lanes of 4,096, int8 as
    served): ``jit_decode_n``, ``jit_prefill`` and ``jit_prefill_with_decode``
    compile for a v5e with the kernels the plan names, a kind at a time:
    ``kda_decode`` at 64 heads on the ``[6, 64, 64, 128, 128]`` float32 state
    (1.61 GB) where it lies, ``flash_decode`` / ``flash_prefill`` at 64 query
    heads over 8 stored K/V heads without rotary embedding, the grouped FFN
    over 40 held experts (the first held count that is no multiple of 16) past
    the MoE cut. The four leaves are donated and aliased and none is copied,
    transposed or padded; the logits are ``[64, 196608]`` (``[65, …]`` mixed),
    never a chunk's 256 rows; and the step's live bytes fit a v5e's 15.75 GB
    and are what the configuration file states (``compiled_live_bytes``)."""
    import json

    if step != "decode":  # the grouped FFN's kernel is chosen by the backend, which is the CPU here
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, cache, plan, steps = _hybrid_case("solar-8l", SingleDeviceSharding(v5e.devices[0]))
    assert plan.kinds() == {"kda": ("xla_chunked", "pallas_kda_decode"), "full": ("pallas:flash_prefill", "pallas:flash_decode")}
    assert cache.state.shape == (6, 64, 64, 128, 128) and cache.state.dtype == jnp.float32
    assert cache.k.shape == (2, 64, 4096, 8, 128) and cache.conv.shape == (6, 64, 3 * 24576) and cache.latent is None
    fn, args = steps[step]
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    calls = re.findall(r'%([a-z_]+)[.\d]* = [^\n]*custom_call_target="tpu_custom_call"', text)
    want = {"decode": ["flash_decode", "kda_decode"], "prefill": ["flash_prefill", "moe_grouped_ffn"],
            "mixed": ["flash_decode", "flash_prefill", "kda_decode", "moe_grouped_ffn"]}[step]
    assert sorted(calls) == want, calls
    mem = compiled.memory_analysis()
    leaves = dict(cache.leaves())
    assert set(leaves) == {"k", "v", "state", "conv"}
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize for a in leaves.values())
    for name, a in leaves.items():
        shape = ",".join(map(str, a.shape))
        assert not re.search(rf"\[{shape}\][^ ]* (copy|transpose|pad)\(", text), name
    # no weight stack copied either: the KDA projections, the gate, the held experts, the head
    for shape in ("6,4096,24576", "6,8192,4096", "2,4096,8192", "2,8192,4096", "8,40,4096,1280", "8,40,1280,4096", "4096,196608"):
        assert not re.search(rf"\[{shape}\][^ ]* (copy|transpose)\(", text), shape
    v = cfg.vocab_size
    if step != "prefill":  # the head on the lanes' rows (and the chunk's last): never the chunk's 256
        assert not re.search(rf"\[(1,)?(256|{256 + 64}),{v}\]", text)
        assert re.search(rf"f32\[{64 + (step == 'mixed')},{v}\]", text)
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    print(f"solar-8l {step}: args {mem.argument_size_in_bytes / 1e9:.2f} GB, temp {mem.temp_size_in_bytes / 1e9:.2f} GB, "
          f"out {mem.output_size_in_bytes / 1e9:.2f} GB, aliased {mem.alias_size_in_bytes / 1e9:.2f} GB, live {live / 1e9:.2f} GB")
    assert 11.0e9 < live < V5E_USABLE_BYTES
    if os.path.exists(SOLAR_CONFIG):
        with open(SOLAR_CONFIG) as f:
            stated = json.load(f)["memory"]["compiled_live_bytes"][step]
        assert abs(live - stated) < 0.02 * stated, (live, stated)
    assert cfg.param_count() == 7_824_662_144  # 7.82 GB of int8 weights


@pytest.mark.parametrize("step", ["decode", "prefill", "mixed"])
def test_olmo_hybrid_step_copies_no_stack_and_pads_no_arena_on_v5e(v5e, step):
    """Olmo-Hybrid's block at published widths (one period, L L L F, 8 lanes
    of 4096 as served), int8, its kernels on. The GDN decode kernel updates
    the layer of the float32 state stack in place — Mosaic accepts the ``[96,
    1920]`` block of a ``[96, 5760]`` lane tile, whole (8, 128) tiles where
    ``[30, 96, 192]`` would pad every head's 192 lanes to 256 — and the dense
    flash kernels read the K/V leaf where it lies, STORED with 32 heads for
    the model's 30: with 30 the chip keeps the arena position-minor and copies
    it whole into the kernels' layout before each call (0.54 GB of temporaries
    in this one-full-layer program, found by this compile). Here no stack is
    copied, transposed or padded, every leaf is donated in place, and the
    temporaries are a few megabytes. ``mixed`` (ISSUE 48): the served model
    WHOLE (32 layers, the whole vocabulary, 8 lanes of 4,096 as
    ``olmo-hybrid.sessions`` serves them), the chunk's 256 rows and the 8
    lanes' step in one launch: ``gdn_decode`` over the lanes beside the
    chunked rule on the chunk's lane, ``flash_decode`` beside
    ``flash_prefill``, and the head on 9 rows."""
    where = SingleDeviceSharding(v5e.devices[0])
    cfg, cache, plan, steps = _hybrid_case("olmo-hybrid-32l" if step == "mixed" else "olmo-hybrid-4l", where)
    assert (plan.gdn_decode, plan.full_decode) == ("pallas_gdn_decode", "pallas:flash_decode")
    fn, args = steps[step]
    compiled = fn.lower(*args).compile()
    if step == "mixed":
        assert cache.state.shape == (24, 8, 96, 5760) and cache.k.shape == (8, 8, 4096, 32, 128)
        return _check_mixed_with_a_linear_mixer(compiled, cfg, cache, 8, ["flash_decode", "flash_prefill", "gdn_decode"])
    text = compiled.as_text()
    if step == "decode":
        assert "gdn_decode" in text and text.count("tpu_custom_call") == 2  # the state kernel and flash_decode
    else:
        assert text.count("tpu_custom_call") == 1  # flash_prefill; the chunked delta rule is XLA
        assert "InvertDiagBlocks" not in text  # what a triangular_solve becomes here: the rule is matmuls alone
    stacks = cache.leaves()
    assert cache.k.shape[3] == 32 and set(stacks) == {"k", "v", "state", "conv"}
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(s.size * s.dtype.itemsize for s in stacks.values())  # every leaf donated in place
    for name, s in stacks.items():  # and no copy, relayout or padding of a whole stack anywhere in the program
        shape = ",".join(map(str, s.shape))
        assert not re.search(rf"\[{shape}\][^ ]* (copy|transpose|pad)\(", text), name
    arena_layer = 2 * math.prod(cache.k.shape[1:])  # one layer of the bf16 K stack: 268 MB
    assert mem.temp_size_in_bytes < arena_layer // 4, mem.temp_size_in_bytes


def _computations(text: str) -> dict:
    """``{name: [instruction lines]}`` of a compiled module's text."""
    comps, name = {}, None
    for ln in text.splitlines():
        m = re.match(r"(?:ENTRY )?%([^ ]+) \(.*\{$", ln)
        if m:
            name = m.group(1)
            comps[name] = []
        elif ln.startswith("}"):
            name = None
        elif name is not None and ln.strip():
            comps[name].append(ln)
    return comps


def _called(ln: str) -> list:
    """The computations an instruction line names: a fusion's, a loop's body
    and condition, a conditional's branches."""
    names = re.findall(r"(?:calls|to_apply|body|condition)=%([^ ,)}]+)", ln)
    for group in re.findall(r"branch_computations=\{([^}]*)\}", ln):
        names += [b.strip().lstrip("%") for b in group.split(",")]
    return names


def _reached(comps: dict, start, stop=()) -> set:
    """The computations those of ``start`` run, themselves included, not
    entering those of ``stop``."""
    seen, todo = set(), list(start)
    while todo:
        c = todo.pop()
        if c not in seen and c not in stop:
            seen.add(c)
            todo += [name for ln in comps.get(c, ()) for name in _called(ln)]
    return seen


def _work_lifted_out_of_a_kinds_loop(text: str, leaves: dict, rows: int, own: dict) -> list:
    """What a two-kind step program compiled for the chip runs OUTSIDE the
    0-or-1-trip loop its source put it in (``models/hybrid.py`` ``of_kind``).

    The kinds' loops are the innermost ``while``s that carry a cache leaf, the
    layer scan's body the one computation that holds both. An instruction of
    that body whose ``op_name`` lies under a kind's loop (``<the loop's>/body/``)
    was lifted there by the compiler and runs in every layer, whatever the
    trip count: reported where it is a matmul (a ``convolution`` or ``dot``,
    alone or fused) or makes an ``s8[...]`` value (a slice of the kind's weight
    stack, copied). ``own``: ``{kind: widths}``, input projections only that
    kind has; ``f32[rows, width]`` may appear in ONE kind's loop and nowhere
    else in the layer's work (the FFN's branches apart: Laguna's dense layer
    is 8192 wide like its window kind's queries)."""
    comps = _computations(text)
    shapes = [f"[{','.join(map(str, a.shape))}]" for a in leaves.values()]
    whiles = [
        (c, ln, re.search(r"body=%([^ ,)]+)", ln).group(1))
        for c, lines in comps.items() for ln in lines
        if re.search(r" while\(", ln) and any(s in ln for s in shapes)
    ]
    holders = {c for c, _, _ in whiles}
    kinds = [(c, ln, body) for c, ln, body in whiles if body not in holders]
    assert len(kinds) == 2 and len({c for c, _, _ in kinds}) == 1, [ln[:80] for _, ln, _ in kinds]
    layer_body = kinds[0][0]
    under = [re.search(r'op_name="([^"]+)"', ln).group(1) + "/body/" for _, ln, _ in kinds]
    found = []
    for ln in comps[layer_body]:
        name = re.search(r'op_name="([^"]+)"', ln)
        if not name or not name.group(1).startswith(tuple(under)):
            continue
        what = ln.strip().split(" = ", 1)[1]
        lines = [ln] + [x for c in _called(ln) for x in comps.get(c, ())]
        if what.startswith("s8[") or any(re.search(r" (convolution|dot)\(", x) for x in lines):
            found.append(f"{what[:48]} <- {name.group(1)}")
    loops = [_reached(comps, [body, re.search(r"condition=%([^ ,)]+)", ln).group(1)]) for _, ln, body in kinds]
    branches = _reached(comps, [b for ln in comps[layer_body] if "branch_computations=" in ln for b in _called(ln)])
    rest = _reached(comps, [layer_body], stop=loops[0] | loops[1] | branches)
    seen_somewhere = False
    for kind, widths in own.items():
        for width in widths:
            value = re.compile(rf"f32\[(1,)?{rows},{width}\]")
            has = lambda cs: any(value.search(ln) for c in cs for ln in comps.get(c, ()))  # noqa: E731
            if has(rest):
                found.append(f"{kind}'s f32[{rows},{width}] in the layer scan's body")
            if has(loops[0]) and has(loops[1]):
                found.append(f"{kind}'s f32[{rows},{width}] in both kinds' loops")
            seen_somewhere |= has(loops[0]) or has(loops[1])
    assert seen_somewhere, own  # the widths are this program's: the check is not blind
    return found


# a served share's lanes (a decode step's rows; the mixed step has 256 more) and
# the input projections ONE kind has: widths no other value of the layer's work shares
TWO_KINDS = {
    "laguna-40l": (8, {"full": [48 * 128], "swa": [64 * 128]}),
    "kimi-27l": (64, {"kda": [3 * 32 * 128], "mla": [32 * 192, 512 + 64]}),
    "olmo-hybrid-32l": (8, {"gdn": [30 * (96 + 96 + 192), 30 * 192]}),
}


@pytest.mark.parametrize("step", ["decode", "mixed"])
@pytest.mark.parametrize("model", sorted(TWO_KINDS))
def test_a_kinds_work_stays_inside_its_loop_on_v5e(v5e, monkeypatch, model, step):
    """The hybrid block with two kinds of mixer, served shares whole (Laguna
    full + window, Kimi-Linear KDA + MLA, Olmo-Hybrid gated delta rule + full),
    the decode step and the mixed step compiled for the described v5e: a
    layer runs, slices and copies only its own kind's projections. Before PR
    51 XLA's loop-invariant code motion lifted both kinds' input projections
    (and a copy of both ``wo`` slices) out of the 0-or-1-trip loops into the
    layer scan's body: 8 to 12 such instructions in each of these six
    programs, run in all 40, 27 or 32 layers (a quarter to a half of them for
    the kind the layer is not). The barrier in ``of_kind`` keeps them in, the
    one after ``full_mixer``'s and ``_mla_query``'s projections keeps the
    compiler from transposing a whole ``wq`` stack instead; nothing the size
    of a weight stack is copied anywhere in the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the grouped FFN's kernel, as on the chip
    cfg, cache, plan, steps = _hybrid_case(model, SingleDeviceSharding(v5e.devices[0]))
    lanes, own = TWO_KINDS[model]
    fn, args = steps[step]
    text = fn.lower(*args).compile().as_text()
    assert _work_lifted_out_of_a_kinds_loop(text, cache.leaves(), 256 + lanes if step == "mixed" else lanes, own) == []
    for kind, stack in args[0].items():  # every mixer kind's large projections, whatever the kind is called
        for name in {"wq", "wo", "wqkv"} & set(stack if isinstance(stack, dict) else ()):
            shape = ",".join(map(str, stack[name].q.shape))
            assert not re.search(rf"s8\[{shape}\][^ ]* (copy|transpose)\(", text), (kind, name)


def test_the_lifted_work_is_found_where_a_loop_lacks_its_barrier(v5e, monkeypatch):
    """The check is not blind: Olmo-Hybrid's decode step traced with the
    barrier as the identity (the parent's program) has both kinds'
    projections and both ``wo`` slices in the layer scan's body."""
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    cfg, cache, plan, steps = _hybrid_case("olmo-hybrid-32l", SingleDeviceSharding(v5e.devices[0]))
    fn, args = steps["decode"]
    text = fn.lower(*args).compile().as_text()
    found = _work_lifted_out_of_a_kinds_loop(text, cache.leaves(), 8, TWO_KINDS["olmo-hybrid-32l"][1])
    assert len(found) >= 9 and any("s8[1,5760,3840]" in f for f in found) and any("f32[8,11520]" in f for f in found), found


def _windowed_case(where, lanes=8, periods=1):
    """SmallThinker's block at published widths (``periods`` whole periods
    G W W W), the chip's share of ep = 4 (16 of 64 experts), int8 as served,
    the two-leaf cache of 8 lanes of 16,384 sized as an engine sizes it for
    chunks of 256 (a ring of 4,608 rows a lane)."""
    import dataclasses

    from agentainer_tpu.models.llama import init_cache, ring_plan

    big = get_config("smallthinker-21b")
    n = 4 * periods
    cfg = dataclasses.replace(
        big, n_layers=n, window_layers=big.window_layers[:n], rope_layers=big.rope_layers[:n],
        experts_held=16, name=f"smallthinker-{n}l",
    )
    params, place = _served_shapes(cfg, where)
    plan = ring_plan(cfg, jnp.bfloat16, 256)
    cache = jax.tree.map(place, jax.eval_shape(lambda: init_cache(cfg, lanes, 16384, jnp.bfloat16, **plan)))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=where)  # noqa: E731

    def decode(params, cache, tokens, positions):
        return forward(params, cfg, tokens, positions, cache)

    def prefill(params, cache, slot, tokens, positions):
        return forward(params, cfg, tokens, positions, cache, slot=slot)

    def mixed(params, cache, slot, tokens, positions, last, lane_tok, lane_pos):
        return forward(params, cfg, tokens, positions, cache, slot=slot, lanes=(lane_tok, lane_pos), last=last)

    steps = {
        "decode": (decode, (params, cache, ints(lanes, 1), ints(lanes, 1))),
        "prefill": (prefill, (params, cache, ints(), ints(1, 256), ints(1, 256))),
        "mixed": (mixed, (params, cache, ints(), ints(1, 256), ints(1, 256), ints(), ints(lanes, 1), ints(lanes, 1))),
    }
    return cfg, plan, cache, steps


@pytest.mark.parametrize("kernel", ["flash_prefill", "flash_decode"])
def test_windowed_kernel_compiles_for_v5e(v5e, kernel):
    """The ring's index maps (a second prefetched bound for prefill, the
    modular block walk) at SmallThinker's shapes: 28 query heads over 4 K/V
    heads of 128 (group 7, a shape no other configuration runs), the 39
    window layers' leaf of 8 lanes x 4,608 rows, window 4,096."""
    where = SingleDeviceSharding(v5e.devices[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    ring, scalar = s((39, B, 4608, 4, HD)), s((), jnp.int32)
    if kernel == "flash_prefill":
        fn = lambda q, k, v, p, lay, slot: flash_prefill(q, k, v, p, lay, slot, window=4096)  # noqa: E731
        args = (s((1, T, 28, HD)), ring, ring, s((1, T), jnp.int32), scalar, scalar)
    else:
        fn = lambda q, k, v, p, lay, slot: flash_decode(q, k, v, p, lay, slot, window=4096)  # noqa: E731
        args = (s((B, 28, HD)), ring, ring, s((B,), jnp.int32), scalar, scalar)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < B * 4608 * 4 * HD * 2 // 8


@pytest.mark.parametrize("step, kernels", [("decode", 2), ("prefill", 3), ("mixed", 5)])
def test_windowed_step_keeps_both_leaves_in_place_on_v5e(v5e, monkeypatch, step, kernels):
    """One period of SmallThinker's block through the ONE layer scan with the
    two kinds of attention as 0-or-1-trip loops over their own leaf: the
    chip's compiler takes the kernels under the loops (``flash_decode`` for
    each kind; ``flash_prefill`` for each kind and the grouped FFN over the
    held experts from 256 rows on; all five in the mixed step), both leaves
    are donated in place, and no copy or relayout of either stands anywhere
    in the program (a ``lax.cond`` over the two kinds would copy the leaf its
    branch passes through: models/hybrid.py found that on the chip)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, plan, cache, steps = _windowed_case(SingleDeviceSharding(v5e.devices[0]))
    assert plan == {"launch_rows": 256, "block": 512}
    assert cache.k.shape == (1, 8, 16384, 4, 128) and cache.wk.shape == (3, 8, 4608, 4, 128)
    fn, args = steps[step]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == kernels, text.count("tpu_custom_call")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(leaf.size * 2 for leaf in cache)
    for leaf in (cache.k, cache.wk):
        shape = ",".join(map(str, leaf.shape))
        assert not re.search(rf"\[{shape}\][^ ]* (copy|transpose)\(", text), shape
    if step != "decode":
        # the chunk's attention is the flash kernel, by name, at the published
        # 28 / 4 x 128: no float32 scores of 28 heads x 256 rows against
        # either leaf's rows stand in the program (ISSUE 49)
        assert "flash_prefill" in text
        assert not re.search(r"f32\[(1,)?28,256,(4608|16384)\]", text)
    # nothing the size of a layer's held experts or of a leaf's layer
    layer_matrix = cfg.n_held * cfg.dim * cfg.ffn_dim
    assert mem.temp_size_in_bytes < min(layer_matrix, 2 * math.prod(cache.wk.shape[1:])) // 8, mem.temp_size_in_bytes


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


@pytest.mark.parametrize("model", ["kimi-5l", "olmo-hybrid-4l"])
def test_hybrid_prefill_inverts_its_triangles_before_the_scan_over_chunks(model):
    """The 256-row prefill program of both hybrid families as traced (no
    compile): nowhere a ``triangular_solve`` (on the chip XLA expands it to an
    ``InvertDiagBlocksLowerTriangular`` custom call that was 27 % of
    Olmo-Hybrid's chunk and half of Kimi's chunked rule; the two compiles above
    look for that name), and the scan over the launch's four chunks of 64
    takes the inverse-applied operands and the query scores as ``xs`` — ``[4,
    1, H, 64, 64]`` among them — and its body holds four matmuls (three
    against the carried state, one against ``U``) and nothing that builds a
    score matrix, a mask or an inverse: no ``exp``, no ``cumsum``, no
    comparison, no inner loop."""
    cfg, _, _, steps = _hybrid_case(model, None)
    fn, args = steps["prefill"]
    eqns = list(_equations(jax.make_jaxpr(fn)(*args).jaxpr))
    assert "triangular_solve" not in {e.primitive.name for e in eqns}
    scores = (4, 1, cfg.kda_heads, 64, 64)
    chunk_scans = [
        e for e in eqns
        if e.primitive.name == "scan" and e.params["length"] == 4 and scores in [v.aval.shape for v in e.invars]
    ]
    assert len(chunk_scans) == 1  # the layer scan traces the linear mixer once
    body = [e.primitive.name for e in _equations(chunk_scans[0].params["jaxpr"].jaxpr)]
    assert body.count("dot_general") == 4, body
    assert not {"exp", "cumsum", "iota", "select_n", "while", "scan", "lt", "ge", "gt", "eq"} & set(body), body
