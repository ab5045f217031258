"""The ``solar_open2`` family through the seam (``families/solar_open2.py``), the
``reason-closed-64`` mix, the cell ``solar.reason`` and the readers ISSUE 57
added, on the CPU at rehearsal widths and on recorded ``/metrics`` documents:
this cell's, and a program's that lacks the new keys (the parent's program
under this PR's benchmark files: every new reader answers ``None`` and none
raises)."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from harness.family import family_of

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
CHILD_ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": os.pathsep.join([REPO, HERE])}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "solar-open2-250b-ep8-1chip"
CELL = "solar.reason"
REDUCED = {"num_hidden_layers", "n_routed_experts", "max_position_embeddings", "torch_dtype"}
ALIASES = ("decode_step_ms", "engine_itl_p50_ms", "batch_occupancy", "device_wait_share", "host_ms_per_req",
           "prefill_dev_share", "state_resets_per_req")
OWN = ("solar_decode_step_roofline", "solar_state_bytes_share")


def solar():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def benchmark_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def reader(name):
    return importlib.import_module("layer_metrics." + name).read


def test_the_file_holds_the_catalogs_published_keys():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
    doc = solar()
    assert doc["source"] == row["source_url"] and doc["family"] == "solar_open2" and doc["model_type"] == "solar_open2"
    changed = {k for k, v in row["config"].items() if doc.get(k, "absent") != v}
    assert changed == REDUCED - {"torch_dtype"}  # every other published key is as published, nested groups whole
    assert set(doc["reduced"]) == REDUCED and all(len(why) > 60 and "published" in why for why in doc["reduced"].values())
    assert (doc["num_hidden_layers"], doc["n_routed_experts"], doc["experts_published"], doc["max_position_embeddings"]) == (8, 40, 320, 4096)
    assert doc["expert_parallel"]["ep"] == 8 and doc["expert_parallel"]["chip"] == 0 and doc["expert_parallel"]["experts"] == "0-39"
    assert "no" in doc["expert_parallel"]["exchange"] and "shared expert" in doc["expert_parallel"]["replicated"]
    # no width is cut and the whole vocabulary is served
    for key in ("hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "moe_intermediate_size",
                "num_experts_per_tok", "vocab_size", "linear_attn_config"):
        assert doc[key] == row["config"][key], key
    entry = next(c for c in benchmark_json()["configs"] if c["name"] == NAME)
    assert set(entry["reduced"]) == REDUCED and entry["source"] == doc["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    for point in ("gqa_gate", "router", "no_qk_norm_no_bias", "kda", "kda_allow_neg_eigval", "intermediate_size", "layer_order"):
        assert len(doc["assumed"][point]) > 60, point
    assert doc["engine_options"] == {"quant": "int8", "synthetic": True, "max_batch": 64, "max_seq": 4096,
                                     "speculative": False, "prefix_cache_bytes": 0}
    assert set(doc["why_engine_options"]) >= {"max_batch", "max_seq", "speculative", "prefix_cache_bytes"}
    live = doc["memory"]["compiled_live_bytes"]
    assert set(live) == {"decode", "prefill", "mixed"} and all(11.0e9 < v < 15.75e9 for v in live.values())
    assert "v5e-8" in doc["stands_for"] and 0.25 * 16e9 < doc["hbm_claim_bytes_per_chip"] <= 16 * 2**30


def test_the_reference_is_plain_and_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "families", "solar_open2_reference.py")) as f:
        text = f.read()
    lines = [ln for ln in text.splitlines() if ln.startswith(("import ", "from "))]
    assert lines == ["from __future__ import annotations", "import jax", "import jax.numpy as jnp"]
    body = text.split('"""', 2)[2]
    assert "agentainer_tpu" not in body and "families." not in body and "bfloat16" not in body and "pallas" not in body
    assert 'jax.default_matmul_precision("highest")' in body and "lax.scan(step" in body  # token by token
    assert "Departures from the published model" in text and "assumed" in text


def test_the_cell_and_its_entries_are_appended_with_closed_lists():
    bench = benchmark_json()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": NAME, "traffic": "reason-closed-64", "chips": 1} and len(cell["why"]) <= 200
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 10 and [c["name"] for c in bench["configs"]].index(NAME) == 8  # after what stood
    mine = [m for m in bench["per_layer"] if m["name"].startswith("solar_")]
    at = [m["name"] for m in bench["per_layer"]].index(mine[0]["name"])
    assert bench["per_layer"][at : at + len(mine)] == mine and at == 86  # one run of entries, after the 86 that stood
    assert {m["name"] for m in mine} >= set(OWN) | {"solar_" + a for a in ALIASES}
    layers = {m["layer"] for m in bench["per_layer"] if not m["name"].startswith("solar_")}
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "req_per_s" and m["layer"] in layers
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    for alias in ALIASES:
        assert reader("solar_" + alias) is reader(alias)
    assert reader("solar_kda_decode_roofline") is reader("kda_decode_roofline")
    # nothing that stood lists the new cell: the accepted entries' lists stay closed
    assert not [m["name"] for m in bench["per_layer"][:86] if CELL in m.get("workloads", [])]


def test_the_traffic_is_the_issues_parameter_for_parameter():
    with open(os.path.join(BENCH, "traffic", "reason-closed-64.json")) as f:
        p = json.load(f)
    assert (p["generator"], p["clients"], p["shared_prefix_tokens"], p["context_limit_tokens"], p["warmup_s"], p["drain_s"]) == (
        "sessions", 64, 0, 2600, 10, 90)
    assert p["turns"] == {"dist": "const", "value": 1} and "think_s" not in p and "later_user_tokens" not in p
    assert p["first_user_tokens"] == {"dist": "lognormal", "median": 384, "sigma": 0.6, "min": 128, "max": 1024}
    assert p["max_tokens"] == {"dist": "uniform", "min": 512, "max": 1536}
    gen = importlib.import_module("generators.sessions")
    for seed in (1, 2**31 + 11):
        stream = gen.sessions(p, seed, seed, "m")
        sessions = [next(stream) for _ in range(256)]
        turns = [s["turns"] for s in sessions]
        assert all(len(t) == 1 for t in turns) and len({s["id"] for s in sessions}) == 256
        prompts = sorted(t[0]["user_tokens"] for t in turns)
        replies = [t[0]["max_tokens"] for t in turns]
        assert 128 <= prompts[0] and prompts[-1] <= 1024 and 350 < prompts[128] < 420  # the median is 384
        assert 512 <= min(replies) and max(replies) <= 1536 and 990 < sum(replies) / 256 < 1060
        assert max(t[0]["context_tokens"] for t in turns) <= 2600 < 4096  # under the served context
        assert len({s["turns"][0]["message"][:64] for s in sessions}) == 256  # unshared text


def test_family_builds_the_programs_config_at_published_sizes():
    import dataclasses

    from agentainer_tpu.models.configs import get_config, solar_open2_kinds

    doc = solar()
    family = family_of(doc)
    cfg = family.model_config(doc)
    big = get_config("solar-open2")
    assert cfg == dataclasses.replace(big, name=NAME, n_layers=8, layer_kinds=solar_open2_kinds(8), experts_held=40,
                                      expert_offset=0, max_seq_len=4096)
    assert cfg.layer_kinds == ("full", "kda", "kda", "kda") * 2 and cfg.n_experts == 320 and cfg.gate_form == "full"
    whole = family.model_config({**doc, "num_hidden_layers": 48, "n_routed_experts": 320, "max_position_embeddings": 1048576})
    assert whole == dataclasses.replace(big, name=NAME)  # the uncut file is the registered model
    assert family.model_config({**doc, "expert_parallel": {"ep": 8, "chip": 3}}).expert_offset == 120
    sizes = family.numerics_sizes(doc)
    assert sizes == {"layers": 5, "prefill": 192, "decode": 8, "cache_len": 256}
    five = family.model_config(doc, n_layers=5)
    assert five.layer_kinds == ("full", "kda", "kda", "kda", "full")  # each kind after the other, both ways
    for wrong in ({"use_rope": True}, {"kda_use_full_proj": True}, {"first_k_dense_replace": 1}, {"use_gqa_gate": False},
                  {"norm_topk_prob": False}, {"linear_attn_config": {**doc["linear_attn_config"], "num_kv_heads": 8}}):
        with pytest.raises(ValueError):
            family.model_config({**doc, **wrong})


def test_a_program_without_the_gate_and_the_plan_fails_the_cell_at_once(monkeypatch):
    """The parent's program under this PR's benchmark files: ``model_config``
    raises before anything is built, so the numerics child exits non-zero in
    seconds and the run prints no result line."""
    from agentainer_tpu.models import configs

    class Parent:  # a ``ModelConfig`` from before ``gate_form``
        pass

    monkeypatch.setattr(configs, "ModelConfig", Parent)
    with pytest.raises(TypeError, match="cannot run KDA beside gated NoPE GQA"):
        family_of(solar()).model_config(solar())


def test_family_arithmetic_against_hand_counts_and_the_programs():
    import jax
    import jax.numpy as jnp

    from agentainer_tpu.models.llama import init_cache

    doc = solar()
    family = family_of(doc)
    cfg = family.model_config(doc)
    lw = family.layer_weight_elements(doc)
    assert lw["kda"] == 4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64 == 137_625_600
    assert lw["full"] == 3 * 4096 * 8192 + 2 * 4096 * 1024 == 109_051_904
    assert lw["expert"] == 3 * 4096 * 1280 == 15_728_640 and lw["moe_fixed"] == 4096 * 320 + 15_728_640
    matrices = 6 * lw["kda"] + 2 * lw["full"] + 8 * (lw["moe_fixed"] + 40 * lw["expert"]) + 2 * 4096 * 196608
    vectors = cfg.param_count() - matrices  # norms, conv filters, A_log, dt_bias, the selection bias
    assert 0 < vectors < 1e6 and cfg.param_count() == 7_824_662_144
    assert family.weight_bytes(doc) == matrices - 4096 * 196608  # a step reads the head, not the embedding
    assert family.experts_chosen(doc, 64) == pytest.approx(40 * (1 - 0.975**64)) and 32.0 < family.experts_chosen(doc, 64) < 32.2
    assert family.experts_chosen(doc, 0) == 0.0 and family.experts_chosen(doc, 10_000) == pytest.approx(40.0)
    assert family.state_bytes_per_lane(doc) == 6 * 64 * 128 * 128 * 4 + 6 * 3 * 24576 * 2 == 25_165_824 + 884_736
    assert family.kv_bytes_per_token(doc) == 2 * 2 * 8 * 128 * 2 == 8192
    assert family.kda_decode_bytes(doc, 64) == 2 * 64 * 64 * 128 * 128 * 4 == 536_870_912  # 4.19 MB a lane, both ways
    assert family.kernel_calls_per_step(doc) == {"kda_decode": 6, "flash_decode": 2}
    cache = jax.eval_shape(lambda: init_cache(cfg, 64, 4096, jnp.bfloat16))
    nbytes = {k: v.size * v.dtype.itemsize for k, v in cache.leaves().items()}
    assert family.cache_bytes(doc) == nbytes and nbytes["state"] == 1_610_612_736 and nbytes["k"] + nbytes["v"] == 2_147_483_648
    resident = cfg.param_count() + sum(nbytes.values())
    assert 11.6e9 < resident < 11.7e9 and resident < 0.75 * 15.75e9  # ISSUE 57's 11.64 GB, before temporaries
    # a step at 64 lanes and 1.3k of context: the issue's 10.0-11.0 GB and its shares
    step = family.decode_step_bytes(doc, 64 * 1300.0, live_lanes=64.0)
    state = 2 * 64 * family.state_bytes_per_lane(doc)
    experts = 8 * family.experts_chosen(doc, 64) * lw["expert"]
    rest = 6 * lw["kda"] + 2 * lw["full"] + 8 * lw["moe_fixed"] + 4096 * 196608
    assert step == pytest.approx(state + experts + rest + 64 * 1300 * 8192) and 10.0e9 < step < 11.0e9
    assert 0.30 < state / step < 0.34 and 0.38 < experts / step < 0.42 and 0.05 < 64 * 1300 * 8192 / step < 0.08
    assert 1.98e9 < rest < 2.0e9
    assert family.decode_step_floor_s(doc, 64 * 1300.0, 819e9, live_lanes=64.0) == pytest.approx(step / 819e9)
    assert 0.0122 < step / 819e9 < 0.0135
    assert family.decode_step_bytes(doc, 0.0) == family.decode_step_bytes(doc, 0.0, live_lanes=64.0)
    # what today's einsum reads of the held experts is more than the yardstick counts
    assert family.weight_bytes(doc) - family.weight_bytes(doc, family.experts_chosen(doc, 64)) > 0.9e9
    assert family.prefill_flops(doc, 256, 512.0) > 2.0 * 256 * (cfg.active_param_count() - 4096 * 196608 - 8 * 7 * lw["expert"])
    assert family.prefill_flops(doc, 256, 512.0, routed=False) > family.prefill_flops(doc, 256, 512.0)


LEDGER = {"jit_decode_n": {"8": {"n": 100, "timed_n": 90, "timed_steps": 720, "device_s": 14.4}}}
RECORDED = {
    "max_batch": 64, "decode_chunk": 8, "decode_steps": 1000, "batch_occupancy": 0.9, "requests_finished": 120,
    "linear": {"kind": "kda", "layers": 6, "heads": 64, "head_dim": 128, "neg_eigval": True, "state_bytes_lane": 25_165_824},
    "cache": {"kinds": ["k", "v", "state", "conv"], "conv_bytes": 64 * 884_736, "state_resets": 130},
    "launches": LEDGER,
}
ZERO = {"max_batch": 64, "decode_chunk": 8, "decode_steps": 0, "batch_occupancy": 0.0, "requests_finished": 0,
        "linear": RECORDED["linear"], "cache": {**RECORDED["cache"], "state_resets": 10},
        "launches": {"jit_decode_n": {"8": {"n": 0, "timed_n": 0, "timed_steps": 0, "device_s": 0.0}}}}
PARENT = {"max_batch": 8, "decode_chunk": 8, "decode_steps": 50, "batch_occupancy": 0.5, "requests_finished": 4,
          "cache": {"kinds": ["kv"]}}
RESPONSES = [{"ok": True, "want_prompt_tokens": 400, "want_completion_tokens": 1000, "context_tokens": 1400}] * 6


def test_readers_on_recorded_documents():
    doc = solar()
    family = family_of(doc)
    cell = {"config": doc, "device": {"platform": "tpu", "kind": "TPU v5e"}}
    args = ([ZERO], [RECORDED], RESPONSES, None, cell)
    assert reader("solar_decode_step_ms")(*args) == pytest.approx(20.0)  # 14.4 s over 720 timed steps
    lanes, context = 0.9 * 64, 400 + 500.0
    floor = family.decode_step_floor_s(doc, lanes * context, 819e9, live_lanes=lanes)
    got = reader("solar_decode_step_roofline")(*args)
    assert got == pytest.approx(100.0 * floor / 0.020) and 40 < got < 70
    on_cpu = {"config": doc, "device": {"platform": "cpu", "kind": "cpu", "rehearsal": True}}
    assert reader("solar_decode_step_roofline")([ZERO], [RECORDED], RESPONSES, None, on_cpu) is None  # no CPU time under a device's name
    share = reader("solar_state_bytes_share")(*args)
    assert share == pytest.approx(2 * lanes * family.state_bytes_per_lane(doc) / family.decode_step_bytes(doc, lanes * context, live_lanes=lanes))
    assert 0.30 < share < 0.36
    longer = [{**r, "want_completion_tokens": 3000} for r in RESPONSES]
    assert reader("solar_state_bytes_share")([ZERO], [RECORDED], longer, None, cell) < share  # it falls as contexts grow
    assert reader("solar_batch_occupancy")(*args) == pytest.approx(0.9)
    assert reader("solar_state_resets_per_req")(*args) == pytest.approx(1.0)
    # the kernel's share from a trace reduced with the kernel's name kept
    trace = {"modules": {"jit_decode_n.1": {"count": 10, "time_s": 1.6}}, "device_ops": [["kda_decode.3", 0.40], ["while.1", 1.0]],
             "counters_before": [dict(ZERO, decode_chunk_hist={"8": 0})], "counters_after": [dict(RECORDED, decode_chunk_hist={"8": 10})]}
    got = reader("solar_kda_decode_roofline")([ZERO], [RECORDED], RESPONSES, trace, cell)
    assert got == pytest.approx(100.0 * 80 * 6 * family.kda_decode_bytes(doc, lanes) / 819e9 / 0.40) and 60 < got < 100


@pytest.mark.parametrize("name", OWN + ("solar_kda_decode_roofline", "solar_decode_step_ms", "solar_state_resets_per_req"))
def test_readers_give_none_where_their_source_is_absent(name):
    """A program without the launch ledger, the ``linear`` block or a per-lane
    state (a parent from before them; another family's cell): every reader
    answers ``None`` and none raises."""
    cell = {"config": solar(), "device": {"platform": "tpu", "kind": "TPU v5e"}}
    trace = {"modules": {"jit_decode_n.1": {"count": 10, "time_s": 0.2}}, "device_ops": [["while.1", 0.2]],
             "counters_before": [dict(PARENT, decode_chunk_hist={"8": 0})], "counters_after": [dict(PARENT, decode_chunk_hist={"8": 4})]}
    assert reader(name)([PARENT], [PARENT], RESPONSES, trace, cell) is None
    assert reader(name)([], [], [], None, cell) is None
    with open(os.path.join(BENCH, "configs", "olmoe-1b-7b-1chip.json")) as f:
        other = {"config": json.load(f), "device": {"platform": "tpu", "kind": "TPU v5e"}}
    if name in OWN:  # a family without the arithmetic
        assert reader(name)([ZERO], [RECORDED], RESPONSES, None, other) is None


def rehearsal_config(tmp_path) -> str:
    doc = {**solar(), **family_of(solar()).REHEARSAL_WIDTHS}
    path = tmp_path / "solar.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_numerics_child_holds_the_program_to_the_familys_own_reference(tmp_path):
    """At rehearsal widths: G K K K G, a prefill of 192 rows (three KDA
    chunks; over the MoE cut) and 8 decode steps against the reference
    computed a layer and a block of the vocabulary at a time."""
    out = subprocess.run([sys.executable, "-m", "benchmark.harness.numerics_child", rehearsal_config(tmp_path), str(2**31 + 5), "--rehearse"],
                         env=CHILD_ENV, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["ok"] and line["config"] == NAME and line["layers"] == 5 and line["positions_compared"] == 40
    assert line["rel_err"] < 1e-4 and line["share_of_positions_within"] == 1.0
    assert line["attention"]["kda_decode"] == "xla_step" and line["attention"]["full_prefill"] == "xla:attention_reference"


def test_the_blockwise_reference_is_the_plain_forward():
    """``families/solar_open2.reference`` (a layer at a time from the int8
    leaves, the head in blocks) gives the logits of the reference module's own
    ``forward`` on the dequantised weights: computing in blocks changes no
    number beyond the order of a concatenation."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from agentainer_tpu.engine.quant import synthetic_quantized_params

    doc = {**solar(), **family_of(solar()).REHEARSAL_WIDTHS}
    family = family_of(doc)
    cfg = family.model_config(doc)
    params = synthetic_quantized_params(cfg, jnp.float32)
    weights, forward = family.reference(params, cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(3, cfg.vocab_size, 48), jnp.int32)
    monkey = family.VOCAB_BLOCK
    family.VOCAB_BLOCK = 200  # three ragged blocks of the 512 columns
    try:
        got = jax.jit(lambda w, t: forward(w, t, lambda x: x))(weights, tokens)
    finally:
        family.VOCAB_BLOCK = monkey
    block = importlib.import_module("families.solar_open2_reference")
    dense = family.dense
    plain = {"embed": dense(params["embed"]), "layers": [family.reference_layer(params, cfg, i) for i in range(cfg.n_layers)],
             "final_norm": dense(params["final_norm"]), "lm_head": dense(params["lm_head"])}
    want = block.forward(
        plain, tokens, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, kda_heads=cfg.kda_heads,
        kda_head_dim=cfg.kda_head_dim, norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token, routed_scale=cfg.moe_scale,
        renormalize=True, neg_eigval=True, expert_offset=cfg.expert_offset)
    assert got.shape == want.shape == (48, 512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_the_start_up_hook_registers_the_family(tmp_path):
    out = subprocess.run(
        [sys.executable, "-c", "from agentainer_tpu.models.configs import get_config; c = get_config('" + NAME + "'); "
         "print(c.layer_kinds.count('kda'), c.n_held, c.n_experts, c.gate_form)"],
        env={**CHILD_ENV, "PYTHONPATH": os.pathsep.join([os.path.join(BENCH, "site"), REPO]),
             "ATPU_BENCH_CONFIG": os.path.join(BENCH, "configs", NAME + ".json")},
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.split() == ["6", "40", "320", "full"], out.stderr[-1500:]


def test_the_cell_rehearses_end_to_end_on_the_cpu():
    """``run.py --workload solar.reason --rehearse``: the daemon, the deploy of
    ``llm:solar-open2-250b-ep8-1chip`` at rehearsal widths, the numerics child,
    the 64 callers of ``reason-closed-64`` and the result line, with every
    listed ``solar_*`` metric that a CPU's counters can carry on a traced
    run's line (control flow only: no number of it is a device's, and the two
    shares of a roofline are absent). A window of 100 s, not 51: on the CPU a
    reply of 512-1536 tokens takes a minute, and a request is in the window
    only if its caller's last reply came inside it. About five minutes."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", str(2**31 + 9), "--seconds", "100",
         "--trace", "1", "--rehearse"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO, capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-3000:])
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0 and line["device"]["rehearsal"] is True
    for name in ("solar_decode_step_ms", "solar_state_bytes_share", "solar_batch_occupancy", "solar_engine_itl_p50_ms",
                 "solar_state_resets_per_req", "solar_host_ms_per_req", "solar_device_wait_share"):
        assert name in line["metrics"], (name, sorted(line["metrics"]))
    assert "solar_decode_step_roofline" not in line["metrics"] and "solar_kda_decode_roofline" not in line["metrics"]
