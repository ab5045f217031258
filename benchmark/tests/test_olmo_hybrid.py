"""The ``olmo_hybrid`` family through the seam (``families/olmo_hybrid.py``),
the ``agent-sessions-16`` mix, the cell ``olmo-hybrid.sessions`` and the
readers ISSUE 32 added, on the CPU at rehearsal widths and on recorded
``/metrics`` documents."""

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
NAME = "olmo-hybrid-7b-1chip"
CELL = "olmo-hybrid.sessions"


def olmo():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def benchmark_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearsal_config(tmp_path):
    doc = {**olmo(), **family_of(olmo()).REHEARSAL_WIDTHS}
    path = tmp_path / "olmo.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_the_file_holds_the_catalogs_published_keys():
    """``model-configs`` catalog, Olmo-Hybrid-7B: every key of its ``config``
    as published (the nested group whole), but the one ``reduced`` names that
    the catalog has; no width among them, no layer cut."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    doc = olmo()
    assert doc["source"] == row["source_url"] and doc["family"] == "olmo_hybrid"
    assert {k for k, v in row["config"].items() if doc.get(k, "absent") != v} == {"max_position_embeddings"}
    assert set(doc["reduced"]) == {"max_position_embeddings", "torch_dtype"}
    assert doc["num_hidden_layers"] == 32 and len(doc["layer_types"]) == 32 and doc["vocab_size"] == 100352
    entry = next(c for c in benchmark_json()["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(doc["reduced"]) and entry["source"] == doc["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    for item in ("norm_placement", "qk_norm", "rope_none", "linear_decay", "linear_output"):
        assert len(doc["assumed"][item]) > 40  # each with its reason
    assert doc["engine_options"] == {"quant": "int8", "synthetic": True, "max_batch": 8, "max_seq": 4096,
                                     "speculative": False, "prefix_cache_bytes": 0}


def test_family_answers_everything_a_family_is_asked():
    family = family_of(olmo())
    assert family.__name__ == "families.olmo_hybrid"
    for name in ("model_config", "REHEARSAL_WIDTHS", "numerics_sizes", "program", "reference", "decode_step_bytes",
                 "prefill_flops", "kv_bytes_per_token", "state_bytes_per_lane"):
        assert hasattr(family, name), name


def test_family_builds_the_programs_config_at_published_sizes():
    import dataclasses

    from agentainer_tpu.models.configs import get_config

    doc = olmo()
    family = family_of(doc)
    cfg = family.model_config(doc)
    assert cfg == dataclasses.replace(get_config("olmo-hybrid-7b"), name=NAME, max_seq_len=4096)
    assert cfg.param_count() == family.param_count(doc) and abs(cfg.param_count() / 7.43e9 - 1) < 0.002
    four = family.model_config(doc, n_layers=4)
    assert four.layer_kinds == ("gdn", "gdn", "gdn", "full") and four.n_dense_layers == 4 and not four.is_moe
    assert family.numerics_sizes(doc) == {"layers": 4, "prefill": 192, "decode": 8, "cache_len": 256}
    for wrong in ({"attention_bias": True}, {"rope_parameters": {"rope_theta": 500000.0}}, {"linear_num_key_heads": 15},
                  {"layer_types": ["sliding_attention"] * 32}):
        with pytest.raises(ValueError):
            family.model_config({**doc, **wrong})


def test_family_arithmetic_against_hand_counts():
    """ISSUE 32's reckoning, element for element: 7.43 B parameters; 122,880 B
    a token for the model's 30 K/V heads, 131,072 as stored with 32; 53.1 MB
    of state a lane (55 MB with its conv rows)."""
    doc = olmo()
    family = family_of(doc)
    lw = family.layer_weight_elements(doc)
    assert lw["gdn"] == 3840 * (2880 + 2880 + 5760) + 3840 * 5760 + 5760 * 3840 + 2 * 3840 * 30  # 88.7 M = 6 hidden^2 + the two heads
    assert lw["full"] == 4 * 3840 * 3840 and lw["ffn"] == 3 * 3840 * 11008
    layers = 24 * (lw["gdn"] + lw["ffn"]) + 8 * (lw["full"] + lw["ffn"])
    assert 6.64e9 < layers < 6.66e9
    assert family.weight_bytes(doc) == layers + 3840 * 100352 and 7.0e9 < family.weight_bytes(doc) < 7.1e9
    vectors = 24 * (4 * 11520 + 2 * 30 + 192) + 8 * 2 * 3840 + 32 * 2 * 3840 + 3840
    assert family.param_count(doc) == layers + 2 * 3840 * 100352 + vectors
    assert 7.42e9 < family.param_count(doc) < 7.44e9
    assert family.kv_bytes_per_token(doc, stored=False) == 8 * 2 * 30 * 128 * 2 == 122_880
    assert family.kv_bytes_per_token(doc) == 8 * 2 * 32 * 128 * 2 == 131_072
    assert [family.stored_kv_heads(n) for n in (1, 2, 4, 8, 12, 30, 40)] == [1, 2, 4, 8, 16, 32, 40]
    state = 24 * 30 * 96 * 192 * 4
    assert state == 53_084_160 and family.state_bytes_per_lane(doc) == state + 24 * 3 * 11520 * 2
    assert family.kernel_calls_per_step(doc) == {"gdn_decode": 24, "flash_decode": 8}
    assert family.gdn_decode_bytes(doc, 8) == 2 * 8 * 30 * 96 * 192 * 4  # 35.4 MB a layer
    assert family.full_decode_bytes(doc, 1000.0) == 1000 * 2 * 32 * 128 * 2
    step = family.decode_step_bytes(doc, live_kv_tokens=8 * 1600.0, live_lanes=8.0)
    assert step == family.weight_bytes(doc) + 2 * 8 * family.state_bytes_per_lane(doc) + 8 * 1600 * 131_072
    assert 9.5e9 < step < 9.7e9
    assert family.decode_step_bytes(doc, 0.0) == family.weight_bytes(doc) + 2 * 8 * family.state_bytes_per_lane(doc)  # max_batch lanes
    flops = family.prefill_flops(doc, 256, 0.0)
    assert flops == family.prefill_flops(doc, 256, 0.0, routed=False)  # dense: nothing is routed
    assert 2.0 * 256 * family.weight_bytes(doc) < flops < 1.02 * 2.0 * 256 * family.weight_bytes(doc)
    assert family.gdn_prefill_flops(doc, 256) == 30 * 4 * (4 * 64 * 64 * 96 + 3 * 64 * 64 * 192 + 6 * 64 * 96 * 192)


def test_the_start_up_hook_registers_the_block(tmp_path):
    env = {**CHILD_ENV, "ATPU_BENCH_CONFIG": rehearsal_config(tmp_path),
           "PYTHONPATH": os.pathsep.join([os.path.join(BENCH, "site"), CHILD_ENV["PYTHONPATH"]])}
    code = ("import sys, dataclasses, json; from agentainer_tpu.models.configs import get_config; "
            f"print(json.dumps(dataclasses.asdict(get_config('{NAME}')))); "
            "print(json.dumps(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'numpy'))))")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    cfg, heavy = map(json.loads, out.stdout.strip().splitlines()[-2:])
    assert cfg["layer_kinds"] == ["gdn", "gdn", "gdn", "full"] and cfg["post_norm"] and cfg["delta_neg_eigval"] and cfg["qk_norm"]
    assert (cfg["kda_heads"], cfg["kda_head_dim"], cfg["kda_v_dim"], cfg["rope_theta"], cfg["n_experts"]) == (6, 12, 24, 0.0, 0)
    assert heavy == []


def test_numerics_child_holds_the_program_to_the_familys_own_reference(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.numerics_child", rehearsal_config(tmp_path), "2147483999", "--rehearse"],
        env=CHILD_ENV, cwd=REPO, capture_output=True, text=True, timeout=900)
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["config"] == NAME and doc["layers"] == 4 and doc["positions_compared"] == 40
    assert doc["ok"] is True and out.returncode == 0, doc
    assert doc["rel_err"] < 1e-4 and doc["share_of_positions_within"] == 1.0
    assert doc["attention"]["gdn_prefill"] == "xla_chunked"


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "families", "olmo_hybrid_reference.py")) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith(("import ", "from "))]
    assert lines == ["from __future__ import annotations", "import jax", "import jax.numpy as jnp"]


def test_agent_sessions_16_is_the_mix_the_issue_gave_and_the_cell_is_named():
    with open(os.path.join(BENCH, "traffic", "agent-sessions-16.json")) as f:
        t = json.load(f)
    assert t["generator"] == "sessions" and t["clients"] == 16 == 2 * olmo()["engine_options"]["max_batch"]
    assert t["shared_prefix_tokens"] == 512
    assert t["turns"] == {"dist": "geometric", "mean": 5, "min": 2, "max": 10}
    assert t["think_s"] == {"dist": "lognormal", "median": 1.0, "sigma": 0.6, "min": 0.2, "max": 5.0}
    assert t["first_user_tokens"] == {"dist": "lognormal", "median": 384, "sigma": 0.7, "min": 64, "max": 1536}
    assert t["later_user_tokens"] == {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32, "max": 1024}
    assert t["max_tokens"] == {"dist": "lognormal", "median": 64, "sigma": 0.6, "min": 16, "max": 192}
    assert (t["context_limit_tokens"], t["warmup_s"], t["drain_s"]) == (3800, 10, 60)
    assert t["context_limit_tokens"] + 1 < olmo()["engine_options"]["max_seq"]
    b = benchmark_json()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)  # by name: where it stands in the list is nobody's contract
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "agent-sessions-16", 1) and len(cell["why"]) <= 200
    mine = {m["name"]: m for m in b["per_layer"] if m.get("workloads") == [CELL]}
    assert set(mine) == {
        "olmo_hybrid_decode_step_roofline", "state_restores_per_req", "olmo_hybrid_session_evictions",
        "prefill_over_new_tokens", "olmo_hybrid_engine_itl_p50_ms", "olmo_hybrid_batch_occupancy",
        "olmo_hybrid_device_wait_share", "olmo_hybrid_host_ms_per_req", "olmo_hybrid_prefill_dev_share"}
    assert all(m["moves"] == "req_per_s" for m in mine.values())
    assert mine["olmo_hybrid_decode_step_roofline"]["unit"] == "%" and mine["prefill_over_new_tokens"]["better"] == "lower"
    # written and without an entry (PERF.md section 7): the kernel's own reader, and the restore's time, which finds
    # nothing to read in this cell (no session is ever restored, so a traced run's line would lack it and be refused)
    assert not any(m["name"] in ("gdn_decode_roofline", "state_restore_ms") for m in b["per_layer"])
    for name in [*mine, "gdn_decode_roofline", "state_restore_ms"]:
        assert callable(importlib.import_module("layer_metrics." + name).read)


def test_the_generator_makes_sessions_of_the_shape_the_cell_is_for():
    import itertools

    from generators import sessions

    with open(os.path.join(BENCH, "traffic", "agent-sessions-16.json")) as f:
        t = json.load(f)
    some = list(itertools.islice(sessions.sessions(t, 3000000011, 3000000011, "m"), 200))
    turns = [len(s["turns"]) for s in some]
    assert 3.5 < sum(turns) / len(turns) < 5.5 and max(turns) <= 10
    assert all(s["turns"][0]["prompt_tokens"] >= 512 + 64 for s in some)
    assert max(s["turns"][-1]["context_tokens"] for s in some) <= 3800
    later = [tn["prompt_tokens"] for s in some for tn in s["turns"][1:]]
    assert 150 < sorted(later)[len(later) // 2] < 250


# -- the readers, on recorded /metrics documents -----------------------------------

BEFORE = [{
    "requests_finished": 40, "prefill_tokens": 20_000, "session_evictions_total": 30, "loop_s": 10.0,
    "decode_steps": 1000, "batch_occupancy": 0.9, "max_batch": 8, "decode_chunk_hist": {"8": 100, "1": 200},
    "cache": {"kinds": ["k", "v", "state", "conv"], "state_restores": 2, "state_resets": 20},
    "phases": {"engine.restore": {"n": 2, "self_s": 0.30, "total_s": 0.40}, "engine.wait_device": {"n": 9, "self_s": 8.0, "total_s": 8.0}},
}]
AFTER = [{
    "requests_finished": 190, "prefill_tokens": 140_000, "session_evictions_total": 120, "loop_s": 61.0,
    "decode_steps": 4000, "batch_occupancy": 0.95, "max_batch": 8, "decode_chunk_hist": {"8": 400, "1": 900},
    "cache": {"kinds": ["k", "v", "state", "conv"], "state_restores": 8, "state_resets": 110},
    "phases": {"engine.restore": {"n": 8, "self_s": 1.50, "total_s": 1.60}, "engine.wait_device": {"n": 99, "self_s": 50.0, "total_s": 50.0}},
}]
PARENT = [{"requests_finished": 40, "cache": {"kinds": ["kv"]}, "phases": {"engine.wait_device": {"n": 1, "self_s": 1.0, "total_s": 1.0}}}]
RESPONSES = [{"ok": True, "want_prompt_tokens": 900, "context_tokens": 1000}, {"ok": True, "want_prompt_tokens": 300, "context_tokens": 2200},
             {"ok": False, "want_prompt_tokens": 5000, "context_tokens": 3000}]


def reader(name):
    return importlib.import_module("layer_metrics." + name).read


def test_cache_manager_readers_on_recorded_documents():
    assert reader("state_restore_ms")(BEFORE, AFTER, RESPONSES, None, {}) == pytest.approx(1000 * 1.2 / 6)
    assert reader("state_restores_per_req")(BEFORE, AFTER, RESPONSES, None, {}) == pytest.approx(6 / 150)
    assert reader("olmo_hybrid_session_evictions")(BEFORE, AFTER, RESPONSES, None, {}) == 90
    assert reader("prefill_over_new_tokens")(BEFORE, AFTER, RESPONSES, None, {}) == pytest.approx(120_000 / 1200)
    # a program without the span, the counter or the state (the parent), and a window without a restore: no reading, no error
    assert reader("state_restore_ms")(PARENT, PARENT, RESPONSES, None, {}) is None
    assert reader("state_restore_ms")(AFTER, AFTER, RESPONSES, None, {}) is None
    assert reader("state_restores_per_req")(PARENT, PARENT, RESPONSES, None, {}) is None
    assert reader("state_restores_per_req")([{"requests_finished": 1}], [{"requests_finished": 9}], [], None, {}) is None
    assert reader("prefill_over_new_tokens")(PARENT, PARENT, RESPONSES, None, {}) is None
    assert reader("prefill_over_new_tokens")(BEFORE, AFTER, [], None, {}) is None


def test_the_aliases_are_the_accepted_readers_themselves():
    for name in ("session_evictions", "engine_itl_p50_ms", "batch_occupancy", "device_wait_share", "host_ms_per_req", "prefill_dev_share"):
        assert reader("olmo_hybrid_" + name) is reader(name)
    assert reader("olmo_hybrid_device_wait_share")(BEFORE, AFTER, RESPONSES, None, {}) == pytest.approx(42.0 / 51.0)


def trace_doc(ops):
    return {
        "modules": {"jit_decode_n": {"time_s": 2.0, "count": 10}, "jit_prefill": {"time_s": 1.5, "count": 40}},
        "device_ops": ops, "busy_s": 4.0, "device_planes": ["/device:TPU:0"],
        "counters_before": [{"decode_chunk_hist": {"8": 100}, "decode_steps": 100, "batch_occupancy": 0.75, "max_batch": 8}],
        "counters_after": [{"decode_chunk_hist": {"8": 110}, "decode_steps": 110, "batch_occupancy": 0.75, "max_batch": 8}],
    }


def test_rooflines_read_the_trace_and_the_familys_bytes():
    doc = olmo()
    family = family_of(doc)
    cell = {"config": doc, "device": {"kind": "TPU v5 lite"}, "seconds": 51.0}
    ok = [r for r in RESPONSES if r["ok"]]
    trace = trace_doc([["while.3", 1.9], ["gdn_decode.7", 0.3], ["gdn_decode.9", 0.1]])
    steps = 10 * 8  # launches in the trace x steps a launch
    step = reader("olmo_hybrid_decode_step_roofline")([], [], RESPONSES, trace, cell)
    need = family.decode_step_bytes(doc, live_kv_tokens=6 * 1600.0, live_lanes=6.0)  # 0.75 of 8 lanes at the mean context
    assert step == pytest.approx(100 * steps * need / 819e9 / 2.0) and 0 < step < 100
    assert sum(r["context_tokens"] for r in ok) / len(ok) == 1600
    # a span whose ladder also ran one-step launches: every traced launch counts as the shortest rung (a floor)
    mixed = trace_doc([])
    mixed["counters_after"] = [{**mixed["counters_after"][0], "decode_chunk_hist": {"8": 110, "1": 40}}]
    mixed["counters_before"] = [{**mixed["counters_before"][0], "decode_chunk_hist": {"8": 100, "1": 10}}]
    assert reader("olmo_hybrid_decode_step_roofline")([], [], RESPONSES, mixed, cell) == pytest.approx(step / 8)
    gdn = reader("gdn_decode_roofline")([], [], RESPONSES, trace, cell)
    assert gdn == pytest.approx(100 * steps * 24 * family.gdn_decode_bytes(doc, 8) / 819e9 / 0.4)  # every lane of the call
    assert reader("olmo_hybrid_prefill_dev_share")([], [], RESPONSES, trace, cell) == pytest.approx(1.5 / 4.0)
    # the kernel is not among the ten ops the trace keeps (a served run), or there is no trace: no reading, no error
    assert reader("gdn_decode_roofline")([], [], RESPONSES, trace_doc([["while.3", 1.9]]), cell) is None
    for name in ("gdn_decode_roofline", "olmo_hybrid_decode_step_roofline"):
        assert reader(name)([], [], RESPONSES, None, cell) is None
    # another family's configuration (no such kernel's bytes): the kernel reader reads nothing
    with open(os.path.join(BENCH, "configs", "kimi-linear-48b-ep8-1chip.json")) as f:
        other = {"config": json.load(f), "device": {"kind": "TPU v5 lite"}}
    assert reader("gdn_decode_roofline")([], [], RESPONSES, trace, other) is None
