"""The ``mistral4`` family through the seam (``families/mistral4.py``), the
``docs-closed-16`` mix, the cell ``mistral4.docs`` and the readers ISSUE 40
added, on the CPU at rehearsal widths and on recorded ``/metrics`` documents:
this cell's, and an accepted cell's that lack the new keys (the parent's
program under this PR's benchmark files: every new reader answers ``None``)."""

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
NAME = "mistral-small-4-119b-ep4-1chip"
CELL = "mistral4.docs"
REDUCED = {"n_routed_experts", "num_hidden_layers", "max_position_embeddings", "torch_dtype", "vision_config"}
ALIASES = ("engine_itl_p50_ms", "batch_occupancy", "device_wait_share", "host_ms_per_req", "prefill_dev_share")


def mistral():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def benchmark_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearsal_config(tmp_path):
    doc = {**mistral(), **family_of(mistral()).REHEARSAL_WIDTHS}
    path = tmp_path / "mistral4.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_the_file_holds_the_catalogs_published_keys():
    """``model-configs`` catalog, Mistral-Small-4-119B-2603: every key of its
    ``config`` as published but the three ``reduced`` names that the catalog
    has (experts held, depth, context); no width among them; the nested
    ``rope_parameters`` copied whole."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Mistral-Small-4-119B-2603")
    doc = mistral()
    assert doc["source"] == row["source_url"] and doc["family"] == "mistral4" and doc["model_type"] == "mistral4"
    changed = {k for k, v in row["config"].items() if doc.get(k, "absent") != v}
    assert changed == {"n_routed_experts", "num_hidden_layers", "max_position_embeddings"}
    assert doc["rope_parameters"] == row["config"]["rope_parameters"]
    assert set(doc["reduced"]) == REDUCED
    assert (doc["n_routed_experts"], doc["experts_published"], doc["num_experts_per_tok"]) == (32, 128, 4)
    assert (doc["num_hidden_layers"], doc["layers_published"], doc["max_position_embeddings"]) == (9, 36, 16384)
    assert {k: doc["expert_parallel"][k] for k in ("ep", "chip", "experts")} == {"ep": 4, "chip": 0, "experts": "0-31"}
    assert (doc["pipeline"]["stages"], doc["pipeline"]["stage"]) == (4, 0) and doc["vocab_size"] == 131072
    entry = next(c for c in benchmark_json()["configs"] if c["name"] == NAME)
    assert set(entry["reduced"]) == REDUCED and entry["source"] == doc["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    for item in ("softmax_scale_mscale", "query_scale", "router", "intermediate_size", "yarn"):
        assert len(doc["assumed"][item]) > 40  # each with its reason
    assert "sigmoid" in doc["assumed"]["router"] and "moe_router" in doc["assumed"]["router"]
    assert doc["engine_options"] == {"quant": "int8", "synthetic": True, "max_batch": 16, "max_seq": 16384,
                                     "speculative": False, "prefix_cache_bytes": 0}
    assert "v5e-4" in doc["stands_for"] and 0.25 * 16e9 < doc["hbm_claim_bytes_per_chip"] <= 16 * 2**30


def test_family_answers_everything_a_family_is_asked():
    family = family_of(mistral())
    assert family.__name__ == "families.mistral4"
    for name in ("model_config", "REHEARSAL_WIDTHS", "numerics_sizes", "program", "reference", "decode_step_bytes",
                 "prefill_flops", "kv_bytes_per_token", "latent_row_bytes", "latent_row_bytes_stored", "mla_decode_bytes"):
        assert hasattr(family, name), name


def test_family_builds_the_programs_config_at_published_sizes():
    import dataclasses

    from agentainer_tpu.models.configs import get_config

    doc = mistral()
    family = family_of(doc)
    cfg = family.model_config(doc)
    want = dataclasses.replace(
        get_config("mistral-small-4-119b"), name=NAME, n_layers=9, layer_kinds=("mla",) * 9, max_seq_len=16384,
        experts_held=32, expert_offset=0, dense_ffn_dim=12288)
    assert cfg == want
    assert (cfg.n_experts, cfg.n_held, cfg.experts_per_token, cfg.mla_q_rank) == (128, 32, 4, 1024)
    assert cfg.linear_kind is None and cfg.positional_kind == "mla" and cfg.n_dense_layers == 0
    chip2 = family.model_config({**doc, "expert_parallel": {"ep": 4, "chip": 2}})
    assert (chip2.experts_held, chip2.expert_offset) == (32, 64)
    whole = family.model_config({**doc, "n_routed_experts": 128, "num_hidden_layers": 36})
    assert (whole.experts_held, whole.expert_offset) == (0, 0) and round(whole.param_count() / 1e9, 1) == 119.0
    sizes = family.numerics_sizes(doc)
    assert sizes == {"layers": 1, "prefill": 8448, "decode": 8, "cache_len": 8704}
    # the prefill crosses the original context, fed in the engine's chunks
    assert sizes["prefill"] > 8192 + 32 and sizes["prefill"] % family.PREFILL_CHUNK == 0
    rope = doc["rope_parameters"]
    for wrong in ({"rope_parameters": {**rope, "rope_type": "linear"}}, {"rope_parameters": {**rope, "mscale": 0.5}},
                  {"n_group": 2}, {"routed_scaling_factor": 2.5}, {"norm_topk_prob": False}, {"first_k_dense_replace": 1},
                  {"tie_word_embeddings": True}, {"qk_head_dim": 192}):
        with pytest.raises(ValueError):
            family.model_config({**doc, **wrong})


def test_family_arithmetic_against_hand_counts_and_the_programs():
    """ISSUE 40's reckoning, element for element, against a count made here
    with nothing of the family's, and against ``ModelConfig``'s own."""
    doc = mistral()
    family = family_of(doc)
    cfg = family.model_config(doc)
    lw = family.layer_weight_elements(doc)
    q_a, q_b, kv_a, kv_b, o = 4096 * 1024, 1024 * 32 * 128, 4096 * 320, 256 * 32 * 192, 4096 * 4096
    assert lw["mla"] == q_a + q_b + kv_a + kv_b + o == 28_049_408
    assert lw["expert"] == 3 * 4096 * 2048 == 25_165_824 and lw["moe_fixed"] == 4096 * 128 + lw["expert"]
    outside = lw["mla"] + lw["moe_fixed"]
    assert 53.7e6 < outside < 53.8e6
    layer = outside + 32 * lw["expert"]
    assert 0.858e9 < layer < 0.860e9
    assert family.weight_bytes(doc) == 9 * layer + 4096 * 131072 and 8.2e9 < family.weight_bytes(doc) < 8.3e9
    # ModelConfig counts the same matrices, the embedding table and the norm vectors beside them
    vectors = 9 * (2 * 4096 + 1024 + 256) + 4096
    assert cfg.param_count() == family.weight_bytes(doc) + 4096 * 131072 + vectors
    assert 8.7e9 < cfg.param_count() < 8.9e9  # 7.73 + 1.07 GB of int8: ISSUE 40's 8.8 GB
    # the latent row: 640 B published, 768 B stored; the arena 1.81 GB
    assert (family.latent_row_bytes(doc), family.latent_row_bytes_stored(doc)) == (640, 768)
    assert family.kv_bytes_per_token(doc) == 9 * 640
    arena = 9 * 16 * 16384 * family.latent_row_bytes_stored(doc)
    assert 1.81e9 < arena < 1.82e9 and cfg.param_count() + arena < 0.68 * 15.75e9
    assert family.mla_decode_bytes(doc, 16 * 5000.0) == 16 * 5000 * 768
    assert family.decode_step_bytes(doc, 16 * 5000.0) == family.weight_bytes(doc) + 16 * 5000 * 9 * 640
    # prefill: a token meets k x held / E = 1 routed expert here on average, and every layer's attention
    matmul = 2.0 * (9 * (outside + 4 * 32 / 128 * lw["expert"]) + 4096 * 131072)
    assert family.prefill_flops(doc, 256, 0.0) == 256 * matmul
    assert family.prefill_flops(doc, 1, 6000.0) == pytest.approx(matmul + 2.0 * 32 * 256 * 6000.0 * 9)
    assert family.prefill_flops(doc, 1, 0.0, routed=False) == 2.0 * (9 * layer + 4096 * 131072)
    # a 256-row chunk: 0.36 TFLOP of projections and experts (ISSUE 40's number), and 0.27 more if the output head
    # runs on every row of the chunk
    head = 256 * 2.0 * 4096 * 131072
    assert 0.35e12 < family.prefill_flops(doc, 256, 0.0) - head < 0.37e12 and 0.27e12 < head < 0.28e12
    # the program's own per-token model agrees on the attention term and on the weights a token meets
    per_token = cfg.flops_per_token(6000) - cfg.flops_per_token(0)
    assert per_token == pytest.approx(2.0 * 32 * 256 * 6000.0 * 9)


def test_the_start_up_hook_registers_the_block(tmp_path):
    env = {**CHILD_ENV, "ATPU_BENCH_CONFIG": rehearsal_config(tmp_path),
           "PYTHONPATH": os.pathsep.join([os.path.join(BENCH, "site"), CHILD_ENV["PYTHONPATH"]])}
    code = ("import sys, dataclasses, json; from agentainer_tpu.models.configs import get_config; "
            f"print(json.dumps(dataclasses.asdict(get_config('{NAME}')))); "
            "print(json.dumps(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'numpy'))))")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    cfg, heavy = map(json.loads, out.stdout.strip().splitlines()[-2:])
    assert cfg["layer_kinds"] == ["mla"] * 3 and cfg["mla_q_rank"] == 24 and cfg["mla_rotary"] and cfg["rope_interleave"]
    assert (cfg["rope_factor"], cfg["rope_original_max"], cfg["q_pos_scale_beta"]) == (128.0, 8192, 0.1)
    assert (cfg["n_experts"], cfg["experts_held"], cfg["expert_offset"], cfg["experts_per_token"]) == (8, 2, 0, 2)
    assert cfg["moe_router"] == "softmax" and cfg["n_shared_experts"] == 1
    assert heavy == []


def test_numerics_child_holds_the_program_to_the_familys_own_reference(tmp_path):
    """At rehearsal widths with the original context at its published 8,192:
    8,448 tokens in chunks of 256 through the cache (the last chunk past the
    boundary), then 8 decode steps, against the reference's full forward."""
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.numerics_child", rehearsal_config(tmp_path), "2147483999", "--rehearse"],
        env=CHILD_ENV, cwd=REPO, capture_output=True, text=True, timeout=900)
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["config"] == NAME and doc["layers"] == 1 and doc["positions_compared"] == 40
    assert doc["ok"] is True and out.returncode == 0, doc
    assert doc["rel_err"] < 1e-4 and doc["share_of_positions_within"] == 1.0
    assert "yarn x128 past 8192" in doc["attention"]["mla_rotary"]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "families", "mistral4_reference.py")) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith(("import ", "from "))]
    assert lines == ["from __future__ import annotations", "import math", "import jax", "import jax.numpy as jnp",
                     "import numpy as np"]


def test_docs_closed_16_is_the_mix_the_issue_gave_and_the_cell_is_named():
    with open(os.path.join(BENCH, "traffic", "docs-closed-16.json")) as f:
        t = json.load(f)
    assert t["generator"] == "sessions" and t["clients"] == 16 == mistral()["engine_options"]["max_batch"]
    assert t["shared_prefix_tokens"] == 0 and t["turns"] == {"dist": "const", "value": 1} and "think_s" not in t
    assert t["first_user_tokens"] == {"dist": "lognormal", "median": 4096, "sigma": 0.5, "min": 1024, "max": 15360}
    assert t["max_tokens"] == {"dist": "uniform", "min": 64, "max": 128}
    assert (t["context_limit_tokens"], t["warmup_s"], t["drain_s"]) == (15900, 10, 60)
    assert t["context_limit_tokens"] + 1 < mistral()["engine_options"]["max_seq"]
    b = benchmark_json()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "docs-closed-16", 1) and len(cell["why"]) <= 200
    assert [w["name"] for w in b["workloads"] if w["config"] == NAME] == [CELL]  # one cell, no second
    assert b["workloads"][-1] is cell and b["configs"][-1]["name"] == NAME  # appended
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 0 and len(b["workloads"]) == 8


def test_no_new_entry_reaches_an_accepted_cell():
    """PR 39's refusal: an entry without a ``workloads`` list, or one naming an
    accepted cell, runs its reader in that cell's traced runs on the PARENT's
    program too. Every entry this PR adds lists this cell alone, and has a
    reader of its own name."""
    b = benchmark_json()
    mine = {m["name"]: m for m in b["per_layer"] if CELL in m.get("workloads", [])}
    assert set(mine) >= {"latent_decode_fetch_share", "long_position_rows_share", "mistral4_engine_itl_p50_ms",
                         "mistral4_batch_occupancy", "mistral4_device_wait_share", "mistral4_host_ms_per_req"}
    assert set(mine) <= {"latent_decode_fetch_share", "long_position_rows_share", *("mistral4_" + a for a in ALIASES)}
    assert all(m["workloads"] == [CELL] and m["moves"] == "req_per_s" for m in mine.values())
    names = [m["name"] for m in b["per_layer"]]
    assert names[-len(mine):] == list(mine)  # appended after every accepted entry
    assert all("workloads" not in m or CELL not in m["workloads"] for m in b["per_layer"][: -len(mine)])
    assert (mine["latent_decode_fetch_share"]["better"], mine["latent_decode_fetch_share"]["source"],
            mine["latent_decode_fetch_share"]["layer"]) == ("lower", "program_counter", "kernels")
    assert (mine["long_position_rows_share"]["better"], mine["long_position_rows_share"]["layer"]) == ("higher", "model runner")
    # no decode-step roofline is entered for this cell (PERF.md section 7: the shared method reads 1.5 x the truth)
    assert not any("roofline" in n for n in mine)
    for name in mine:
        assert callable(importlib.import_module("layer_metrics." + name).read)
    # the cell reports an accepted metric that has no list too (req_p50_ms, tok_per_s, engine_ttft_p50_ms, ...):
    # their readers are the accepted ones and read what every engine reports
    assert {m["name"] for m in b["per_layer"] if "workloads" not in m} == {
        "req_p50_ms", "tok_per_s", "engine_ttft_p50_ms", "compiles_in_window", "engine_boot_s", "boot_import_backend_s",
        "boot_weights_s", "boot_warmup_s", "boot_jit_s", "boot_cache_misses"}


def test_the_generator_makes_prompts_of_the_shape_the_cell_is_for():
    import itertools

    from generators import sessions

    with open(os.path.join(BENCH, "traffic", "docs-closed-16.json")) as f:
        t = json.load(f)
    some = list(itertools.islice(sessions.sessions(t, 3000000011, 3000000011, "m"), 640))
    assert all(len(s["turns"]) == 1 for s in some)
    prompts = sorted(s["turns"][0]["prompt_tokens"] for s in some)
    assert 3900 < prompts[len(prompts) // 2] < 4300 and prompts[0] >= 1024 and prompts[-1] <= 15361
    past_8k = sum(p > 8192 for p in prompts) / len(prompts)
    assert 0.06 < past_8k < 0.11  # about 8 %
    assert max(s["turns"][0]["context_tokens"] for s in some) <= 15900
    out = [s["turns"][0]["max_tokens"] for s in some]
    assert 64 <= min(out) and max(out) <= 128 and 90 < sum(out) / len(out) < 102


# -- the readers, on recorded /metrics documents -----------------------------------


def attention(**counts):
    return {"mla_decode": "pallas_mla_decode", "mla_prefill": "pallas_mla_prefill", "decode_block_positions": 0,
            "decode_blocks_live": 0, "decode_blocks_stored": 0, "latent_block_positions": 512, "rope_original_max": 8192,
            **counts}


# this cell's engine at the window's start and end
BEFORE = [{"requests_finished": 20, "max_batch": 16, "decode_steps": 300, "batch_occupancy": 0.8,
           "attention": attention(latent_decode_blocks_live=30_000, latent_decode_blocks_stored=153_600,
                                  rows_positioned=100_000, rows_past_original_max=4_000)}]
AFTER = [{"requests_finished": 120, "max_batch": 16, "decode_steps": 1500, "batch_occupancy": 0.85,
          "attention": attention(latent_decode_blocks_live=150_000, latent_decode_blocks_stored=768_000,
                                 rows_positioned=600_000, rows_past_original_max=64_000)}]
# an accepted cell's engine under the PARENT's program: Kimi-Linear's latent leaf, none of the new keys
PARENT = [{"requests_finished": 40, "max_batch": 64, "decode_steps": 900, "batch_occupancy": 0.9,
           "attention": {"mla_decode": "pallas_mla_decode", "decode_block_positions": 0, "decode_blocks_live": 0,
                         "decode_blocks_stored": 0}}]
PARENT_LATER = [{**PARENT[0], "requests_finished": 180, "decode_steps": 4100}]
RESPONSES = [{"ok": True, "want_prompt_tokens": 4100, "context_tokens": 4200}, {"ok": True, "want_prompt_tokens": 9000, "context_tokens": 9100}]


def reader(name):
    return importlib.import_module("layer_metrics." + name).read


def test_the_counter_readers_on_recorded_documents():
    assert reader("latent_decode_fetch_share")(BEFORE, AFTER, RESPONSES, None, {}) == pytest.approx(120_000 / 614_400)
    assert reader("long_position_rows_share")(BEFORE, AFTER, RESPONSES, None, {}) == pytest.approx(60_000 / 500_000)
    for name in ("latent_decode_fetch_share", "long_position_rows_share"):
        # the parent's program (no such counter), a window in which nothing was launched, an empty document:
        # no reading and no error
        assert reader(name)(PARENT, PARENT_LATER, RESPONSES, None, {}) is None
        assert reader(name)(AFTER, AFTER, RESPONSES, None, {}) is None
        assert reader(name)([{}], [{}], [], None, {}) is None
        assert reader(name)([], [], [], None, {}) is None
        # a trace is not asked for, and one that is there changes nothing
        assert reader(name)(BEFORE, AFTER, RESPONSES, {"busy_s": 3.0, "modules": {}}, {}) == reader(name)(BEFORE, AFTER, RESPONSES, None, {})
    # Kimi-Linear under THIS program counts latent blocks and no positioned rows: one reads, the other does not
    kimi = [{"attention": {"latent_block_positions": 512, "latent_decode_blocks_live": 10, "latent_decode_blocks_stored": 80}}]
    kimi_later = [{"attention": {"latent_block_positions": 512, "latent_decode_blocks_live": 30, "latent_decode_blocks_stored": 160}}]
    assert reader("latent_decode_fetch_share")(kimi, kimi_later, [], None, {}) == pytest.approx(0.25)
    assert reader("long_position_rows_share")(kimi, kimi_later, [], None, {}) is None


def test_the_aliases_are_the_accepted_readers_themselves():
    for name in ALIASES:
        assert reader("mistral4_" + name) is reader(name)
    trace = {"busy_s": 4.0, "device_planes": ["/device:TPU:0"],
             "modules": {"jit_prefill": {"time_s": 3.4, "count": 160}, "jit_decode_n": {"time_s": 0.6, "count": 12}}}
    assert reader("mistral4_prefill_dev_share")(BEFORE, AFTER, RESPONSES, trace, {}) == pytest.approx(0.85)
    assert reader("mistral4_prefill_dev_share")(BEFORE, AFTER, RESPONSES, None, {}) is None
