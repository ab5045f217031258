"""The ``laguna`` family through the seam (``families/laguna.py``), the
``docs-closed-8`` population, the cell ``laguna.docs`` and the readers ISSUE 50
added, on the CPU at rehearsal widths and on recorded ``/metrics`` documents:
this cell's, and an accepted cell's that lack the new keys (the parent's
program under this PR's benchmark files: every new reader answers ``None``
and none raises)."""

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
NAME = "laguna-xs2-33b-ep8-1chip"
CELL = "laguna.docs"
REDUCED = {"num_experts", "max_position_embeddings", "torch_dtype"}
ALIASES = ("window_kv_fetch_share", "window_wraps_per_req", "long_position_rows_share", "engine_itl_p50_ms",
           "batch_occupancy", "device_wait_share", "host_ms_per_req", "prefill_dev_share")
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def laguna():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def benchmark_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearsal_config(tmp_path):
    doc = {**laguna(), **family_of(laguna()).REHEARSAL_WIDTHS}
    path = tmp_path / "laguna.json"
    path.write_text(json.dumps(doc))
    return str(path)


def reader(name):
    return importlib.import_module("layer_metrics." + name).read


def test_the_file_holds_the_catalogs_published_keys():
    """``model-configs`` catalog, Laguna-XS.2: every key of its ``config`` as
    published but the two ``reduced`` names the catalog has (experts held,
    context); no width among them; the nested ``rope_parameters`` and the
    three per-layer lists copied whole."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    doc = laguna()
    assert doc["source"] == row["source_url"] and doc["family"] == "laguna" and doc["model_type"] == "laguna"
    changed = {k for k, v in row["config"].items() if doc.get(k, "absent") != v}
    assert changed == {"num_experts", "max_position_embeddings"}
    for whole in ("rope_parameters", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        assert doc[whole] == row["config"][whole]
    assert set(doc["reduced"]) == REDUCED
    assert (doc["num_experts"], doc["experts_published"], doc["num_experts_per_tok"]) == (32, 256, 8)
    assert (doc["num_hidden_layers"], doc["max_position_embeddings"], doc["context_published"]) == (40, 16384, 262144)
    assert {k: doc["expert_parallel"][k] for k in ("ep", "chip", "experts")} == {"ep": 8, "chip": 0, "experts": "0-31"}
    entry = next(c for c in benchmark_json()["configs"] if c["name"] == NAME)
    assert set(entry["reduced"]) == REDUCED and entry["source"] == doc["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    assert set(doc["assumed"]) == {"gate", "router", "shared_expert", "qk_norm", "rotary"}  # the five points
    assert all(len(reason) > 40 for reason in doc["assumed"].values())  # each with its reason
    assert doc["engine_options"] == {"quant": "int8", "synthetic": True, "max_batch": 8, "max_seq": 16384}
    assert "v5e-8" in doc["stands_for"] and 0.25 * 16e9 < doc["hbm_claim_bytes_per_chip"] <= 16 * 2**30
    live = doc["memory"]["compiled_live_bytes"]
    assert set(live) == {"decode", "prefill", "mixed"} and all(12.0e9 < v < 15.75e9 for v in live.values())
    assert doc["memory"]["param_hbm_bytes"] + doc["memory"]["kv_arena_bytes"] < min(doc["memory"]["device_peak_bytes_in_use"])
    assert 0.25 * 16e9 < min(doc["memory"]["device_peak_bytes_in_use"]) and max(doc["memory"]["device_peak_bytes_in_use"]) < 15.75e9


def test_family_answers_everything_a_family_is_asked():
    family = family_of(laguna())
    assert family.__name__ == "families.laguna"
    for name in ("model_config", "REHEARSAL_WIDTHS", "numerics_sizes", "program", "reference", "decode_step_bytes",
                 "prefill_flops", "kv_bytes_per_token", "row_bytes", "ring_rows", "attended_rows", "chunk_rows_read",
                 "mixed_step_floor_s"):
        assert hasattr(family, name), name


def test_family_builds_the_programs_config_at_published_sizes():
    import dataclasses

    from agentainer_tpu.models.configs import get_config

    doc = laguna()
    family = family_of(doc)
    cfg = family.model_config(doc)
    assert cfg == dataclasses.replace(get_config("laguna-xs.2"), name=NAME, max_seq_len=16384, experts_held=32, expert_offset=0)
    assert (cfg.n_heads, cfg.window_heads, cfg.n_kv_heads, cfg.head_dim, cfg.window) == (48, 64, 8, 128, 512)
    assert (cfg.n_experts, cfg.n_held, cfg.experts_per_token, cfg.moe_scale) == (256, 32, 8, 2.5)
    assert cfg.layer_kinds.count("full") == 10 and cfg.layer_kinds.count("swa") == 30 and cfg.linear_kind is None
    assert (cfg.rope_theta, cfg.swa_rope_theta, cfg.rope_partial, cfg.rope_factor, cfg.rope_original_max) == (
        500000.0, 10000.0, 0.5, 64.0, 4096)
    chip5 = family.model_config({**doc, "expert_parallel": {"ep": 8, "chip": 5}})
    assert (chip5.experts_held, chip5.expert_offset) == (32, 160)
    whole = family.model_config({**doc, "num_experts": 256})
    assert (whole.experts_held, whole.expert_offset) == (0, 0) and whole.param_count() == 33_442_430_976 + 81 * 2048
    sizes = family.numerics_sizes(doc)
    assert sizes == {"layers": 5, "prefill": 4352, "decode": 8, "cache_len": 4608}
    five = family.model_config(doc, n_layers=sizes["layers"])
    # every kind of mixer and of FFN: a dense FFN under full attention, three sliding layers, a full layer with experts
    assert five.layer_kinds == ("full", "swa", "swa", "swa", "full") and five.n_dense_layers == 1
    # the ring (1,024 rows) wraps, fed in the engine's chunks, and rows pass the original context
    assert sizes["prefill"] > family.ring_rows(doc) + 256 and sizes["prefill"] % family.PREFILL_CHUNK == 0
    assert sizes["prefill"] > cfg.rope_original_max + 32 and family.ring_rows(doc) == 1024
    rope = doc["rope_parameters"]
    for wrong in (
        {"rope_parameters": {**rope, "full_attention": {**rope["full_attention"], "rope_type": "linear"}}},
        {"rope_parameters": {**rope, "sliding_attention": {**rope["sliding_attention"], "partial_rotary_factor": 0.5}}},
        {"gating": False}, {"attention_bias": True}, {"tie_word_embeddings": True}, {"moe_apply_router_weight_on_input": True},
        {"mlp_layer_types": ["sparse", "dense"] + ["sparse"] * 38}, {"num_attention_heads": 64},
        {"num_attention_heads_per_layer": [48, 64, 64, 56] * 10}, {"shared_expert_intermediate_size": 768},
        {"layer_types": ["sliding_attention"] * 40},
    ):
        with pytest.raises(ValueError):
            family.model_config({**doc, **wrong})


def test_family_arithmetic_against_hand_counts_and_the_programs():
    """ISSUE 50's reckoning, element for element, against a count made here
    with nothing of the family's, and against ``ModelConfig``'s own."""
    doc = laguna()
    family = family_of(doc)
    cfg = family.model_config(doc)
    lw = family.layer_weight_elements(doc)
    assert lw["full"] == 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48 == 29_458_432
    assert lw["sliding"] == 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64 == 37_879_808
    assert lw["expert"] == 3 * 2048 * 512 == 3_145_728 and lw["moe_fixed"] == 2048 * 256 + 3_145_728
    assert lw["dense"] == 3 * 2048 * 8192 == 50_331_648
    outside = 10 * lw["full"] + 30 * lw["sliding"] + lw["dense"] + 39 * lw["moe_fixed"] + 2 * 2048 * 100352
    assert outside == 2_035_482_624 and 39 * 32 * lw["expert"] == 3_925_868_544
    vectors = 81 * 2048
    assert family.param_count(doc) == cfg.param_count() == outside + 3_925_868_544 + vectors == 5_961_517_056
    # a step streams everything but the embedding table (a gather) and the vectors
    assert family.weight_bytes(doc) == outside - 2048 * 100352 + 3_925_868_544 and 5.7e9 < family.weight_bytes(doc) < 5.8e9
    # the cache: 4,096 B a row a layer; 10 full layers of 16,384 rows and 30 rings of 1,024 at 8 lanes
    assert family.row_bytes(doc) == 4096 and family.ring_rows(doc) == 1024
    lane = family.kv_resident_bytes_per_lane(doc)
    assert lane == 10 * 4096 * 16384 + 30 * 4096 * 1024 and 6.37e9 < 8 * lane < 6.38e9
    assert cfg.param_count() + 8 * lane < 0.79 * 15.75e9  # 12.3 GB before temporaries
    assert family.kv_bytes_per_token(doc) == 40 * 4096
    assert family.kv_bytes_per_token(doc, 4096.0) == 4096 * (10 * 4096 + 30 * 512) / 4096
    assert family.decode_step_bytes(doc, 8 * 5000.0, lanes=8) == family.weight_bytes(doc) + 8 * 4096 * (10 * 5000 + 30 * 512)
    assert family.decode_step_bytes(doc, kv_bytes=123.0) == family.weight_bytes(doc) + 123.0
    # prefill: a token meets k x held / E = 1 routed expert here on average; the head runs on a row a chunk
    per_token = 2.0 * (10 * lw["full"] + 30 * lw["sliding"] + lw["dense"] + 39 * (lw["moe_fixed"] + lw["expert"]))
    assert family.token_matmul_flops(doc) == per_token and 3.4e9 < per_token < 3.6e9
    assert family.prefill_flops(doc, 256, 0.0) == 256 * per_token + 2.0 * 2048 * 100352
    attn = 4.0 * 128 * (10 * 48 * 6000.0 + 30 * 64 * 512.0)
    assert family.prefill_flops(doc, 1, 6000.0) == pytest.approx(per_token + 2.0 * 2048 * 100352 / 256 + attn)
    assert family.attended_rows(doc, 4000) == {"global": 8e6, "window": 512 * 512 / 2 + (4000 - 512) * 512}
    assert family.attended_rows(doc, 300) == {"global": 45000.0, "window": 45000.0}
    assert family.chunk_rows_read(doc, 600) == {"global": 256.0 + 512 + 600, "window": 256.0 + 512 + 600, "launches": 3}
    assert family.chunk_rows_read(doc, 1024)["window"] == 256 + 512 + 767 + 767
    # the program's own per-token model agrees on both kinds' attention
    assert cfg.flops_per_token(6000) - cfg.flops_per_token(0) == pytest.approx(attn)
    # the floor of a mixed launch: bytes-bound at 8 riders (7.0 ms of weights + the K/V rows)
    attended = {"global_rows": 4000.0 + 8 * 4000, "window_rows": 767.0 + 8 * 512, "global_pairs": 256 * 3900.0 + 8 * 4000,
                "window_pairs": 256 * 512.0 + 8 * 512}
    floor = family.mixed_step_floor_s(doc, 256, 8, attended, V5E)
    kv = 4096 * (10 * 36000 + 30 * 4863)
    assert floor == pytest.approx((family.weight_bytes(doc) + kv) / 819e9) and 0.009 < floor < 0.010
    flops = 264 * per_token + 9 * 2.0 * 2048 * 100352 + family.attention_flops(doc, attended["global_pairs"], attended["window_pairs"])
    assert flops / 197e12 < floor  # the FLOPs' side is the smaller here: 5.2 ms
    nothing = {"global_rows": 0.0, "window_rows": 0.0, "global_pairs": 0.0, "window_pairs": 0.0}
    assert family.mixed_step_floor_s(doc, 256, 0, nothing, {**V5E, "hbm_bytes_per_s": 1e18}) == pytest.approx(
        (256 * per_token + 2.0 * 2048 * 100352) / 197e12)


def test_the_start_up_hook_registers_the_block(tmp_path):
    env = {**CHILD_ENV, "ATPU_BENCH_CONFIG": rehearsal_config(tmp_path),
           "PYTHONPATH": os.pathsep.join([os.path.join(BENCH, "site"), CHILD_ENV["PYTHONPATH"]])}
    code = ("import sys, dataclasses, json; from agentainer_tpu.models.configs import get_config; "
            f"print(json.dumps(dataclasses.asdict(get_config('{NAME}')))); "
            "print(json.dumps(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'numpy'))))")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    cfg, heavy = map(json.loads, out.stdout.strip().splitlines()[-2:])
    assert cfg["layer_kinds"] == ["full", "swa", "swa", "swa"] * 2 and (cfg["n_heads"], cfg["swa_heads"]) == (6, 8)
    assert (cfg["window"], cfg["rope_factor"], cfg["rope_original_max"], cfg["rope_partial"]) == (16, 64.0, 4096, 0.5)
    assert (cfg["n_experts"], cfg["experts_held"], cfg["expert_offset"], cfg["experts_per_token"]) == (8, 2, 0, 2)
    assert cfg["attn_gate"] and cfg["moe_scale"] == 2.5 and cfg["n_shared_experts"] == 1 and cfg["n_dense_layers"] == 1
    assert heavy == []


def test_numerics_child_holds_the_program_to_the_familys_own_reference(tmp_path):
    """At rehearsal widths with the original context at its published 4,096:
    4,352 tokens in chunks of 256 through the cache (the ring of 16 + 256 rows
    wraps sixteen times; the last chunk is past the boundary), then 8 decode
    steps, against the reference's full forward."""
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.numerics_child", rehearsal_config(tmp_path), "2147483999", "--rehearse"],
        env=CHILD_ENV, cwd=REPO, capture_output=True, text=True, timeout=900)
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["config"] == NAME and doc["layers"] == 5 and doc["positions_compared"] == 40
    assert doc["ok"] is True and out.returncode == 0, doc
    assert doc["rel_err"] < 1e-4 and doc["share_of_positions_within"] == 1.0
    assert doc["attention"]["ring_rows"] == 16 + 256 and doc["attention"]["swa_prefill"]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "families", "laguna_reference.py")) as f:
        text = f.read()
    lines = [ln for ln in text.splitlines() if ln.startswith(("import ", "from "))]
    assert lines == ["from __future__ import annotations", "import math", "import jax", "import jax.numpy as jnp",
                     "import numpy as np"]
    assert "import agentainer_tpu" not in text and "from agentainer_tpu" not in text and "families." not in text.split('"""')[2]
    assert 'jax.default_matmul_precision("highest")' in text and "bfloat16" not in text


def test_docs_closed_8_resolves_its_base_and_the_cell_is_named():
    sys.path.insert(0, BENCH)
    try:
        run = importlib.import_module("run")
    finally:
        sys.path.remove(BENCH)
    cell = run.load_cell(CELL)
    t = cell["traffic"]
    with open(os.path.join(BENCH, "traffic", "docs-closed-16.json")) as f:
        base = json.load(f)
    assert t["clients"] == 8 == laguna()["engine_options"]["max_batch"] and t["base"] == "docs-closed-16"
    assert {k: v for k, v in t.items() if k not in ("clients", "about", "base", "first_user_tokens")} == {
        k: v for k, v in base.items() if k not in ("clients", "about", "first_user_tokens")}
    # ISSUE 50's one narrowing, made after the first eight seeds spread 5.3 %: the prompts clipped to 2,048-12,288,
    # the same median, sigma, replies and callers
    assert base["first_user_tokens"] == {"dist": "lognormal", "median": 4096, "sigma": 0.5, "min": 1024, "max": 15360}
    assert t["first_user_tokens"] == {"dist": "lognormal", "median": 4096, "sigma": 0.5, "min": 2048, "max": 12288}
    assert t["max_tokens"] == {"dist": "uniform", "min": 64, "max": 128} and t["generator"] == "sessions"
    assert (t["context_limit_tokens"], t["warmup_s"], t["drain_s"]) == (15900, 10, 60) and "think_s" not in t
    assert t["context_limit_tokens"] + 1 < laguna()["engine_options"]["max_seq"] and "coding agents" in t["about"]
    assert cell["config"]["name"] == NAME and cell["chips"] == 1
    b = benchmark_json()
    entry = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (NAME, "docs-closed-8", 1)
    assert len(entry["why"]) <= 200 and "ep = 8" in entry["why"] and "whole" in entry["why"]
    assert [w["name"] for w in b["workloads"] if w["config"] == NAME] == [CELL]  # one cell, no second
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 0
    # what the cell reports: the end-to-end pair and every entry that lists it or lists nothing
    assert {m["name"] for m in cell["end_to_end"]} == {"req_per_s", "setup_s"}
    assert {m["name"] for m in cell["per_layer"] if "workloads" in m} == {m["name"] for m in mine(b).values()}


def mine(b):
    return {m["name"]: m for m in b["per_layer"] if CELL in m.get("workloads", [])}


def test_no_new_entry_reaches_an_accepted_cell():
    """PR 39's and PR 46's refusals: an entry without a ``workloads`` list, or
    one naming an accepted cell, runs its reader in that cell's traced runs on
    the PARENT's program too. Every entry this PR adds lists this cell alone
    and has a reader of its own name; no accepted entry names this cell."""
    b = benchmark_json()
    entries = mine(b)
    assert set(entries) >= {"laguna_moe_tile_fill", *("laguna_" + a for a in ALIASES)}
    assert set(entries) <= {"laguna_moe_tile_fill", "laguna_mixed_step_roofline", *("laguna_" + a for a in ALIASES)}
    assert all(m["workloads"] == [CELL] and m["moves"] == "req_per_s" and m["name"].startswith("laguna_")
               for m in entries.values())
    accepted = {m["name"]: m for m in b["per_layer"] if m["name"] not in entries}
    for a in ALIASES:  # an alias repeats the accepted entry's unit, direction, source and layer
        assert {k: entries["laguna_" + a][k] for k in ("unit", "better", "source", "layer")} == {
            k: accepted[a][k] for k in ("unit", "better", "source", "layer")}, a
    assert (entries["laguna_moe_tile_fill"]["source"], entries["laguna_moe_tile_fill"]["layer"]) == ("program_counter", "model runner")
    if "laguna_mixed_step_roofline" in entries:
        m = entries["laguna_mixed_step_roofline"]
        assert (m["unit"], m["source"], m["layer"], m["better"]) == ("%", "device_trace", "kernels", "higher")
    for name in (*entries, "laguna_mixed_step_roofline"):
        assert callable(reader(name))
    layers = {m["layer"] for m in accepted.values()}
    assert {m["layer"] for m in entries.values()} <= layers  # no new layer name


def test_the_generator_gives_every_window_about_the_same_work():
    import itertools

    from generators import sessions

    sys.path.insert(0, BENCH)
    try:
        t = importlib.import_module("run").load_cell(CELL)["traffic"]
    finally:
        sys.path.remove(BENCH)
    some = list(itertools.islice(sessions.sessions(t, 3000000011, 3000000011, "m"), 640))
    assert all(len(s["turns"]) == 1 for s in some)
    prompts = sorted(s["turns"][0]["prompt_tokens"] for s in some)
    assert 3900 < prompts[len(prompts) // 2] < 4300 and prompts[0] >= 2048 and prompts[-1] <= 12289
    # a quarter of the prompt ROWS sit past position 4,096, YaRN's original context here
    past = sum(max(0, p - 4096) for p in prompts) / sum(prompts)
    assert 0.2 < past < 0.33
    assert all(p > 1024 + 256 for p in prompts)  # the ring (1,024 rows) wraps in every request
    assert max(s["turns"][0]["context_tokens"] for s in some) <= 15900


# -- the readers, on recorded /metrics documents -----------------------------------


def attention(**counts):
    base = {"full_decode": "pallas:flash_decode", "swa_decode": "pallas:flash_decode", "decode_block_positions": 512,
            "window": 512, "window_layers": 30, "global_layers": 10, "window_rows": 1024, "global_rows": 16384,
            "window_block_positions": 512, "rope_original_max": 4096, "heads": {"full": 48, "swa": 64}, "gate": "per_head"}
    return {**base, **counts}


def moe(**counts):
    return {"impl": "all_experts_einsum", "experts": 256, "experts_held": 32, "shared_experts": 1, "router": "softmax",
            "top_k": 8, **counts}


# this cell's engine at the window's start and end
BEFORE = [{"requests_finished": 20, "max_batch": 8, "decode_steps": 2000, "batch_occupancy": 0.8,
           "prefill_launches": 400, "prefill_tokens": 100_000, "mixed_launches": 390, "mixed_decode_lanes": 2_000,
           "attention": attention(window_decode_blocks_live=4_000, window_decode_blocks_unbounded=16_000, window_wraps=18,
                                  global_decode_rows=8_000_000, window_decode_rows=1_000_000, rows_positioned=102_000,
                                  rows_past_original_max=20_000, global_decode_blocks_stored=512_000),
           "moe": moe(assignments=816_000, rows_routed=300_000, rows_all_experts=0)}]
AFTER = [{"requests_finished": 200, "max_batch": 8, "decode_steps": 19_000, "batch_occupancy": 0.85,
          "prefill_launches": 3_800, "prefill_tokens": 950_000, "mixed_launches": 3_700, "mixed_decode_lanes": 19_000,
          "attention": attention(window_decode_blocks_live=38_000, window_decode_blocks_unbounded=190_000, window_wraps=196,
                                 global_decode_rows=93_000_000, window_decode_rows=9_700_000, rows_positioned=969_320,
                                 rows_past_original_max=250_000, global_decode_blocks_stored=4_864_000),
          "moe": moe(assignments=7_754_560, rows_routed=2_850_000, rows_all_experts=10_240)}]
# an accepted cell's engine under the PARENT's program: SmallThinker's ring, none of the new keys
PARENT = [{"requests_finished": 40, "max_batch": 8, "decode_steps": 900, "batch_occupancy": 0.9,
           "attention": {"decode": "pallas:flash_decode", "decode_block_positions": 512, "decode_blocks_live": 10,
                         "decode_blocks_stored": 100},
           "moe": {"impl": "all_experts_einsum", "experts": 64, "top_k": 6}}]
PARENT_LATER = [{**PARENT[0], "requests_finished": 95, "decode_steps": 4100}]
RESPONSES = [{"ok": True, "want_prompt_tokens": 4100, "context_tokens": 4200}, {"ok": True, "want_prompt_tokens": 9000, "context_tokens": 9100}]
CELL_DOC = {"config": laguna(), "device": {"kind": "TPU v5 lite"}, "seconds": 51.0}


def test_the_tile_fill_reader_on_recorded_documents():
    read = reader("laguna_moe_tile_fill")
    # the launches under the cut (320 rows x 32 held = 10,240) take their 320 x 8 choices out of the count
    pairs = (7_754_560 - 816_000 - 10_240 / 32 * 8) * 32 / 256
    assert read(BEFORE, AFTER, RESPONSES, None, CELL_DOC) == pytest.approx(pairs / 2_550_000)
    assert 0.3 < read(BEFORE, AFTER, RESPONSES, None, CELL_DOC) < 0.36
    for before, after in ((PARENT, PARENT_LATER), (AFTER, AFTER), ([{}], [{}]), ([], [])):
        assert read(before, after, RESPONSES, None, CELL_DOC) is None  # no counter, no launch, no document: no error
    assert read(BEFORE, AFTER, [], {"busy_s": 3.0, "modules": {}}, {}) == read(BEFORE, AFTER, RESPONSES, None, CELL_DOC)


def test_the_mixed_step_roofline_reader_on_recorded_documents():
    read = reader("laguna_mixed_step_roofline")
    family = family_of(laguna())
    trace = {"busy_s": 4.6, "modules": {"jit_prefill_with_decode": {"time_s": 4.2, "count": 280},
                                        "jit_prefill": {"time_s": 0.12, "count": 10}, "jit_decode_n": {"time_s": 0.2, "count": 12}},
             "counters_before": BEFORE, "counters_after": AFTER}
    got = read(BEFORE, AFTER, RESPONSES, trace, CELL_DOC)
    # by hand: rows and riders a launch from the counters, the riders' K/V rows by kind, the chunk's from the prompts
    launches, rows, lanes = 3_400, 850_000 / 3_400, 17_000 / 3_400
    riding = 17_000 / (867_320 - 850_000)
    reads = [family.chunk_rows_read(laguna(), p) for p in (4100, 9000)]
    pairs = [family.attended_rows(laguna(), p) for p in (4100, 9000)]
    n = sum(r["launches"] for r in reads)
    lane = {"global": riding * 85_000_000 / launches, "window": riding * 8_700_000 / launches}
    attended = {"global_rows": sum(r["global"] for r in reads) / n + lane["global"],
                "window_rows": sum(r["window"] for r in reads) / n + lane["window"],
                "global_pairs": sum(x["global"] for x in pairs) / n + lane["global"],
                "window_pairs": sum(x["window"] for x in pairs) / n + lane["window"]}
    floor = family.mixed_step_floor_s(laguna(), rows, lanes, attended, V5E)
    assert got == pytest.approx(100.0 * 290 * floor / 4.32) and 40 < got < 100
    # nothing to read: no trace, a trace without such a launch, a program without the counters, no prompt, another family
    assert read(BEFORE, AFTER, RESPONSES, None, CELL_DOC) is None
    assert read(BEFORE, AFTER, RESPONSES, {**trace, "modules": {"jit_decode_n": {"time_s": 1.0, "count": 9}}}, CELL_DOC) is None
    assert read(BEFORE, AFTER, RESPONSES, {**trace, "counters_before": PARENT, "counters_after": PARENT_LATER}, CELL_DOC) is None
    assert read(BEFORE, AFTER, RESPONSES, {**trace, "counters_before": AFTER}, CELL_DOC) is None
    assert read(BEFORE, AFTER, [], trace, CELL_DOC) is None
    assert read(BEFORE, AFTER, RESPONSES, {"modules": trace["modules"]}, CELL_DOC) is None
    with open(os.path.join(BENCH, "configs", "smallthinker-21b-ep4-1chip.json")) as f:
        other = {**CELL_DOC, "config": json.load(f)}
    assert read(BEFORE, AFTER, RESPONSES, trace, other) is None


def test_the_aliases_are_the_accepted_readers_themselves():
    for name in ALIASES:
        assert reader("laguna_" + name) is reader(name)
    assert reader("laguna_window_kv_fetch_share")(BEFORE, AFTER, RESPONSES, None, {}) == pytest.approx(34_000 / 174_000)
    assert reader("laguna_window_wraps_per_req")(BEFORE, AFTER, RESPONSES, None, {}) == pytest.approx(178 / 180)
    assert reader("laguna_long_position_rows_share")(BEFORE, AFTER, RESPONSES, None, {}) == pytest.approx(230_000 / 867_320)
    trace = {"busy_s": 4.0, "device_planes": ["/device:TPU:0"],
             "modules": {"jit_prefill_with_decode": {"time_s": 3.4, "count": 160}, "jit_decode_n": {"time_s": 0.6, "count": 12}}}
    assert reader("laguna_prefill_dev_share")(BEFORE, AFTER, RESPONSES, trace, {}) == pytest.approx(0.85)
    # the parent's program, an empty document, no trace: no reading and no error, whatever the reader
    for name in ALIASES:
        for before, after in ((PARENT, PARENT_LATER), ([{}], [{}])):
            reader("laguna_" + name)(before, after, RESPONSES, None, {})
    for name in ("window_kv_fetch_share", "window_wraps_per_req", "long_position_rows_share", "prefill_dev_share"):
        assert reader("laguna_" + name)(PARENT, PARENT_LATER, RESPONSES, None, {}) is None
