"""The ``smallthinker`` family through the seam (``families/smallthinker.py``),
the ``mixed-closed-8`` mix, the cell ``smallthinker.mixed`` and the readers
ISSUE 37 added, on the CPU at rehearsal widths and on recorded ``/metrics``
documents."""

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness.family import family_of

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
CHILD_ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": os.pathsep.join([REPO, HERE])}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "smallthinker-21b-ep4-1chip"
CELL = "smallthinker.mixed"


def small():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def benchmark_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearsal_config(tmp_path):
    doc = {**small(), **family_of(small()).REHEARSAL_WIDTHS}
    path = tmp_path / "smallthinker.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_the_file_holds_the_catalogs_published_keys():
    """``model-configs`` catalog, SmallThinker-21BA3B-Instruct: every key of
    its ``config`` as published but the one ``reduced`` names that the catalog
    has (the experts held here); no width among them, no layer cut, the
    context served whole."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "SmallThinker-21BA3B-Instruct")
    doc = small()
    assert doc["source"] == row["source_url"] and doc["family"] == "smallthinker"
    assert {k for k, v in row["config"].items() if doc.get(k, "absent") != v} == {"moe_num_primary_experts"}
    assert set(doc["reduced"]) == {"moe_num_primary_experts", "torch_dtype"}
    assert (doc["moe_num_primary_experts"], doc["experts_published"]) == (16, 64)
    assert {k: doc["expert_parallel"][k] for k in ("ep", "chip", "experts")} == {"ep": 4, "chip": 0, "experts": "0-15"}
    assert doc["num_hidden_layers"] == 52 and doc["max_position_embeddings"] == 16384 and doc["vocab_size"] == 151936
    assert doc["sliding_window_layout"] == doc["rope_layout"] == [0, 1, 1, 1] * 13 and doc["sliding_window_size"] == 4096
    entry = next(c for c in benchmark_json()["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(doc["reduced"]) and entry["source"] == doc["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    for item in ("router_input", "gates", "reglu", "attention", "rope", "window", "norms"):
        assert len(doc["assumed"][item]) > 40  # each with its reason
    assert doc["engine_options"] == {"quant": "int8", "synthetic": True, "max_batch": 8, "max_seq": 16384,
                                     "speculative": False, "prefix_cache_bytes": 0}
    assert set(doc["why_engine_options"]) == {"max_batch", "max_seq", "speculative", "prefix_cache_bytes", "quant, synthetic"}
    assert "v5e-4" in doc["stands_for"] and doc["hbm_claim_bytes_per_chip"] <= 16 * 2**30


def test_family_answers_everything_a_family_is_asked():
    family = family_of(small())
    assert family.__name__ == "families.smallthinker"
    for name in ("model_config", "REHEARSAL_WIDTHS", "numerics_sizes", "program", "reference", "decode_step_bytes",
                 "prefill_flops", "kv_bytes_per_token", "ring_rows", "attended_rows", "kv_rows_read"):
        assert hasattr(family, name), name


def test_family_builds_the_programs_config_at_published_sizes():
    import dataclasses

    from agentainer_tpu.models.configs import get_config

    doc = small()
    family = family_of(doc)
    cfg = family.model_config(doc)
    assert cfg == dataclasses.replace(get_config("smallthinker-21b"), name=NAME, experts_held=16, expert_offset=0)
    assert cfg.head_dim == 128 and cfg.n_experts == 64 and cfg.n_held == 16 and cfg.experts_per_token == 6
    assert cfg.param_count() == family.param_count(doc) and abs(cfg.param_count() / 6.78e9 - 1) < 0.005
    chip2 = family.model_config({**doc, "expert_parallel": {"ep": 4, "chip": 2}})
    assert (chip2.experts_held, chip2.expert_offset) == (16, 32)
    whole = family.model_config({**doc, "moe_num_primary_experts": 64})
    assert (whole.experts_held, whole.expert_offset) == (0, 0) and abs(whole.param_count() / 21.5e9 - 1) < 0.01
    four = family.model_config(doc, n_layers=4)
    assert four.window_layers == four.rope_layers == (0, 1, 1, 1) and (four.n_global, four.n_window) == (1, 3)
    sizes = family.numerics_sizes(doc)
    assert sizes == {"layers": 4, "prefill": 5632, "decode": 8, "cache_len": 6144}
    # the prefill wraps the ring: past R + 512, fed in the engine's chunks
    assert sizes["prefill"] >= family.ring_rows(doc) + 512 >= 5120 and sizes["prefill"] % family.PREFILL_CHUNK == 0
    for wrong in ({"rope_scaling": {"type": "yarn"}}, {"tie_word_embeddings": True}, {"norm_topk_prob": False},
                  {"rope_layout": [0, 1]}):
        with pytest.raises(ValueError):
            family.model_config({**doc, **wrong})


def test_family_arithmetic_against_hand_counts():
    """ISSUE 37's reckoning, element for element, against a count made here
    with nothing of the family's: 21.14 M a layer outside its experts, 5.90 M
    an expert, 6.78 B held, 2,048 B a token a layer, 804 MB a lane."""
    doc = small()
    family = family_of(doc)
    lw = family.layer_weight_elements(doc)
    q, kv, o, router = 2560 * 28 * 128, 2 * 2560 * 4 * 128, 28 * 128 * 2560, 2560 * 64
    assert lw == {"attention": q + kv + o, "expert": 3 * 2560 * 768, "router": router}
    assert lw["attention"] + lw["router"] == 21_135_360 and lw["expert"] == 5_898_240
    layer = lw["attention"] + router + 16 * lw["expert"]
    assert family.weight_bytes(doc) == 52 * layer + 2560 * 151936 and 6.3e9 < family.weight_bytes(doc) < 6.5e9
    assert family.param_count(doc) == 52 * (layer + 2 * 2560) + 2 * 2560 * 151936 + 2560
    assert family.row_bytes(doc) == 2 * 4 * 128 * 2 == 2048
    assert family.ring_rows(doc) == 4608 == family.ring_rows({**doc, "engine_options": {**doc["engine_options"], "prefill_chunk": 512}})
    assert family.ring_rows({**doc, "engine_options": {"max_seq": 2048}}) == 2048  # never more than the arena
    lane = 13 * 2048 * 16384 + 39 * 2048 * 4608
    assert family.kv_resident_bytes_per_lane(doc) == lane and 800e6 < lane < 810e6
    # were the window layers to hold the context: 1,745 MB a lane, 14.0 GB for 8
    assert 52 * 2048 * 16384 == 1_744_830_464 and 8 * 52 * 2048 * 16384 + family.weight_bytes(doc) > 15.75e9
    assert 8 * lane + family.param_count(doc) < 13.3e9
    # a token inside the window costs every layer a row; a long context's mean token fewer
    assert family.kv_bytes_per_token(doc) == 52 * 2048 == family.kv_bytes_per_token(doc, 4096.0)
    assert family.kv_bytes_per_token(doc, 16384.0) == 2048 * (13 * 16384 + 39 * 4096) / 16384
    assert family.kv_rows_read(doc, 300.0) == {"global": 300.0, "window": 300.0}
    assert family.kv_rows_read(doc, 9000.0) == {"global": 9000.0, "window": 4096.0}
    # a decode step: the weights once and the rows the lanes' queries see
    ctx = np.array([300, 2000, 5000, 12000, 700, 4096, 4097, 9000], float)
    rows = 13 * ctx.sum() + 39 * np.minimum(ctx, 4096).sum()
    assert family.decode_step_bytes(doc, kv_bytes=2048 * rows) == family.weight_bytes(doc) + 2048 * rows
    even = family.decode_step_bytes(doc, live_kv_tokens=8 * 6000.0, lanes=8.0)
    assert even == family.weight_bytes(doc) + 8 * 2048 * (13 * 6000 + 39 * 4096)
    assert family.decode_step_bytes(doc) == family.weight_bytes(doc)
    # prefill: the keys a prompt's tokens attend to, by kind of layer
    for p in (100, 4096, 4097, 12000):
        by_hand = {"global": sum(j for j in range(p)), "window": sum(min(j, 4096) for j in range(p))}
        got = family.attended_rows(doc, p)
        assert abs(got["global"] / (by_hand["global"] + p / 2) - 1) < 1e-9
        assert abs(got["window"] / by_hand["window"] - 1) < 2e-2  # a continuous count of a discrete sum
    matmul = 2.0 * (52 * (lw["attention"] + router + 6 * 16 / 64 * lw["expert"]) + 2560 * 151936)
    assert family.prefill_flops(doc, 256, 0.0) == 256 * matmul
    attn = 4.0 * 28 * 128 * (13 * 6000.0 + 39 * 3000.0)
    assert family.prefill_flops(doc, 1, 6000.0, mean_window_context=3000.0) == pytest.approx(matmul + attn)
    assert family.prefill_flops(doc, 1, 6000.0) == pytest.approx(matmul + 4.0 * 28 * 128 * (13 * 6000.0 + 39 * 4096.0))
    every = family.prefill_flops(doc, 1, 0.0, routed=False)
    assert every == 2.0 * (52 * layer + 2560 * 151936)
    # a 12k-token prompt: 36 TFLOP of attention, 13.4 global and 22.8 window (40 if the window layers saw everything;
    # ISSUE 37 reckoned the window layers at 28 by counting every query at the full window)
    seen = family.attended_rows(doc, 12000)
    tf = lambda layers, rows: 4.0 * 28 * 128 * layers * rows / 1e12  # noqa: E731
    assert 13 < tf(13, seen["global"]) < 14 and 22 < tf(39, seen["window"]) < 23.5 and 39 < tf(39, seen["global"]) < 41


def test_the_start_up_hook_registers_the_block(tmp_path):
    env = {**CHILD_ENV, "ATPU_BENCH_CONFIG": rehearsal_config(tmp_path),
           "PYTHONPATH": os.pathsep.join([os.path.join(BENCH, "site"), CHILD_ENV["PYTHONPATH"]])}
    code = ("import sys, dataclasses, json; from agentainer_tpu.models.configs import get_config; "
            f"print(json.dumps(dataclasses.asdict(get_config('{NAME}')))); "
            "print(json.dumps(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'numpy'))))")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    cfg, heavy = map(json.loads, out.stdout.strip().splitlines()[-2:])
    assert cfg["window_layers"] == cfg["rope_layers"] == [0, 1, 1, 1, 0, 1, 1, 1] and cfg["window"] == 4096
    assert (cfg["head_size"], cfg["dim"], cfg["n_heads"], cfg["n_kv_heads"]) == (16, 48, 6, 2)
    assert (cfg["n_experts"], cfg["experts_held"], cfg["expert_offset"], cfg["experts_per_token"]) == (8, 2, 0, 2)
    assert cfg["ffn_act"] == "relu" and cfg["early_router"] and cfg["moe_renormalize"]
    assert heavy == []


def test_numerics_child_holds_the_program_to_the_familys_own_reference(tmp_path):
    """At rehearsal widths with the window at its published 4,096: 5,632
    tokens in chunks of 256 through a ring of 4,352 rows (wrapped), then 8
    decode steps, against the reference's full forward."""
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.numerics_child", rehearsal_config(tmp_path), "2147483999", "--rehearse"],
        env=CHILD_ENV, cwd=REPO, capture_output=True, text=True, timeout=900)
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["config"] == NAME and doc["layers"] == 4 and doc["positions_compared"] == 40
    assert doc["ok"] is True and out.returncode == 0, doc
    assert doc["rel_err"] < 1e-4 and doc["share_of_positions_within"] == 1.0


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "families", "smallthinker_reference.py")) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith(("import ", "from "))]
    assert lines == ["from __future__ import annotations", "import jax", "import jax.numpy as jnp"]


def test_mixed_closed_8_is_the_mix_the_issue_gave_and_the_cell_is_named():
    with open(os.path.join(BENCH, "traffic", "mixed-closed-8.json")) as f:
        t = json.load(f)
    assert t["generator"] == "sessions" and t["clients"] == 8 == small()["engine_options"]["max_batch"]
    assert t["shared_prefix_tokens"] == 0 and t["turns"] == {"dist": "const", "value": 1} and "think_s" not in t
    assert t["first_user_tokens"] == {"dist": "lognormal", "median": 2048, "sigma": 1.0, "min": 256, "max": 14336}
    assert t["max_tokens"] == {"dist": "uniform", "min": 128, "max": 384}
    assert (t["context_limit_tokens"], t["warmup_s"], t["drain_s"]) == (15000, 10, 60)
    assert t["context_limit_tokens"] + 1 < small()["engine_options"]["max_seq"]
    b = benchmark_json()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "mixed-closed-8", 1) and len(cell["why"]) <= 200
    assert [w["name"] for w in b["workloads"] if w["config"] == NAME] == [CELL]  # one cell, no second
    mine = {m["name"]: m for m in b["per_layer"] if m.get("workloads") == [CELL]}
    assert set(mine) == {
        "window_kv_fetch_share", "window_wraps_per_req", "smallthinker_engine_itl_p50_ms",
        "smallthinker_batch_occupancy", "smallthinker_device_wait_share", "smallthinker_host_ms_per_req"}
    assert all(m["moves"] == "req_per_s" for m in mine.values())
    assert (mine["window_kv_fetch_share"]["better"], mine["window_kv_fetch_share"]["source"]) == ("lower", "program_counter")
    assert mine["window_wraps_per_req"]["layer"] == "cache manager"
    # written, tested and WITHOUT an entry (PERF.md section 7): the three readers that need a module or an op to be in
    # the capture. This cell's capture is 1.4-2.7 s (52 layers fill the profiler's buffer) and held no ``jit_decode_n``
    # launch at all in one traced run of two (every step rode a chunk): a line that lacks a listed metric refuses the PR
    unlisted = ("smallthinker_decode_step_roofline", "smallthinker_prefill_step_roofline", "window_decode_roofline")
    assert not any(m["name"] in unlisted for m in b["per_layer"])
    for name in [*mine, *unlisted]:
        assert callable(importlib.import_module("layer_metrics." + name).read)


def test_the_generator_makes_prompts_of_the_shape_the_cell_is_for():
    import itertools

    from generators import sessions

    with open(os.path.join(BENCH, "traffic", "mixed-closed-8.json")) as f:
        t = json.load(f)
    some = list(itertools.islice(sessions.sessions(t, 3000000011, 3000000011, "m"), 640))
    assert all(len(s["turns"]) == 1 for s in some)
    prompts = sorted(s["turns"][0]["prompt_tokens"] for s in some)
    assert 1900 < prompts[len(prompts) // 2] < 2200 and prompts[0] >= 256 and prompts[-1] <= 14337
    past_window = sum(p > 4096 for p in prompts) / len(prompts)
    past_8k = sum(p > 8192 for p in prompts) / len(prompts)
    assert 0.2 < past_window < 0.3 and 0.06 < past_8k < 0.11  # a quarter; one in twelve
    assert max(s["turns"][0]["context_tokens"] for s in some) <= 15000
    out = [s["turns"][0]["max_tokens"] for s in some]
    assert 128 <= min(out) and max(out) <= 384 and 240 < sum(out) / len(out) < 272


# -- the readers, on recorded /metrics documents -----------------------------------


def attention(**counts):
    return {"window": 4096, "window_layers": 39, "global_layers": 13, "window_rows": 4608, "global_rows": 16384,
            "decode_block_positions": 512, "window_block_positions": 512, **counts}


BEFORE = [{
    "requests_finished": 10, "max_batch": 8, "decode_steps": 100, "batch_occupancy": 0.9, "decode_chunk_hist": {"8": 10, "1": 20},
    "attention": attention(window_decode_blocks_live=1000, window_decode_blocks_unbounded=1200, window_decode_blocks_stored=7200,
                           global_decode_blocks_live=1200, global_decode_blocks_stored=25600, window_wraps=2,
                           global_decode_rows=500_000, window_decode_rows=400_000),
}]
AFTER = [{
    "requests_finished": 90, "max_batch": 8, "decode_steps": 1100, "batch_occupancy": 0.95, "decode_chunk_hist": {"8": 110, "1": 220},
    "attention": attention(window_decode_blocks_live=41_000, window_decode_blocks_unbounded=61_200, window_decode_blocks_stored=79_200,
                           global_decode_blocks_live=61_200, global_decode_blocks_stored=281_600, window_wraps=22,
                           global_decode_rows=26_100_000, window_decode_rows=18_000_000),
}]
PARENT = [{"requests_finished": 40, "max_batch": 8, "attention": {"decode_block_positions": 512, "decode_blocks_live": 5, "decode_blocks_stored": 9}}]
RESPONSES = [{"ok": True, "want_prompt_tokens": 900, "context_tokens": 1000}, {"ok": True, "want_prompt_tokens": 9000, "context_tokens": 9200},
             {"ok": False, "want_prompt_tokens": 5000, "context_tokens": 3000}]


def reader(name):
    return importlib.import_module("layer_metrics." + name).read


def test_window_counters_readers_on_recorded_documents():
    assert reader("window_kv_fetch_share")(BEFORE, AFTER, RESPONSES, None, {}) == pytest.approx(40_000 / 60_000)
    assert reader("window_wraps_per_req")(BEFORE, AFTER, RESPONSES, None, {}) == pytest.approx(20 / 80)
    # a program without the ring (the parent), and a window in which nothing decoded or finished: no reading, no error
    for name in ("window_kv_fetch_share", "window_wraps_per_req"):
        assert reader(name)(PARENT, PARENT, RESPONSES, None, {}) is None
        assert reader(name)(AFTER, AFTER, RESPONSES, None, {}) is None
        assert reader(name)([{}], [{}], [], None, {}) is None


def test_the_aliases_are_the_accepted_readers_themselves():
    for name in ("engine_itl_p50_ms", "batch_occupancy", "device_wait_share", "host_ms_per_req"):
        assert reader("smallthinker_" + name) is reader(name)


def trace_doc(ops, before=BEFORE, after=AFTER):
    return {
        "modules": {"jit_decode_n": {"time_s": 2.0, "count": 10}, "jit_prefill": {"time_s": 1.5, "count": 30},
                    "jit_prefill_with_decode": {"time_s": 0.5, "count": 10}},
        "device_ops": ops, "busy_s": 4.5, "device_planes": ["/device:TPU:0"],
        "counters_before": before, "counters_after": after,
    }


def test_rooflines_read_the_trace_the_counters_and_the_familys_bytes():
    doc = small()
    family = family_of(doc)
    cell = {"config": doc, "device": {"kind": "TPU v5 lite"}, "seconds": 51.0}
    trace = trace_doc([["while.3", 1.9], ["flash_decode.7", 0.5], ["flash_decode.9", 0.3]])
    # steps the counters saw: stored blocks / (8 lanes x 32 blocks of 512 in 16,384 rows) = 1,000, riders included
    counted = (281_600 - 25_600) / (8 * 32)
    assert counted == 1000
    kv = 2048 * (13 * 25_600_000 + 39 * 17_600_000) / counted
    steps = 10 * (100 * 8 + 200 * 1) / 300  # launches in the trace x the mean steps a launch
    step = reader("smallthinker_decode_step_roofline")([], [], RESPONSES, trace, cell)
    assert step == pytest.approx(100 * steps * (family.weight_bytes(doc) + kv) / 819e9 / 2.0) and 0 < step < 100
    kernel = reader("window_decode_roofline")([], [], RESPONSES, trace, cell)
    assert kernel == pytest.approx(100 * (steps + 10) * kv / 819e9 / 0.8) and 0 < kernel < 100  # + the ten mixed launches' steps
    # prefill: both prefill modules' launches and time, the keys summed prompt by prompt
    ok = [r["want_prompt_tokens"] for r in RESPONSES if r["ok"]]
    tokens = 40 * sum(ok) / sum(-(-p // 256) for p in ok)
    seen = [family.attended_rows(doc, p) for p in ok]
    flops = family.prefill_flops(doc, tokens, sum(s["global"] for s in seen) / sum(ok),
                                 mean_window_context=sum(s["window"] for s in seen) / sum(ok))
    got = reader("smallthinker_prefill_step_roofline")([], [], RESPONSES, trace, cell)
    assert got == pytest.approx(100 * flops / 2.0 / 197e12) and 0 < got < 100
    # a window layer is NOT counted at the prompt's length: the accepted reader's one mean context would count more
    assert flops < family.prefill_flops(doc, tokens, sum(s["global"] for s in seen) / sum(ok), mean_window_context=sum(s["global"] for s in seen) / sum(ok))
    # the kernel is not among the ops the trace keeps (a served run), there is no trace, or the program has no ring
    assert reader("window_decode_roofline")([], [], RESPONSES, trace_doc([["while.3", 1.9]]), cell) is None
    for name in ("window_decode_roofline", "smallthinker_decode_step_roofline", "smallthinker_prefill_step_roofline"):
        assert reader(name)([], [], RESPONSES, None, cell) is None
    parent = trace_doc([["flash_decode.7", 0.5]], before=PARENT, after=PARENT)
    for name in ("window_decode_roofline", "smallthinker_decode_step_roofline"):
        assert reader(name)([], [], RESPONSES, parent, cell) is None
    # another family's configuration: no ``attended_rows``, nothing read
    with open(os.path.join(BENCH, "configs", "olmoe-1b-7b-1chip.json")) as f:
        other = {"config": json.load(f), "device": {"kind": "TPU v5 lite"}}
    assert reader("smallthinker_prefill_step_roofline")([], [], RESPONSES, trace, other) is None
