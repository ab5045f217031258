"""The ``kimi_linear`` family through the seam (``families/kimi_linear.py``),
the ``gen-closed-64`` mix, the cell ``kimi.decode`` and the readers ISSUE 30
added, on the CPU at rehearsal widths and on hand-built counter documents."""

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


def kimi():
    with open(os.path.join(BENCH, "configs", "kimi-linear-48b-ep8-1chip.json")) as f:
        return json.load(f)


def benchmark_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearsal_config(tmp_path):
    doc = {**kimi(), **family_of(kimi()).REHEARSAL_WIDTHS}
    path = tmp_path / "kimi.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_the_file_holds_the_catalogs_published_keys():
    """``model-configs`` catalog, Kimi-Linear-48B-A3B-Instruct: every key of
    its ``config`` as published (the nested group whole), but those that
    ``reduced`` names; no width among them."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    doc = kimi()
    assert doc["source"] == row["source_url"]
    assert {k for k, v in row["config"].items() if doc.get(k, "absent") != v} == {"num_experts", "model_max_length"}
    assert set(doc["reduced"]) == {"num_experts", "model_max_length", "torch_dtype"}
    assert (doc["num_experts"], doc["experts_published"], doc["expert_parallel"]["ep"]) == (32, 256, 8)
    entry = next(c for c in benchmark_json()["configs"] if c["name"] == doc["name"])
    assert sorted(entry["reduced"]) == sorted(doc["reduced"]) and entry["source"] == doc["source"]
    assert entry["file"] == "benchmark/configs/kimi-linear-48b-ep8-1chip.json"


def test_family_builds_the_programs_config_at_published_sizes():
    import dataclasses

    from agentainer_tpu.models.configs import get_config

    doc = kimi()
    family = family_of(doc)
    assert family.__name__ == "families.kimi_linear"
    cfg = family.model_config(doc)
    want = dataclasses.replace(
        get_config("kimi-linear-48b"), name="kimi-linear-48b-ep8-1chip", max_seq_len=4096, experts_held=32, expert_offset=0)
    assert cfg == want and cfg.n_experts == 256 and cfg.n_held == 32
    assert abs(cfg.param_count() / 7.9e9 - 1) < 0.01  # one chip's share
    five = family.model_config(doc, n_layers=5)
    assert five.layer_kinds == ("kda", "kda", "kda", "mla", "kda") and five.n_dense_layers == 1
    assert family.numerics_sizes(doc) == {"layers": 5, "prefill": 192, "decode": 8, "cache_len": 256}
    chip3 = family.model_config({**doc, "expert_parallel": {"ep": 8, "chip": 3}})
    assert (chip3.expert_offset, chip3.experts_held) == (96, 32)
    for wrong in ({"q_lora_rank": 1536}, {"mla_use_nope": False}, {"num_expert_group": 8}, {"moe_router_activation_func": "softmax"}):
        with pytest.raises(ValueError):
            family.model_config({**doc, **wrong})


def test_family_arithmetic_from_the_files_sizes():
    """ISSUE 30's reckoning, element for element."""
    doc = kimi()
    family = family_of(doc)
    lw = family.layer_weight_elements(doc)
    assert lw["kda"] == 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32  # 39.5 M
    assert lw["mla"] == 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304  # 29.1 M
    assert lw["expert"] == 3 * 2304 * 1024 and lw["dense_ffn"] == 3 * 2304 * 9216
    weights = 20 * lw["kda"] + 7 * lw["mla"] + lw["dense_ffn"] + 26 * (33 * lw["expert"] + 2304 * 256) + 2304 * 163840
    assert family.weight_bytes(doc) == weights and 7.4e9 < weights < 7.6e9
    assert family.state_bytes_per_lane(doc) == 20 * 32 * 128 * 128 * 4 + 20 * 3 * 12288 * 2  # 41.9 MB + 1.5 MB
    assert family.kv_bytes_per_token(doc) == 7 * 576 * 2 == 8064
    assert family.kernel_calls_per_step(doc) == {"kda_decode": 20, "mla_decode": 7}
    assert family.kda_decode_bytes(doc, 64) == 2 * 64 * 32 * 128 * 128 * 4  # 268 MB a layer
    assert family.mla_decode_bytes(doc, 1000.0) == 1000 * 576 * 2
    step = family.decode_step_bytes(doc, live_kv_tokens=64 * 1900.0, live_lanes=64.0)
    assert step == weights + 2 * 64 * family.state_bytes_per_lane(doc) + 64 * 1900 * 8064
    assert 14.0e9 < step < 14.5e9  # the issue's 14.9 GB counts the embedding's rows too
    assert family.decode_step_bytes(doc, 0.0) == weights + 2 * 64 * family.state_bytes_per_lane(doc)  # max_batch lanes
    routed = family.prefill_flops(doc, 256, 0.0)
    assert routed < family.prefill_flops(doc, 256, 0.0, routed=False) and routed > 2.0 * 256 * 2304 * 163840


def test_the_start_up_hook_registers_the_hybrid_block(tmp_path):
    """As the daemon and the engine host run it: the layer pattern, the held
    experts and the router rule reach ``register()``; nothing heavy is imported."""
    env = {**CHILD_ENV, "ATPU_BENCH_CONFIG": rehearsal_config(tmp_path),
           "PYTHONPATH": os.pathsep.join([os.path.join(BENCH, "site"), CHILD_ENV["PYTHONPATH"]])}
    code = ("import sys, dataclasses, json; from agentainer_tpu.models.configs import get_config; "
            "print(json.dumps(dataclasses.asdict(get_config('kimi-linear-48b-ep8-1chip')))); "
            "print(json.dumps(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'numpy'))))")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    cfg, heavy = map(json.loads, out.stdout.strip().splitlines()[-2:])
    assert cfg["layer_kinds"] == ["kda", "kda", "kda", "mla", "kda"] and cfg["moe_router"] == "sigmoid"
    assert (cfg["n_experts"], cfg["experts_held"], cfg["experts_per_token"], cfg["n_shared_experts"]) == (8, 2, 2, 1)
    assert heavy == []


def test_numerics_child_holds_the_program_to_the_familys_own_reference(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.numerics_child", rehearsal_config(tmp_path), "2147483999", "--rehearse"],
        env=CHILD_ENV, cwd=REPO, capture_output=True, text=True, timeout=900)
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["config"] == "kimi-linear-48b-ep8-1chip" and doc["layers"] == 5 and doc["positions_compared"] == 40
    assert doc["ok"] is True and out.returncode == 0, doc
    assert doc["rel_err"] < 1e-4 and doc["share_of_positions_within"] == 1.0
    assert doc["attention"]["kda_prefill"] == "xla_chunked"


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "families", "kimi_linear_reference.py")) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith(("import ", "from "))]
    assert lines == ["from __future__ import annotations", "import jax", "import jax.numpy as jnp"]


def test_gen_closed_64_is_the_mix_the_issue_gave_and_the_cell_is_named():
    with open(os.path.join(BENCH, "traffic", "gen-closed-64.json")) as f:
        t = json.load(f)
    assert t["clients"] == kimi()["engine_options"]["max_batch"] == 64  # one caller a lane
    assert t["turns"] == {"dist": "const", "value": 1} and t["shared_prefix_tokens"] == 0 and "think_s" not in t
    assert t["first_user_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 256, "max": 3072}
    assert t["max_tokens"] == {"dist": "uniform", "min": 256, "max": 512}
    assert (t["context_limit_tokens"], t["warmup_s"], t["drain_s"]) == (3600, 10, 60)
    assert t["context_limit_tokens"] + 1 < kimi()["engine_options"]["max_seq"]
    b = benchmark_json()
    cell = next(w for w in b["workloads"] if w["name"] == "kimi.decode")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("kimi-linear-48b-ep8-1chip", "gen-closed-64", 1)
    mine = {m["name"]: m for m in b["per_layer"] if m.get("workloads") == ["kimi.decode"]}
    # the two kernel readers (``kda_decode_roofline``, ``mla_decode_roofline``) have no entry: the trace's
    # ten kept ops are all loops in this program (a ``while`` holds its body's time), so they read nothing
    # in a served run (PERF.md section 7 names the harness edit that would let them in)
    assert set(mine) == {
        "kimi_decode_step_roofline", "kimi_engine_itl_p50_ms", "kimi_batch_occupancy", "kimi_device_wait_share",
        "kimi_host_ms_per_req", "kimi_prefill_dev_share", "state_resets_per_req"}
    assert all(m["moves"] == "req_per_s" for m in mine.values())
    for name in [*mine, "kda_decode_roofline", "mla_decode_roofline"]:
        assert callable(importlib.import_module("layer_metrics." + name).read)


def trace_doc(ops):
    return {
        "modules": {"jit_decode_n": {"time_s": 2.0, "count": 10}, "jit_prefill": {"time_s": 0.5, "count": 20}},
        "device_ops": ops, "busy_s": 4.0, "device_planes": ["/device:TPU:0"],
        "counters_before": [{"decode_chunk_hist": {"8": 100}, "decode_steps": 100, "batch_occupancy": 1.0, "max_batch": 64}],
        "counters_after": [{"decode_chunk_hist": {"8": 110}, "decode_steps": 110, "batch_occupancy": 1.0, "max_batch": 64}],
    }


def test_kernel_rooflines_read_the_trace_by_the_kernels_pinned_names():
    doc = kimi()
    cell = {"config": doc, "device": {"kind": "TPU v5 lite"}, "seconds": 51.0}
    responses = [{"ok": True, "context_tokens": 2000}, {"ok": True, "context_tokens": 1800}]
    trace = trace_doc([["while.3", 1.9], ["kda_decode.7", 0.8], ["kda_decode.9", 0.2], ["mla_decode.2", 0.1]])
    steps = 10 * 8  # launches in the trace x steps a launch
    kda = importlib.import_module("layer_metrics.kda_decode_roofline").read([], [], responses, trace, cell)
    assert kda == pytest.approx(100 * steps * 20 * 2 * 64 * 32 * 128 * 128 * 4 / 819e9 / 1.0)
    mla = importlib.import_module("layer_metrics.mla_decode_roofline").read([], [], responses, trace, cell)
    assert mla == pytest.approx(100 * steps * 7 * 64 * 1900 * 576 * 2 / 819e9 / 0.1)
    step = importlib.import_module("layer_metrics.kimi_decode_step_roofline").read([], [], responses, trace, cell)
    need = family_of(doc).decode_step_bytes(doc, live_kv_tokens=64 * 1900.0, live_lanes=64.0)
    assert step == pytest.approx(100 * steps * need / 819e9 / 2.0)
    # the kernel is not among the ten ops the trace keeps, or the program has none (the parent): no reading, no error
    for name in ("kda_decode_roofline", "mla_decode_roofline"):
        assert importlib.import_module("layer_metrics." + name).read([], [], responses, trace_doc([["while.3", 1.9]]), cell) is None
        assert importlib.import_module("layer_metrics." + name).read([], [], responses, None, cell) is None


def test_state_resets_per_req_reads_the_cache_block_and_nothing_from_a_program_without_one():
    read = importlib.import_module("layer_metrics.state_resets_per_req").read
    before = [{"requests_finished": 10, "cache": {"state_resets": 12}}]
    after = [{"requests_finished": 110, "cache": {"state_resets": 112}}]
    assert read(before, after, [], None, {}) == 1.0
    assert read([{"requests_finished": 10}], [{"requests_finished": 110}], [], None, {}) is None  # the parent
    assert read([{"requests_finished": 1, "cache": {"kinds": ["kv"]}}], [{"requests_finished": 9, "cache": {"kinds": ["kv"]}}], [], None, {}) is None
