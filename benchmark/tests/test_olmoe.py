"""The ``olmoe`` family through the seam (``families/olmoe.py``), the
``gen-closed-16`` mix, and the four readers ISSUE 26 added, on the CPU at
rehearsal widths and on hand-built counter documents."""

import importlib
import itertools
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


def olmoe():
    with open(os.path.join(BENCH, "configs", "olmoe-1b-7b-1chip.json")) as f:
        return json.load(f)


def rehearsal_config(tmp_path, family="olmoe"):
    doc = {**olmoe(), **family_of(olmoe()).REHEARSAL_WIDTHS, "family": family}
    path = tmp_path / f"{family}.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_the_file_holds_the_catalogs_published_keys():
    """``model-configs`` catalog, OLMoE-1B-7B-0125-Instruct: every key of its
    ``config`` as published, but the two that ``reduced`` names."""
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe", "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
    }
    doc = olmoe()
    assert {k for k, v in published.items() if doc[k] != v} == {"max_position_embeddings"}
    assert set(doc["reduced"]) == {"max_position_embeddings", "torch_dtype"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == doc["name"])
    assert sorted(entry["reduced"]) == sorted(doc["reduced"]) and entry["source"] == doc["source"]


def test_family_builds_the_programs_config_at_published_sizes():
    from agentainer_tpu.models.configs import get_config
    import dataclasses

    doc = olmoe()
    family = family_of(doc)
    assert family.__name__ == "families.olmoe"
    cfg = family.model_config(doc)
    want = dataclasses.replace(get_config("olmoe-1b-7b"), name="olmoe-1b-7b-1chip", max_seq_len=2048)
    assert cfg == want and cfg.qk_norm and not cfg.moe_renormalize
    assert family.model_config(doc, n_layers=2).n_layers == 2
    assert family.numerics_sizes(doc) == {"layers": 2, "prefill": 96, "decode": 8, "cache_len": 256}
    with pytest.raises(ValueError):
        family.model_config({**doc, "clip_qkv": 8.0})


def test_family_arithmetic_from_the_files_sizes():
    doc = olmoe()
    family = family_of(doc)
    layer = 4 * 2048 * 2048 + 64 * 3 * 2048 * 1024 + 2048 * 64
    assert family.kv_bytes_per_token(doc) == 131072
    assert family.decode_step_bytes(doc, 0.0) == 16 * layer + 2048 * 50304
    assert family.decode_step_bytes(doc, 1000.0) - family.decode_step_bytes(doc, 0.0) == 131072000.0
    routed = 16 * (4 * 2048 * 2048 + 8 * 3 * 2048 * 1024 + 2048 * 64) + 2048 * 50304
    assert family.prefill_flops(doc, 10, 0.0) == 10 * 2.0 * routed
    assert family.prefill_flops(doc, 10, 0.0, routed=False) == 10 * 2.0 * (16 * layer + 2048 * 50304)
    assert family.prefill_flops(doc, 1, 100.0) - family.prefill_flops(doc, 1, 0.0) == 4.0 * 2048 * 100 * 16


def test_the_start_up_hook_registers_the_mixture(tmp_path):
    """As the daemon and the engine host run it: 64 -> 8 experts at rehearsal
    widths reach ``register()`` with the block's two flags."""
    env = {**CHILD_ENV, "ATPU_BENCH_CONFIG": rehearsal_config(tmp_path),
           "PYTHONPATH": os.pathsep.join([os.path.join(BENCH, "site"), CHILD_ENV["PYTHONPATH"]])}
    code = ("import sys, dataclasses, json; from agentainer_tpu.models.configs import get_config; "
            "print(json.dumps(dataclasses.asdict(get_config('olmoe-1b-7b-1chip')))); "
            "print(json.dumps(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'numpy'))))")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    cfg, heavy = map(json.loads, out.stdout.strip().splitlines()[-2:])
    assert (cfg["n_experts"], cfg["experts_per_token"], cfg["dim"], cfg["ffn_dim"]) == (8, 2, 64, 32)
    assert cfg["qk_norm"] is True and cfg["moe_renormalize"] is False and heavy == []


@pytest.mark.parametrize("family, passes", [("olmoe", True), ("olmoe_llama_reference", False)])
def test_numerics_child_holds_the_program_to_olmoes_own_reference(tmp_path, family, passes):
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.numerics_child", rehearsal_config(tmp_path, family), "7", "--rehearse"],
        env=CHILD_ENV, cwd=REPO, capture_output=True, text=True, timeout=600)
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["config"] == "olmoe-1b-7b-1chip" and doc["layers"] == 2 and doc["positions_compared"] == 40
    assert doc["ok"] is passes and out.returncode == (0 if passes else 4), doc
    if passes:
        assert doc["rel_err"] < 1e-5 and doc["share_of_positions_within"] == 1.0
    else:
        assert doc["rel_err"] > doc["tolerance"] == 0.02


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "families", "olmoe_reference.py")) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith(("import ", "from "))]
    assert lines == ["from __future__ import annotations", "import jax", "import jax.numpy as jnp"]


def test_gen_closed_16_is_the_mix_the_issue_gave():
    with open(os.path.join(BENCH, "traffic", "gen-closed-16.json")) as f:
        t = json.load(f)
    assert t["clients"] == olmoe()["engine_options"]["max_batch"] == 16  # one caller a lane
    assert t["shared_prefix_tokens"] == 0 and "think_s" not in t and (t["warmup_s"], t["drain_s"]) == (10, 60)
    gen = importlib.import_module("generators." + t["generator"])
    sessions = list(itertools.islice(gen.sessions(t, 2147480001, 2147480001, "m"), 256))
    assert sessions == list(itertools.islice(gen.sessions(t, 2147480001, 2147480001, "m"), 256))
    turns = [s["turns"] for s in sessions]
    assert all(len(ts) == 1 for ts in turns)
    prompts = [ts[0]["prompt_tokens"] for ts in turns]
    outs = [ts[0]["max_tokens"] for ts in turns]
    assert 129 <= min(prompts) and max(prompts) <= 1025 and 256 <= min(outs) and max(outs) <= 512
    assert max(ts[0]["context_tokens"] for ts in turns) <= 2000 <= olmoe()["engine_options"]["max_seq"]
    assert sum(outs) / len(outs) == pytest.approx(384, abs=4) and sum(prompts) / len(prompts) == pytest.approx(577, abs=8)
    assert len({ts[0]["message"][:64] for ts in turns}) == len(turns)  # unshared text


def moe_block(impl, experts, top_k):
    return [{"prefill_tokens": 5, "moe": {"impl": impl, "experts": experts, "top_k": top_k, "renormalize": False}}]


@pytest.mark.parametrize("docs, want", [
    (moe_block("all_experts_einsum", 64, 8), 8.0),
    (moe_block("all_experts_einsum", 8, 2), 4.0),
    (moe_block("routed_dispatch", 64, 8), None),  # nothing counts what its buffers hold yet
    (moe_block("none", 0, 0), None),  # a dense model
    ([{"prefill_tokens": 5}], None),  # a program without the block (the parent)
    ([], None),
], ids=["olmoe", "mixtral", "routed", "dense", "parent", "no_engine"])
def test_moe_rows_over_routed_reads_the_moe_block(docs, want):
    rows = importlib.import_module("layer_metrics.moe_rows_over_routed")
    assert rows.read(docs, docs, [], None, {}) == want


@pytest.mark.parametrize("name", ["engine_itl_p50_ms", "batch_occupancy", "device_wait_share", "host_ms_per_req"])
def test_olmoe_cells_read_the_accepted_readers_under_names_of_their_own(name):
    """The accepted entries' ``workloads`` lists are closed, so the new cells
    get the same ``read`` under a new name, and the same unit, direction,
    source and layer."""
    alias = importlib.import_module("layer_metrics.olmoe_" + name)
    assert alias.read is importlib.import_module("layer_metrics." + name).read
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    old, new = entries[name], entries["olmoe_" + name]
    assert new["workloads"] == ["olmoe.decode", "olmoe.longprompt"]
    assert {k: v for k, v in new.items() if k not in ("name", "workloads")} == {
        k: v for k, v in old.items() if k not in ("name", "workloads")}


def test_roofline_readers_are_the_existing_method_with_olmoes_arithmetic():
    import layer_metrics.decode_step_roofline as decode
    import layer_metrics.prefill_step_roofline as prefill
    from harness import peaks

    assert importlib.import_module("layer_metrics.moe_decode_step_roofline").read is decode.read
    assert importlib.import_module("layer_metrics.moe_prefill_step_roofline").read is prefill.read
    cfg = olmoe()
    family = family_of(cfg)
    cell = {"config": cfg, "device": {"kind": "TPU v5 lite"}, "seconds": 51.0}
    peak = peaks.peaks_of("TPU v5 lite")
    responses = [{"ok": True, "want_prompt_tokens": 512, "context_tokens": 900},
                 {"ok": True, "want_prompt_tokens": 1024, "context_tokens": 1500}]
    trace = {
        "modules": {"jit_decode_n": {"count": 10, "time_s": 2.0}, "jit_prefill": {"count": 6, "time_s": 0.3}},
        "counters_before": [{"decode_chunk_hist": {"8": 100}, "decode_steps": 100, "batch_occupancy": 1.0, "max_batch": 16}],
        "counters_after": [{"decode_chunk_hist": {"8": 110}, "decode_steps": 110, "batch_occupancy": 1.0, "max_batch": 16}],
    }
    need = family.decode_step_bytes(cfg, live_kv_tokens=16 * 1200.0)
    assert decode.read([], [], responses, trace, cell) == pytest.approx(100.0 * 80 * need / peak["hbm_bytes_per_s"] / 2.0)
    tokens = 6 * 1536 / 6
    flops = family.prefill_flops(cfg, tokens, (512 * 512 + 1024 * 1024) / (2.0 * 1536))
    assert prefill.read([], [], responses, trace, cell) == pytest.approx(100.0 * flops / 0.3 / peak["bf16_flops"])
    assert decode.read([], [], responses, {"modules": {}}, cell) is None and prefill.read([], [], responses, None, cell) is None


def test_benchmark_json_names_the_new_entries_last():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert [c["name"] for c in b["configs"]] == ["mixtral-8x7b-1chip", "olmoe-1b-7b-1chip"]
    assert [(w["name"], w["traffic"], w["chips"]) for w in b["workloads"][-2:]] == [
        ("olmoe.decode", "gen-closed-16", 1), ("olmoe.longprompt", "doc-closed", 1)]
    new = {m["name"]: m for m in b["per_layer"][-7:]}
    assert new["moe_rows_over_routed"]["workloads"] == ["olmoe.decode", "olmoe.longprompt"]
    assert new["moe_decode_step_roofline"]["workloads"] == ["olmoe.decode"]
    assert new["moe_prefill_step_roofline"]["workloads"] == ["olmoe.longprompt"]
    assert all(m["moves"] == "req_per_s" for m in new.values())
    assert len([n for n in new if n.startswith("olmoe_")]) == 4
