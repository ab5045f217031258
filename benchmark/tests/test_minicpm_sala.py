"""The ``minicpm_sala`` family through the seam (``families/minicpm_sala.py``),
the ``longdoc-sessions-8`` mix, the cell ``sala.longdoc`` and the readers ISSUE
54 added, on the CPU at rehearsal widths and on recorded ``/metrics``
documents: this cell's, and an accepted cell's that lack the new keys (the
parent's program under this PR's benchmark files: every new reader answers
``None`` and none raises)."""

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
NAME = "minicpm-sala-9b-1chip"
CELL = "sala.longdoc"
REDUCED = {"max_position_embeddings", "torch_dtype"}
ALIASES = ("mixed_launch_ms", "engine_itl_p50_ms", "batch_occupancy", "device_wait_share", "host_ms_per_req")
OWN = ("sala_sparse_rows_read_share", "sala_sparse_steps_share", "sala_first_turns_in_window",
       "sala_snapshot_mb_per_req", "sala_live_context_rows")


def sala():
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
        row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
    doc = sala()
    assert doc["source"] == row["source_url"] and doc["family"] == "minicpm_sala" and doc["model_type"] == "minicpm_sala"
    changed = {k for k, v in row["config"].items() if doc.get(k, "absent") != v}
    assert changed == {"max_position_embeddings"} and doc["mixer_types"] == row["config"]["mixer_types"]
    assert set(doc["reduced"]) == REDUCED
    assert (doc["num_hidden_layers"], doc["max_position_embeddings"], doc["context_published"]) == (32, 49152, 524288)
    entry = next(c for c in benchmark_json()["configs"] if c["name"] == NAME)
    assert set(entry["reduced"]) == REDUCED and entry["source"] == doc["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    points = [k for k in doc["assumed"] if k[0].isdigit()]
    assert len(points) == 8 and all(len(doc["assumed"][k]) > 40 for k in points)  # the eight points, each with its source
    assert doc["engine_options"] == {"quant": "int8", "synthetic": True, "max_batch": 8, "max_seq": 49152}
    assert doc["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "init_blocks": 1,
                                    "window_size": 2048, "topk": 64, "dense_len": 8192}
    live = doc["memory"]["compiled_live_bytes"]
    assert set(live) == {"decode", "prefill", "mixed"} and all(13.0e9 < v < 15.75e9 for v in live.values())
    assert 0.25 * 16e9 < doc["hbm_claim_bytes_per_chip"] <= 16 * 2**30


def test_the_reference_names_each_assumed_point_and_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "families", "minicpm_sala_reference.py")) as f:
        text = f.read()
    for n in range(1, 9):
        assert f"assumed ({n})" in text, n
    assert "agentainer_tpu" not in text.split('"""', 2)[2]


def test_the_cell_and_its_entries_are_appended_with_closed_lists():
    bench = benchmark_json()
    assert bench["workloads"][-1] == {**bench["workloads"][-1], "name": CELL, "config": NAME,
                                      "traffic": "longdoc-sessions-8", "chips": 1}
    assert bench["configs"][-1]["name"] == NAME and len(bench["workloads"]) == 10
    mine = [m for m in bench["per_layer"] if m["name"].startswith("sala_")]
    assert [m["name"] for m in bench["per_layer"]][-len(mine):] == [m["name"] for m in mine]
    assert {m["name"] for m in mine} >= set(OWN) | {"sala_" + a for a in ALIASES}
    layers = {m["layer"] for m in bench["per_layer"] if not m["name"].startswith("sala_")}
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "req_per_s" and m["layer"] in layers
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    for alias in ALIASES:
        assert reader("sala_" + alias) is reader(alias)


def test_the_traffic_is_the_issues_parameter_for_parameter():
    with open(os.path.join(BENCH, "traffic", "longdoc-sessions-8.json")) as f:
        p = json.load(f)
    assert (p["generator"], p["clients"], p["shared_prefix_tokens"], p["context_limit_tokens"], p["drain_s"]) == (
        "sessions", 8, 0, 48000, 60)
    assert p["turns"] == {"dist": "const", "value": 400} and "think_s" not in p
    assert p["first_user_tokens"] == {"dist": "uniform", "min": 12288, "max": 20480}
    assert p["later_user_tokens"] == {"dist": "lognormal", "median": 128, "sigma": 0.6, "min": 32, "max": 512}
    assert p["max_tokens"] == {"dist": "uniform", "min": 32, "max": 64}
    assert p["warmup_s"] % 10 == 0 and 40 <= p["warmup_s"] <= 150
    gen = importlib.import_module("generators.sessions")
    for seed in (1, 2**31 + 11):
        stream = gen.sessions(p, seed, seed, "m")
        for _ in range(8):
            turns = next(stream)["turns"]
            # a session ends by its context, with room for twice the turns a run holds
            assert 100 < len(turns) < 400 and turns[-1]["context_tokens"] <= 48000
            assert 12288 <= turns[0]["user_tokens"] <= 20480 and all(32 <= t["user_tokens"] <= 512 for t in turns[1:])
            assert all(t["context_tokens"] > 8192 for t in turns)  # every turn past dense_len


def test_family_builds_the_programs_config_at_published_sizes():
    import dataclasses

    from agentainer_tpu.models.configs import get_config

    doc = sala()
    family = family_of(doc)
    cfg = family.model_config(doc)
    assert cfg == dataclasses.replace(get_config("minicpm-sala"), name=NAME, max_seq_len=49152)
    sizes = family.numerics_sizes(doc)
    assert sizes == {"layers": 4, "prefill": 8704, "decode": 8, "cache_len": 8768}
    four = family.model_config(doc, n_layers=4)
    assert four.layer_kinds == ("sparse", "lightning", "lightning", "sparse") and four.residual_scale == cfg.residual_scale
    # the compared positions and every decode step choose 64 of 137 blocks, 33 of them forced
    assert sizes["prefill"] - 32 > cfg.sparse_dense_len and sizes["cache_len"] // 64 == 137 and cfg.sparse_forced_blocks == 33
    for wrong in ({"attn_use_rope": True}, {"lightning_use_rope": False}, {"qk_norm": False}, {"use_output_gate": False},
                  {"lightning_nkv": 8}, {"tie_word_embeddings": True}, {"mixer_types": ["minicpm4", "mamba"] * 16}):
        with pytest.raises(ValueError):
            family.model_config({**doc, **wrong})


def test_family_arithmetic_against_hand_counts_and_the_programs():
    doc = sala()
    family = family_of(doc)
    cfg = family.model_config(doc)
    lw = family.layer_weight_elements(doc)
    assert lw["sparse"] == 3 * 4096 * 4096 + 2 * 4096 * 256 == 52_428_800
    assert lw["lightning"] == 5 * 4096 * 4096 == 83_886_080 and lw["ffn"] == 3 * 4096 * 16384 == 201_326_592
    matrices = 8 * (lw["sparse"] + lw["ffn"]) + 24 * (lw["lightning"] + lw["ffn"]) + 2 * 4096 * 73448
    assert matrices == 9_476_833_280
    assert family.param_count(doc) == cfg.param_count() == matrices + 373_504
    assert family.weight_bytes(doc) == matrices - 4096 * 73448 and 9.1e9 < family.weight_bytes(doc) < 9.2e9
    assert family.state_bytes_per_lane(doc) == 24 * 32 * 128 * 128 * 4 == 50_331_648
    assert family.kv_bytes_per_token(doc) == 8192 and family.pooled_bytes_per_token(doc) == 256.0
    assert family.cache_bytes(doc) == {"k+v": 3_221_225_472, "ck": 100_663_296, "state": 402_653_184}
    assert cfg.param_count() + sum(family.cache_bytes(doc).values()) < 0.84 * 15.75e9  # 13.2 GB before temporaries
    # a query under dense_len reads its rows; one past it 64 blocks of 64, and scores a pooled key every 16 rows
    assert family.rows_read(doc, 5000) == 5000 and family.rows_read(doc, 30000) == 4096
    assert family.sparse_attend_bytes(doc, 30000) == 2 * 4096 * 2 * 128 * 2 == 4_194_304
    assert family.sparse_index_bytes(doc, 30000) == (30000 - 31) / 16 * 512
    assert family.sparse_index_flops(doc, 30000, rows=256) == 2.0 * 256 * 32 * 128 * (30000 - 31) / 16
    assert family.sparse_attend_flops(doc, 30000) == 4.0 * 32 * 128 * 4096
    assert family.sparse_attend_flops(doc, 30000, rows=256, masked=True) == 4.0 * 256 * 32 * 128 * 30000
    assert family.lightning_step_bytes(doc, 8) == 2 * 8 * 32 * 128 * 128 * 4 == 33_554_432
    assert family.lightning_chunk_flops(doc, 256) == 32 * (4.0 * 64 * 64 * 128 + 4.0 * 64 * 128 * 128) * 4
    step = family.decode_step_bytes(doc, [30000.0] * 8)
    want = family.weight_bytes(doc) + 2 * 8 * 50_331_648 + 8 * 8 * (4_194_304 + (30000 - 31) / 16 * 512)
    assert step == pytest.approx(want) and 10.2e9 < step < 10.4e9
    assert family.weight_bytes(doc) / step > 0.85  # the weights are most of a step's bytes
    assert family.decode_step_floor_s(doc, [30000.0] * 8, 819e9) == pytest.approx(want / 819e9)
    # reading every live row in the 8 sparse layers would add 8 x 8 x (30000 - 4096) x 1 KB
    assert 8 * 8 * (30000 - 4096) * 1024 > 1.6e9
    # the program's own per-token model agrees past dense_len: the chosen rows and the pooled keys
    assert cfg.flops_per_token(30000) - cfg.flops_per_token(0) == pytest.approx(
        8 * (family.sparse_attend_flops(doc, 30000) + 2.0 * 32 * 128 * 30000 / 16))


RECORDED = {
    "attention": {"sparse": {"steps_dense": 10, "steps_sparse": 990, "rows_live": 30_000_000, "rows_read": 4_100_000,
                             "blocks_live": 1, "blocks_selected": 1, "blocks_forced": 1, "pooled_rows_scored": 1}},
    "kv_snapshots": 3, "kv_snapshot_bytes": 600_000_000,
}
ZERO = {"attention": {"sparse": {k: 0 for k in RECORDED["attention"]["sparse"]}}, "kv_snapshots": 0, "kv_snapshot_bytes": 0}
PARENT = {"attention": {"decode_blocks_live": 5}, "kv_snapshots": 2, "phases": {"engine.snapshot": {"n": 2, "total_s": 1.0}}}


def test_readers_on_recorded_documents():
    responses = [{"ok": True, "turn": 3, "context_tokens": 30000}] * 5 + [{"ok": True, "turn": 0, "context_tokens": 15000}]
    args = ([ZERO], [RECORDED], responses, None, {"config": sala()})
    assert reader("sala_sparse_rows_read_share")(*args) == pytest.approx(4.1 / 30)
    assert reader("sala_sparse_steps_share")(*args) == 0.99
    assert reader("sala_live_context_rows")(*args) == 30000.0
    assert reader("sala_first_turns_in_window")(*args) == 1.0
    assert reader("sala_first_turns_in_window")([ZERO], [RECORDED], responses[:5], None, {}) == 0.0
    assert reader("sala_snapshot_mb_per_req")(*args) == 100.0
    trace = {"modules": {"jit_decode_n.1": {"count": 10, "time_s": 0.2}},
             "counters_before": [{"decode_chunk_hist": {"1": 0, "8": 0}, "max_batch": 8, "batch_occupancy_sum": 0, "batch_occupancy_n": 0}],
             "counters_after": [{"decode_chunk_hist": {"1": 4, "8": 6}, "max_batch": 8, "batch_occupancy_sum": 0, "batch_occupancy_n": 0}]}
    mod = importlib.import_module("layer_metrics.sala_decode_step_roofline")
    mod.live_lanes = lambda *a: 8.0
    got = mod.read([ZERO], [RECORDED], responses, trace, {"config": sala(), "device": {"kind": "TPU v5e"}})
    family = family_of(sala())
    mean = (5 * 30000 + 15000) / 6
    assert got == pytest.approx(100.0 * 10 * family.decode_step_floor_s(sala(), [mean] * 8, 819e9, live_lanes=8.0) / 0.2)
    assert 0 < got < 100


@pytest.mark.parametrize("name", OWN + ("sala_decode_step_roofline",))
def test_readers_give_none_on_a_parents_counters(name):
    """The parent's program has no ``attention.sparse`` block and no
    ``kv_snapshot_bytes``; an accepted cell's family has no
    ``decode_step_floor_s``: every reader answers ``None`` and none raises."""
    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b-1chip.json")) as f:
        cell = {"config": json.load(f), "device": {"kind": "TPU v5e"}}
    trace = {"modules": {"jit_decode_n.1": {"count": 10, "time_s": 0.2}},
             "counters_before": [dict(PARENT, decode_chunk_hist={"1": 0})], "counters_after": [dict(PARENT, decode_chunk_hist={"1": 4})]}
    responses = [{"ok": True, "turn": 1, "context_tokens": 100}]
    got = reader(name)([PARENT], [PARENT], responses, trace, cell)
    assert got is None or name == "sala_first_turns_in_window" and got == 0.0
    assert reader(name)([], [], [], None, cell) is None


def test_numerics_child_holds_the_program_to_the_familys_own_reference(tmp_path):
    """At rehearsal widths: a prefill fed 40 rows a launch (splitting pooling
    kernels of 8) to 200 rows, past ``dense_len`` 96, and 8 decode steps, all
    compared positions choosing their blocks."""
    doc = {**sala(), **family_of(sala()).REHEARSAL_WIDTHS}
    path = tmp_path / "sala.json"
    path.write_text(json.dumps(doc))
    out = subprocess.run([sys.executable, "-m", "benchmark.harness.numerics_child", str(path), str(2**31 + 5), "--rehearse"],
                         env=CHILD_ENV, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["ok"] and line["layers"] == 4 and line["positions_compared"] == 40 and line["rel_err"] < 1e-4
    assert line["attention"]["sparse_prefill"] == "xla:block_mask" and line["attention"]["lightning_decode"] == "xla_step"
