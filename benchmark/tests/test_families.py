"""The family seam (``harness/family.py``): the ``llama`` family answers what
the hard-wired code answered before it (PR 25; the expected values are the
old code's, kept as literals), no harness file names a block, and a family
that is not Llama's — other key names, its own reference — is carried by
new files alone (``tests/families/``, found through ``PYTHONPATH``)."""

import glob
import importlib
import json
import os
import re
import subprocess
import sys

import pytest

from harness import trace_reduce
from harness.family import family_of

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
# what a child needs to find the test-only families beside the benchmark's
CHILD_ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": os.pathsep.join([REPO, HERE])}

RENAMED_KEYS = {
    "name": "renamed-keys-test", "hidden_size": 64, "ffn_hidden_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_kv_heads": 2, "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "num_experts": 8, "num_experts_per_tok": 2, "max_position_embeddings": 2048,
}


def mixtral():
    with open(os.path.join(BENCH, "configs", "mixtral-8x7b-1chip.json")) as f:
        return json.load(f)


def test_a_configuration_without_a_family_is_llamas():
    assert "family" not in mixtral() and family_of(mixtral()).__name__ == "families.llama"
    with pytest.raises(ValueError):
        family_of({"family": "../site/sitecustomize"})
    with pytest.raises(ModuleNotFoundError):
        family_of({"family": "no_such_block"})


@pytest.mark.parametrize("rehearsal, n_layers, want", [
    (False, None, dict(vocab_size=32000, dim=4096, n_layers=6, n_heads=32, n_kv_heads=8, ffn_dim=14336)),
    (False, 1, dict(vocab_size=32000, dim=4096, n_layers=1, n_heads=32, n_kv_heads=8, ffn_dim=14336)),
    (True, None, dict(vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128)),
])
def test_llama_family_maps_the_file_as_model_fields_did(rehearsal, n_layers, want):
    from agentainer_tpu.models.configs import ModelConfig

    doc, family = mixtral(), family_of(mixtral())
    if rehearsal:
        doc = {**doc, **family.REHEARSAL_WIDTHS}
    assert family.model_config(doc, n_layers) == ModelConfig(
        name="mixtral-8x7b-1chip", max_seq_len=2048, rope_theta=1000000.0, norm_eps=1e-05, n_experts=8,
        experts_per_token=2, **want)


def test_llama_family_sizes_the_numerics_check_as_before():
    doc, family = mixtral(), family_of(mixtral())
    # one Mixtral layer is 5.6 GB in float32: two would not fit beside the int8 weights
    assert family.numerics_sizes(doc) == {"layers": 1, "prefill": 96, "decode": 8, "cache_len": 256}
    assert family.numerics_sizes({**doc, **family.REHEARSAL_WIDTHS})["layers"] == 2


def test_llama_family_arithmetic_and_the_roofline_readers_read_as_before():
    doc = mixtral()
    family = family_of(doc)
    assert family.decode_step_bytes(doc, 1000.0) == 8863219712.0
    assert family.prefill_flops(doc, 256, 800.0) == 1298522963968.0
    assert family.prefill_flops(doc, 256, 800.0, routed=False) == 4545518239744.0
    assert family.kv_bytes_per_token(doc) == 24576
    # the readers on the trace recorded on the chip (the numerics child's two
    # jitted functions at 2 layers, so the shares mean nothing: the same
    # inputs have to give the same numbers as before the seam)
    modules = trace_reduce.reduce_planes(trace_reduce.load(os.path.join(HERE, "data", "numerics.xplane.pb")))["modules"]
    cell = {"config": doc, "device": {"kind": "TPU v5 lite"}, "seconds": 51.0}
    responses = [{"ok": True, "want_prompt_tokens": 300, "context_tokens": 332},
                 {"ok": True, "want_prompt_tokens": 512, "context_tokens": 544}]
    prefill = importlib.import_module("layer_metrics.prefill_step_roofline")
    assert prefill.read([], [], responses, {"modules": modules}, cell) == pytest.approx(369.93421267790717, rel=1e-12)
    decode = importlib.import_module("layer_metrics.decode_step_roofline")
    trace = {
        "modules": {**modules, "jit_decode_n": modules["jit_decode"]},
        "counters_before": [{"decode_chunk_hist": {"8": 100}, "decode_steps": 100, "batch_occupancy": 0.5, "max_batch": 8}],
        "counters_after": [{"decode_chunk_hist": {"8": 110, "4": 10}, "decode_steps": 120, "batch_occupancy": 0.5, "max_batch": 8}],
    }
    assert decode.read([], [], responses, trace, cell) == pytest.approx(5910.272866848205, rel=1e-12)
    # a reader asks the cell's own family: the same sizes under other key names read the same
    renamed = {"family": "renamed_keys", **{{"num_local_experts": "num_experts", "intermediate_size": "ffn_hidden_size",
                                             "num_key_value_heads": "num_kv_heads"}.get(k, k): v for k, v in doc.items()}}
    assert prefill.read([], [], responses, {"modules": modules}, {**cell, "config": renamed}) == pytest.approx(369.93421267790717, rel=1e-12)


# keys of a block's published config.json (``vocab_size`` is also the field of
# the program's ``ModelConfig`` from which the numerics child draws its tokens)
BLOCK_KEYS = (
    "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim", "num_hidden_layers",
    "num_local_experts", "num_experts", "n_routed_experts", "num_experts_per_tok", "rope_theta", "rms_norm_eps",
    "max_position_embeddings", "sliding_window",
)


@pytest.mark.parametrize("path", ["run.py", "site/sitecustomize.py", "harness/numerics_child.py", "harness/family.py",
                                  "harness/compare.py", *sorted(glob.glob("layer_metrics/*.py", root_dir=BENCH))])
def test_only_a_family_knows_a_block(path):
    with open(os.path.join(BENCH, path)) as f:
        text = f.read()
    assert [k for k in BLOCK_KEYS if re.search(rf"\b{k}\b", text)] == []
    assert "models.llama" not in text and "harness.reference" not in text and "import reference" not in text


@pytest.mark.parametrize("path", sorted(glob.glob("families/*.py", root_dir=BENCH)) + ["harness/reference.py"])
def test_a_family_file_holds_no_tolerance(path):
    with open(os.path.join(BENCH, path)) as f:
        text = f.read()
    assert not re.search(r"REL_TOL|MIN_SHARE_WITHIN|rel_err|share_within|position_errs", text)


def write_config(tmp_path, family):
    path = tmp_path / f"{family}.json"
    path.write_text(json.dumps({**RENAMED_KEYS, "family": family}))
    return str(path)


def test_a_familys_own_keys_reach_register(tmp_path):
    """The start-up hook, as the daemon and the engine host run it: the
    expert count published as ``num_experts`` is registered, not dropped."""
    env = {**CHILD_ENV, "ATPU_BENCH_CONFIG": write_config(tmp_path, "renamed_keys"),
           "PYTHONPATH": os.pathsep.join([os.path.join(BENCH, "site"), CHILD_ENV["PYTHONPATH"]])}
    code = ("import sys, dataclasses, json; from agentainer_tpu.models.configs import get_config; "
            "print(json.dumps(dataclasses.asdict(get_config('renamed-keys-test')))); "
            "print(json.dumps(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'numpy'))))")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    cfg, heavy = map(json.loads, out.stdout.strip().splitlines()[-2:])
    assert (cfg["n_experts"], cfg["experts_per_token"], cfg["n_kv_heads"], cfg["ffn_dim"]) == (8, 2, 2, 128)
    assert heavy == []  # the hook imports nothing heavy


@pytest.mark.parametrize("family, passes", [("renamed_keys", True), ("renamed_keys_no_renorm", False)])
def test_numerics_child_compares_with_the_familys_own_reference(tmp_path, family, passes):
    """The same program's forward twice: against the ``llama`` mathematics it
    passes, against a reference with another router rule it fails on
    ``rel_err`` — so what is compared against is the family's file."""
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.numerics_child", write_config(tmp_path, family), "7", "--rehearse"],
        env=CHILD_ENV, cwd=REPO, capture_output=True, text=True, timeout=600)
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["config"] == "renamed-keys-test" and doc["layers"] == 2 and doc["positions_compared"] == 40
    assert doc["ok"] is passes and out.returncode == (0 if passes else 4)
    if passes:
        assert doc["rel_err"] < 1e-5 and doc["share_of_positions_within"] == 1.0
    else:
        assert doc["rel_err"] > doc["tolerance"] == 0.02 and doc["share_of_positions_within"] < 0.85
