"""Start-up hook the harness puts first on the daemon's ``PYTHONPATH``.

Only registry names reach the engine (``LLMEngine.create`` → ``get_config``)
and the daemon validates a deploy against the same registry, so a benchmark
configuration that is not in ``models/configs.py`` has to be registered in
both processes. The engine host inherits the daemon's environment, so this
file runs in each: it reads the configuration file named in
``ATPU_BENCH_CONFIG`` and calls the program's public ``register()``. It
imports nothing heavy (``models.configs`` is a dataclass and a dict) and
edits no program file. A model config accepted from a file by the program
itself would make it unnecessary (PERF.md, Open questions).
"""

import os


def _register() -> None:
    path = os.environ.get("ATPU_BENCH_CONFIG")
    if not path:
        return
    import json

    from agentainer_tpu.models.configs import ModelConfig, register

    with open(path) as f:
        doc = json.load(f)
    register(ModelConfig(**model_fields(doc)))


def model_fields(doc: dict, n_layers: int | None = None) -> dict:
    """The program's ``ModelConfig`` fields from a configuration file whose
    top level holds the model's published ``config.json`` keys, as run."""
    heads = int(doc["num_attention_heads"])
    if "head_dim" in doc and int(doc["head_dim"]) * heads != int(doc["hidden_size"]):
        raise ValueError("the program's block derives head_dim as hidden_size / heads")
    return {
        "name": doc["name"],
        "vocab_size": int(doc["vocab_size"]),
        "dim": int(doc["hidden_size"]),
        "n_layers": int(n_layers if n_layers is not None else doc["num_hidden_layers"]),
        "n_heads": heads,
        "n_kv_heads": int(doc.get("num_key_value_heads", heads)),
        "ffn_dim": int(doc["intermediate_size"]),
        "max_seq_len": int(doc["max_position_embeddings"]),
        "rope_theta": float(doc["rope_theta"]),
        "norm_eps": float(doc["rms_norm_eps"]),
        "n_experts": int(doc.get("num_local_experts", 0) or 0),
        "experts_per_token": int(doc.get("num_experts_per_tok", 2) or 2),
    }


_register()
