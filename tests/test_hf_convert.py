"""HF-checkpoint import: weight-name mapping, transposes, tied embeddings,
MoE expert stacking, and the load_params format dispatch."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.models.configs import get_config
from agentainer_tpu.models.llama import forward, init_params


def _write_hf_llama(tmp_path, cfg, tied=False, seed=0):
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    d, hd = cfg.dim, cfg.head_dim

    def w(*shape):
        return rng.standard_normal(shape).astype(np.float32) * 0.02

    tensors = {
        "model.embed_tokens.weight": w(cfg.vocab_size, d),
        "model.norm.weight": np.ones(d, np.float32),
    }
    if not tied:
        tensors["lm_head.weight"] = w(cfg.vocab_size, d)
    for i in range(cfg.n_layers):
        L = f"model.layers.{i}."
        tensors[L + "input_layernorm.weight"] = np.ones(d, np.float32)
        tensors[L + "post_attention_layernorm.weight"] = np.ones(d, np.float32)
        tensors[L + "self_attn.q_proj.weight"] = w(cfg.n_heads * hd, d)
        tensors[L + "self_attn.k_proj.weight"] = w(cfg.n_kv_heads * hd, d)
        tensors[L + "self_attn.v_proj.weight"] = w(cfg.n_kv_heads * hd, d)
        tensors[L + "self_attn.o_proj.weight"] = w(d, cfg.n_heads * hd)
        if cfg.is_moe:
            tensors[L + "block_sparse_moe.gate.weight"] = w(cfg.n_experts, d)
            for e in range(cfg.n_experts):
                E = L + f"block_sparse_moe.experts.{e}."
                tensors[E + "w1.weight"] = w(cfg.ffn_dim, d)
                tensors[E + "w2.weight"] = w(d, cfg.ffn_dim)
                tensors[E + "w3.weight"] = w(cfg.ffn_dim, d)
        else:
            tensors[L + "mlp.gate_proj.weight"] = w(cfg.ffn_dim, d)
            tensors[L + "mlp.up_proj.weight"] = w(cfg.ffn_dim, d)
            tensors[L + "mlp.down_proj.weight"] = w(d, cfg.ffn_dim)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(
        json.dumps(
            {
                "model_type": "llama",
                "vocab_size": cfg.vocab_size,
                "hidden_size": cfg.dim,
                "num_hidden_layers": cfg.n_layers,
                "num_attention_heads": cfg.n_heads,
                "num_key_value_heads": cfg.n_kv_heads,
                "intermediate_size": cfg.ffn_dim,
                "rope_theta": cfg.rope_theta,
                "rms_norm_eps": cfg.norm_eps,
                **(
                    {
                        "num_local_experts": cfg.n_experts,
                        "num_experts_per_tok": cfg.experts_per_token,
                    }
                    if cfg.is_moe
                    else {}
                ),
            }
        )
    )
    return tensors


def test_llama_mapping_and_forward(tmp_path):
    cfg = get_config("tiny")
    tensors = _write_hf_llama(tmp_path, cfg)

    from agentainer_tpu.engine.checkpoint import load_params

    params = load_params(cfg, tmp_path, dtype=jnp.float32)

    # pytree shape parity with random init
    ref = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert jax.tree.structure(params) == jax.tree.structure(ref)
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree_util.tree_leaves_with_path(ref),
    ):
        assert a.shape == b.shape, (pa, a.shape, b.shape)

    # spot-check the transpose convention on layer 1
    np.testing.assert_allclose(
        np.asarray(params["layers"]["wq"][1]),
        tensors["model.layers.1.self_attn.q_proj.weight"].T,
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(params["lm_head"]), tensors["lm_head.weight"].T, rtol=1e-6
    )

    # imported params drive a real forward pass
    tokens = jnp.zeros((1, 4), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(4), (1, 4))
    logits, _ = forward(params, cfg, tokens, positions)
    assert logits.shape == (1, 4, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_tied_embeddings(tmp_path):
    cfg = get_config("tiny")
    tensors = _write_hf_llama(tmp_path, cfg, tied=True)
    from agentainer_tpu.engine.hf_convert import load_hf_params

    params = load_hf_params(cfg, tmp_path, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(params["lm_head"]),
        tensors["model.embed_tokens.weight"].T,
        rtol=1e-6,
    )


def test_moe_expert_stacking(tmp_path):
    cfg = get_config("tiny-moe")
    tensors = _write_hf_llama(tmp_path, cfg)
    from agentainer_tpu.engine.hf_convert import load_hf_params

    params = load_hf_params(cfg, tmp_path, dtype=jnp.float32)
    assert params["layers"]["w_gate"].shape == (
        cfg.n_layers, cfg.n_experts, cfg.dim, cfg.ffn_dim,
    )
    np.testing.assert_allclose(
        np.asarray(params["layers"]["w_down"][0, 1]),
        tensors["model.layers.0.block_sparse_moe.experts.1.w2.weight"].T,
        rtol=1e-6,
    )
    tokens = jnp.zeros((1, 4), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(4), (1, 4))
    logits, _ = forward(params, cfg, tokens, positions)
    assert bool(jnp.isfinite(logits).all())


def test_config_from_hf(tmp_path):
    cfg = get_config("tiny")
    _write_hf_llama(tmp_path, cfg)
    from agentainer_tpu.engine.hf_convert import config_from_hf

    derived = config_from_hf(tmp_path)
    assert derived.dim == cfg.dim
    assert derived.n_layers == cfg.n_layers
    assert derived.n_kv_heads == cfg.n_kv_heads
    assert not derived.is_moe


def _write_hf_olmoe(tmp_path, cfg, seed=0):
    """A checkpoint directory under OLMoE's published names: ``num_experts``
    and ``norm_topk_prob`` in config.json, ``mlp.gate``,
    ``mlp.experts.{e}.{gate,up,down}_proj`` and ``self_attn.{q,k}_norm``."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    d, hd = cfg.dim, cfg.head_dim

    def w(*shape, scale=0.02):
        return rng.standard_normal(shape).astype(np.float32) * scale

    tensors = {
        "model.embed_tokens.weight": w(cfg.vocab_size, d),
        "model.norm.weight": np.ones(d, np.float32),
        "lm_head.weight": w(cfg.vocab_size, d, scale=0.2),
    }
    for i in range(cfg.n_layers):
        L = f"model.layers.{i}."
        tensors[L + "input_layernorm.weight"] = np.ones(d, np.float32)
        tensors[L + "post_attention_layernorm.weight"] = np.ones(d, np.float32)
        tensors[L + "self_attn.q_proj.weight"] = w(cfg.n_heads * hd, d, scale=0.16)
        tensors[L + "self_attn.k_proj.weight"] = w(cfg.n_kv_heads * hd, d, scale=0.16)
        tensors[L + "self_attn.v_proj.weight"] = w(cfg.n_kv_heads * hd, d)
        tensors[L + "self_attn.o_proj.weight"] = w(d, cfg.n_heads * hd)
        tensors[L + "self_attn.q_norm.weight"] = rng.uniform(0.25, 4.0, cfg.n_heads * hd).astype(np.float32)
        tensors[L + "self_attn.k_norm.weight"] = rng.uniform(0.25, 4.0, cfg.n_kv_heads * hd).astype(np.float32)
        tensors[L + "mlp.gate.weight"] = w(cfg.n_experts, d, scale=0.2)
        for e in range(cfg.n_experts):
            E = L + f"mlp.experts.{e}."
            tensors[E + "gate_proj.weight"] = w(cfg.ffn_dim, d)
            tensors[E + "up_proj.weight"] = w(cfg.ffn_dim, d)
            tensors[E + "down_proj.weight"] = w(d, cfg.ffn_dim, scale=0.8)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps({
        "model_type": "olmoe", "architectures": ["OlmoeForCausalLM"], "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.dim, "num_hidden_layers": cfg.n_layers, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "intermediate_size": cfg.ffn_dim, "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps, "max_position_embeddings": cfg.max_seq_len, "num_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.experts_per_token, "norm_topk_prob": False, "clip_qkv": None,
        "tie_word_embeddings": False,
    }))
    return tensors


def test_config_from_hf_reads_an_olmoe_checkpoint_as_a_mixture(tmp_path):
    """``num_experts`` (not Mixtral's ``num_local_experts``) used to read as
    0 experts: a 64-expert model loaded as dense."""
    from agentainer_tpu.engine.hf_convert import config_from_hf

    cfg = get_config("tiny-olmoe")
    _write_hf_olmoe(tmp_path, cfg)
    derived = config_from_hf(tmp_path)
    assert (derived.n_experts, derived.experts_per_token) == (cfg.n_experts, cfg.experts_per_token)
    assert derived.qk_norm and not derived.moe_renormalize and derived.is_moe
    assert derived.param_count() == cfg.param_count()


def test_qk_norm_follows_the_checkpoint_not_the_model_name(tmp_path):
    """No ``config.json`` key states QK-norm: a checkpoint that calls itself
    ``olmoe`` but holds no ``q_norm`` weights has none, and one under another
    name that holds them has."""
    from agentainer_tpu.engine.hf_convert import config_from_hf

    _write_hf_llama(tmp_path, get_config("tiny-moe"))
    doc = json.loads((tmp_path / "config.json").read_text())
    (tmp_path / "config.json").write_text(json.dumps({**doc, "model_type": "olmoe"}))
    assert not config_from_hf(tmp_path).qk_norm

    other = tmp_path / "other"
    other.mkdir()
    _write_hf_olmoe(other, get_config("tiny-olmoe"))
    doc = json.loads((other / "config.json").read_text())
    (other / "config.json").write_text(json.dumps({**doc, "model_type": "olmo-next"}))
    assert config_from_hf(other).qk_norm


def test_mixtral_config_keeps_its_rule(tmp_path):
    from agentainer_tpu.engine.hf_convert import config_from_hf

    _write_hf_llama(tmp_path, get_config("tiny-moe"))
    derived = config_from_hf(tmp_path)
    assert derived.moe_renormalize and not derived.qk_norm


def test_olmoe_checkpoint_loads_and_matches_the_plain_reference(tmp_path):
    import importlib.util
    import os

    from agentainer_tpu.engine.hf_convert import config_from_hf, load_hf_params

    cfg = get_config("tiny-olmoe")
    tensors = _write_hf_olmoe(tmp_path, cfg)
    derived = config_from_hf(tmp_path)
    params = load_hf_params(derived, tmp_path, dtype=jnp.float32)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.float32)))
    np.testing.assert_array_equal(
        np.asarray(params["layers"]["q_norm"][1]), tensors["model.layers.1.self_attn.q_norm.weight"])
    np.testing.assert_array_equal(
        np.asarray(params["layers"]["w_up"][0, 3]), tensors["model.layers.0.mlp.experts.3.up_proj.weight"].T)
    np.testing.assert_array_equal(
        np.asarray(params["layers"]["router"][1]), tensors["model.layers.1.mlp.gate.weight"].T)

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "olmoe_reference", os.path.join(here, "benchmark", "families", "olmoe_reference.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    tokens = jnp.asarray(np.random.default_rng(1).integers(3, cfg.vocab_size, 16), jnp.int32)
    layers = [{k: jnp.asarray(v[i]) for k, v in params["layers"].items()} for i in range(cfg.n_layers)]
    want = ref.forward(
        {"embed": jnp.asarray(params["embed"]), "layers": layers, "final_norm": jnp.asarray(params["final_norm"]),
         "lm_head": jnp.asarray(params["lm_head"])},
        tokens, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token)
    got, _ = forward(jax.tree.map(jnp.asarray, params), derived, tokens[None], jnp.arange(16)[None], use_flash=False)
    err = np.sqrt(np.mean((np.asarray(got[0]) - np.asarray(want)) ** 2, -1)) / np.std(np.asarray(want), -1)
    assert float(np.median(err)) < 1e-4


def test_config_from_hf_reads_a_smallthinker_config_as_the_benchmarks_family_does(tmp_path):
    """The published ``config.json`` (the benchmark's configuration file
    holds its keys) gives the registered model: a head of its own, the two
    per-layer layouts, the window, ReGLU and the early router. The tensors'
    names wait for a checkpoint: loading refuses by name, it does not guess."""
    import dataclasses
    import os

    from agentainer_tpu.engine.hf_convert import config_from_hf, load_hf_params

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs", "smallthinker-21b-ep4-1chip.json")) as f:
        doc = json.load(f)
    published = {k: v for k, v in doc.items() if k not in ("name", "family", "source", "reduced", "assumed", "engine_options",
                                                           "why_engine_options", "expert_parallel", "experts_published",
                                                           "hbm_claim_bytes_per_chip", "stands_for", "torch_dtype")}
    published["moe_num_primary_experts"] = doc["experts_published"]
    (tmp_path / "config.json").write_text(json.dumps(published))
    from safetensors.numpy import save_file

    save_file({"model.norm.weight": np.ones((4,), np.float32)}, str(tmp_path / "model.safetensors"))
    derived = config_from_hf(tmp_path)
    assert derived == dataclasses.replace(get_config("smallthinker-21b"), name=derived.name)
    assert derived.head_dim == 128 and derived.n_window == 39 and derived.ffn_act == "relu" and derived.early_router
    with pytest.raises(NotImplementedError, match="checkpoint key mapping"):
        load_hf_params(derived, tmp_path)
    # a Llama config that states the derived head width keeps deriving it
    other = tmp_path / "llama"
    other.mkdir()
    _write_hf_llama(other, get_config("tiny"))
    llama_doc = json.loads((other / "config.json").read_text())
    stated = {**llama_doc, "head_dim": llama_doc["hidden_size"] // llama_doc["num_attention_heads"]}
    (other / "config.json").write_text(json.dumps(stated))
    plain = config_from_hf(other)
    assert plain.head_size == 0 and not plain.window_layers and plain.ffn_act == "silu" and not plain.early_router


def test_config_from_hf_reads_a_mistral4_config_as_the_benchmarks_family_does(tmp_path):
    """``model_type: mistral4``: the published ``config.json`` (the benchmark's
    configuration file holds its keys) gives the registered model — MLA in
    every layer, the query's low-rank pair, the YaRN parameters, the query's
    scale, the softmax router with a shared expert — and the same fields the
    benchmark's family derives from the file as run. The hybrid block's
    tensors' names wait for a checkpoint: loading refuses by name."""
    import dataclasses
    import os
    import sys

    from agentainer_tpu.engine.hf_convert import config_from_hf, load_hf_params

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs", "mistral-small-4-119b-ep4-1chip.json")) as f:
        doc = json.load(f)
    ours = ("name", "family", "source", "reduced", "assumed", "engine_options", "why_engine_options", "expert_parallel",
            "pipeline", "experts_published", "layers_published", "hbm_claim_bytes_per_chip", "stands_for", "torch_dtype")
    as_run = {k: v for k, v in doc.items() if k not in ours}
    published = {**as_run, "n_routed_experts": doc["experts_published"], "num_hidden_layers": doc["layers_published"],
                 "max_position_embeddings": 1_048_576}
    from safetensors.numpy import save_file

    for sub, conf in (("published", published), ("as_run", as_run)):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / "config.json").write_text(json.dumps(conf))
        save_file({"model.norm.weight": np.ones((4,), np.float32)}, str(tmp_path / sub / "model.safetensors"))
    derived = config_from_hf(tmp_path / "published")
    assert derived == dataclasses.replace(get_config("mistral-small-4-119b"), name=derived.name)
    assert derived.mla_q_rank == 1024 and derived.rope_factor == 128.0 and derived.linear_kind is None
    with pytest.raises(NotImplementedError, match="hybrid block"):
        load_hf_params(derived, tmp_path / "published")
    # the file as run, read by the benchmark's family: the same model but for the chip's share
    sys.path.insert(0, os.path.join(here, "benchmark"))
    try:
        from harness.family import family_of

        served = family_of(doc).model_config(doc)
    finally:
        sys.path.remove(os.path.join(here, "benchmark"))
    same = dataclasses.replace(config_from_hf(tmp_path / "as_run"), name=served.name, experts_held=32, n_experts=128,
                               dense_ffn_dim=served.dense_ffn_dim)
    assert same == served
