"""An engine switch is set in the deployment's ``model.options`` and nowhere
else: not in ``config.yaml``'s ``features:``, not in an ``ATPU_*`` variable.
What the daemon still hands its engines through the environment is the three
policies that have a half in the proxy (streaming, tiering, deadlines)."""

import json

import pytest

from agentainer_tpu import cli
from agentainer_tpu.config import ENGINE_SWITCHES, Config, FeatureFlags, load_config
from agentainer_tpu.daemon import build_services
from agentainer_tpu.engine import llm
from agentainer_tpu.engine.llm_serve import LLMServeApp
from agentainer_tpu.models.configs import get_config
from agentainer_tpu.runtime.backend import FakeBackend
from agentainer_tpu.store import MemoryStore


def _serve_app(config_name: str, options: dict | None = None) -> LLMServeApp:
    return LLMServeApp(
        env={
            "AGENTAINER_MODEL_CONFIG": config_name,
            "AGENTAINER_MODEL_OPTIONS": json.dumps(options or {}),
            "AGENTAINER_CHIPS": "",
        }
    )


def _daemon(tmp_path, monkeypatch, config: Config | None = None) -> None:
    """Leave in ``os.environ`` what an engine host inherits from a daemon of
    this configuration (the default one if none is given)."""
    for name in ("ATPU_KV_TIERING", "ATPU_STREAMING", "ATPU_DEADLINES"):
        # the daemon writes over these; monkeypatch puts them back afterwards
        monkeypatch.setenv(name, "")
    build_services(
        config=config or Config(),
        store=MemoryStore(),
        backend=FakeBackend(),
        console_logs=False,
        data_dir=str(tmp_path),
    )


def test_feature_flags_hold_no_engine_switch():
    assert not set(ENGINE_SWITCHES) & set(FeatureFlags.__dataclass_fields__)


@pytest.mark.parametrize("switch", ENGINE_SWITCHES)
@pytest.mark.parametrize("value", ["0", "1"])
def test_an_environment_variable_is_not_an_engine_switch(switch, value, tmp_path, monkeypatch):
    _daemon(tmp_path, monkeypatch)
    before = _serve_app("tiny")._engine_options()
    monkeypatch.setenv(f"ATPU_{switch.upper()}", value)
    assert _serve_app("tiny")._engine_options() == before
    assert switch not in before


@pytest.mark.parametrize("switch", ENGINE_SWITCHES)
def test_model_options_is_where_an_engine_switch_is_set(switch, tmp_path, monkeypatch):
    _daemon(tmp_path, monkeypatch)
    for value in (True, False):
        assert _serve_app("tiny", {switch: value})._engine_options()[switch] is value


# What the engine of each family came to at the parent of the PR that took
# the fleet defaults away (a daemon with a default Config(), nothing in
# model.options): the constructor's own defaults give the same.
_KV = {"speculative": True, "prefix_cache": True, "kinds": ["kv"], "off": []}
_ALL_OFF = ["fused_decode", "kv_tiering", "mesh", "paged_kv", "prefix_cache", "speculative"]
_NOT_KV = {"speculative": False, "prefix_cache": False, "off": _ALL_OFF}
_AT_THE_PARENT = {
    "tiny": _KV,
    "tiny-moe": _KV,
    "tiny-olmoe": _KV,
    "tiny-kimi-linear": {**_NOT_KV, "kinds": ["latent", "state", "conv"]},
    "tiny-olmo-hybrid": {**_NOT_KV, "kinds": ["k", "v", "state", "conv"]},
    "tiny-smallthinker": {**_NOT_KV, "kinds": ["kv", "kv_ring"]},
    "tiny-mistral4": {**_NOT_KV, "kinds": ["latent"]},
}
_SAME_FOR_ALL = {
    "adaptive_decode": True,
    "paged_kv": False,
    "fused_decode": False,
    "inloop_spec": False,  # engages under fused_decode + speculative only
    "approx_topk": False,
    "deadlines": True,
}


@pytest.mark.parametrize("config_name", sorted(_AT_THE_PARENT))
def test_a_default_daemon_builds_the_engine_it_built_with_fleet_defaults(
    config_name, tmp_path, monkeypatch
):
    _daemon(tmp_path, monkeypatch)
    options = _serve_app(config_name)._engine_options()
    assert not set(ENGINE_SWITCHES) & set(options)
    expected = _AT_THE_PARENT[config_name]
    asked = {
        k: options.get(k)
        for k in ("speculative", "prefix_cache", "paged_kv", "fused_decode", "kv_tiering")
    }
    on, off = llm.cache_features(get_config(config_name), {k: v or None for k, v in asked.items()})
    assert (on["speculative"], on["prefix_cache"]) == (expected["speculative"], expected["prefix_cache"])
    assert not (on["paged_kv"] or on["fused_decode"] or on["kv_tiering"])
    assert sorted(set(off) | ({"mesh"} if off else set())) == expected["off"]
    engine = llm.LLMEngine.create(
        config_name, options=dict(options, skip_warmup=True, max_batch=2, max_seq=64)
    )
    try:
        m = engine.metrics()
    finally:
        engine.shutdown()
    assert m["cache"]["kinds"] == expected["kinds"]
    assert sorted(m["cache"].get("off", {})) == expected["off"]
    assert {k: m[k] for k in _SAME_FOR_ALL} == _SAME_FOR_ALL
    assert (m["speculative"], m["prefix_cache"]) == (expected["speculative"], expected["prefix_cache"])
    assert engine.kv_tiering is False and engine.streaming is False


@pytest.mark.parametrize("switch", ["paged_kv", "speculative"])
def test_load_config_refuses_an_engine_switch_under_features(switch, tmp_path, monkeypatch):
    monkeypatch.delenv("ATPU_STREAMING", raising=False)  # an earlier daemon's write-back
    path = tmp_path / "config.yaml"
    path.write_text(f"features:\n  streaming: true\n  {switch}: true\n")
    with pytest.raises(ValueError, match=rf"features\.{switch}.*model\.options\.{switch}"):
        load_config(str(path))
    path.write_text("features:\n  streaming: true\n")
    assert load_config(str(path)).features.streaming is True


def test_deploy_option_lands_in_the_posted_model_options(monkeypatch):
    posted = {}

    def call(args, method, path, body=None):
        posted.update(body)
        return {"data": {"id": "a-1", "name": body["name"]}}

    monkeypatch.setattr(cli, "_call", call)
    cli.main(
        ["deploy", "--name", "x", "--model", "llm:tiny"]
        + ["--option", "paged_kv=true", "--option", "spec_gamma_max=4"]
        + ["--option", "system_prompt=You are terse."]
    )
    assert posted["model"] == {
        "engine": "llm",
        "config": "tiny",
        "options": {"paged_kv": True, "spec_gamma_max": 4, "system_prompt": "You are terse."},
    }
    with pytest.raises(SystemExit, match="KEY=VALUE"):
        cli.main(["deploy", "--name", "x", "--option", "paged_kv"])


def test_deploy_shows_option_and_no_flag_of_a_switch(capsys):
    with pytest.raises(SystemExit):
        cli.main(["deploy", "--help"])
    text = capsys.readouterr().out
    assert "--option KEY=VALUE" in text
    for name in ENGINE_SWITCHES + ("kv_tiering", "streaming", "deadlines"):
        assert "--" + name.replace("_", "-") not in text
        assert "--no-" + name.replace("_", "-") not in text


@pytest.mark.parametrize("policy", ["kv_tiering", "streaming", "deadlines"])
def test_the_three_policies_still_reach_an_engine(policy, tmp_path, monkeypatch):
    config = Config()
    config.features.kv_tiering = config.features.streaming = True
    config.deadlines.enabled = False
    _daemon(tmp_path, monkeypatch, config)
    want = policy != "deadlines"
    assert _serve_app("tiny")._engine_options()[policy] is want
    # the deployment's own options still win over the daemon's policy
    assert _serve_app("tiny", {policy: not want})._engine_options()[policy] is (not want)
    all_latent = _serve_app("tiny-mistral4")._engine_options()
    if policy == "kv_tiering":
        # a cache that cannot hold it: the policy falls away, with the reason
        # the engine reports, instead of failing the build
        assert "kv_tiering" not in all_latent
        assert not llm.fleet_default_applies("tiny-mistral4", "kv_tiering")
        assert "latent leaf" in llm._cache_off(get_config("tiny-mistral4"))[0]["kv_tiering"]
    else:
        assert all_latent[policy] is want
