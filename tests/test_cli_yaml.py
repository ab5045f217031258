"""CLI + declarative deployment tests."""

import json
import subprocess
import sys
import textwrap

import pytest

from agentainer_tpu.core.errors import InvalidInput
from agentainer_tpu.manager.agents import AgentManager
from agentainer_tpu.manager.deployconfig import (
    apply_deployment,
    fan_out,
    load_deployment,
    parse_deployment,
    parse_quantity,
)
from agentainer_tpu.runtime.backend import FakeBackend
from agentainer_tpu.runtime.scheduler import SliceScheduler, SliceTopology
from agentainer_tpu.store import MemoryStore

YAML_DOC = """
apiVersion: agentainer/v1
kind: AgentDeployment
metadata:
  name: demo-fleet
spec:
  agents:
    - name: backendsvc
      model: echo
      replicas: 2
      env:
        ROLE: worker
      resources:
        chips: 1
        hbm: 2G
      autoRestart: true
      healthCheck:
        endpoint: /health
        interval_s: 5
        retries: 2
    - name: frontend
      model: echo
      dependsOn: [backendsvc]
"""


def test_shipped_examples_parse():
    """The example fleets in examples/ must stay loadable."""
    import pathlib

    from agentainer_tpu.manager.deployconfig import fan_out

    root = pathlib.Path(__file__).resolve().parent.parent / "examples"
    yamls = sorted(root.glob("*.yaml"))
    assert yamls, "examples/ should ship deployment YAMLs"
    for path in yamls:
        config = load_deployment(str(path))
        assert config.agents
        for spec in config.agents:
            assert list(fan_out(spec))


def test_parse_quantity():
    assert parse_quantity("2G") == 2 * 1000**3
    assert parse_quantity("2Gi") == 2 * 1024**3
    assert parse_quantity("512M") == 512 * 1000**2
    assert parse_quantity(123) == 123
    with pytest.raises(InvalidInput):
        parse_quantity("12q")


def test_load_and_fan_out(tmp_path):
    path = tmp_path / "deploy.yaml"
    path.write_text(YAML_DOC)
    config = load_deployment(str(path))
    assert config.name == "demo-fleet"
    # topo order: dependency first
    assert [a.name for a in config.agents] == ["backendsvc", "frontend"]
    names = [n for spec in config.agents for n, _ in fan_out(spec)]
    assert names == ["backendsvc-1", "backendsvc-2", "frontend"]
    be = config.agents[0]
    assert be.resources.hbm_bytes == 2 * 1000**3
    assert be.auto_restart and be.health_check.retries == 2


def test_env_expansion(tmp_path, monkeypatch):
    monkeypatch.setenv("MY_MODEL", "echo")
    path = tmp_path / "d.yaml"
    path.write_text(
        "kind: AgentDeployment\nspec:\n  agents:\n    - name: a\n      model: ${MY_MODEL}\n"
    )
    config = load_deployment(str(path))
    assert config.agents[0].model.engine == "echo"


def test_validation_errors():
    with pytest.raises(InvalidInput):
        parse_deployment({"kind": "Deployment"})
    with pytest.raises(InvalidInput):
        parse_deployment({"kind": "AgentDeployment", "spec": {"agents": []}})
    dup = {"kind": "AgentDeployment", "spec": {"agents": [{"name": "a"}, {"name": "a"}]}}
    with pytest.raises(InvalidInput):
        parse_deployment(dup)
    # unknown dependency — including FORWARD references the reference missed
    bad_dep = {
        "kind": "AgentDeployment",
        "spec": {"agents": [{"name": "a", "dependsOn": ["zzz"]}]},
    }
    with pytest.raises(InvalidInput):
        parse_deployment(bad_dep)
    cycle = {
        "kind": "AgentDeployment",
        "spec": {
            "agents": [
                {"name": "a", "dependsOn": ["b"]},
                {"name": "b", "dependsOn": ["a"]},
            ]
        },
    }
    with pytest.raises(InvalidInput, match="cycle"):
        parse_deployment(cycle)


@pytest.mark.parametrize("axis", ["sp", "pp"])
def test_deployment_yaml_refuses_unserved_layout(tmp_path, axis):
    path = tmp_path / "long.yaml"
    path.write_text(
        textwrap.dedent(
            f"""
            kind: AgentDeployment
            spec:
              agents:
                - name: longctx
                  model:
                    engine: llm
                    config: tiny
                    options: {{{axis}: 4, max_seq: 8192}}
            """
        )
    )
    with pytest.raises(InvalidInput, match=f"{axis}=4.*not served"):
        load_deployment(str(path))


def test_forward_dependency_ok():
    """The reference only resolved deps against earlier-declared names
    (deployment.go:129-156); we accept forward declarations."""
    doc = {
        "kind": "AgentDeployment",
        "spec": {
            "agents": [
                {"name": "first", "dependsOn": ["second"]},
                {"name": "second"},
            ]
        },
    }
    config = parse_deployment(doc)
    assert [a.name for a in config.agents] == ["second", "first"]


def test_apply_deployment_starts_in_order(tmp_path):
    store = MemoryStore()
    mgr = AgentManager(store, FakeBackend(), SliceScheduler(store, SliceTopology(total_chips=8)))
    path = tmp_path / "deploy.yaml"
    path.write_text(YAML_DOC)
    config = load_deployment(str(path))
    created = apply_deployment(mgr, config, start=True)
    assert len(created) == 3
    statuses = {a.name: a.status.value for a in mgr.list_agents(sync_first=False)}
    assert statuses == {
        "backendsvc-1": "running",
        "backendsvc-2": "running",
        "frontend": "running",
    }


def test_cli_help_runs():
    out = subprocess.run(
        [sys.executable, "-m", "agentainer_tpu.cli", "--help"],
        capture_output=True,
        text=True,
        timeout=60,
        env={"PATH": "/usr/bin:/bin", "PYTHONPATH": "/root/repo", "HOME": "/root"},
    )
    assert out.returncode == 0
    for verb in ("deploy", "start", "stop", "pause", "resume", "backup", "audit", "invoke"):
        assert verb in out.stdout


def test_cli_deploy_model_dir_and_models_verbs(tmp_path):
    """CLI e2e for the builder flow: `deploy --model-dir` validates +
    registers + deploys; `models` lists the artifact (builder.go:98-218 +
    main.go:404-443 progress UX analogue)."""
    import asyncio

    from .test_e2e_local import start_stack, teardown
    from .test_hf_convert import _write_hf_llama
    from agentainer_tpu.models.configs import get_config

    model_dir = tmp_path / "ckpt"
    model_dir.mkdir()
    _write_hf_llama(model_dir, get_config("tiny"))

    async def body():
        services, client = await start_stack(tmp_path)
        try:
            base = ["--server", f"http://127.0.0.1:{client.server.port}", "--token", "e2e-token"]

            def cli(*argv):
                return subprocess.run(
                    [sys.executable, "-m", "agentainer_tpu.cli", *base, *argv],
                    capture_output=True,
                    text=True,
                    timeout=120,
                    env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
                )

            deploy = await asyncio.to_thread(
                cli, "deploy", "--name", "cli-model", "--model-dir", str(model_dir)
            )
            assert deploy.returncode == 0, deploy.stderr
            assert "validated" in deploy.stdout  # build progress lines shown
            assert "built artifact 'cli-model'" in deploy.stdout
            assert "deployed cli-model" in deploy.stdout

            models = await asyncio.to_thread(cli, "models")
            assert models.returncode == 0, models.stderr
            assert "cli-model" in models.stdout and "hf" in models.stdout

            # the deployed agent references the registered checkpoint
            agents = services.manager.list_agents(sync_first=False)
            agent = next(a for a in agents if a.name == "cli-model")
            assert agent.model.checkpoint == str(model_dir.resolve())
            assert agent.model.engine == "llm"
        finally:
            await teardown(services, client)

    asyncio.run(body())
