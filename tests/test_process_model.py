"""One process for each set of chips, one place for compiled programs.

The rules a chip-local deployment stands on, checked without a chip:

- the environment ``LocalBackend`` builds for an engine host binds it to
  the chips the scheduler assigned — two engines on chips (0,) and (1,) get
  disjoint visibility — and names exactly one compile cache;
- that cache is ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
  and ``<checkout>/.jax_cache`` otherwise, whatever the data dir, and the
  warm-boot marker lives inside it;
- a device kind outside the peaks table yields no MFU/MBU at all, and an
  engine never lands on the CPU backend without being asked to;
- replicas of a chip-backed agent are placed on distinct chips, and chips
  held by one engine process are not handed to another.
"""

from pathlib import Path

import pytest

from agentainer_tpu.core.spec import Agent, ModelRef, Resources
from agentainer_tpu.runtime.local import LocalBackend, chip_visibility_env
from agentainer_tpu.runtime.scheduler import SliceScheduler, SliceTopology
from agentainer_tpu.store import MemoryStore
from agentainer_tpu.utils.compile_cache import compile_cache_dir
from agentainer_tpu.utils.hw import chip_spec

CHECKOUT = Path(__file__).resolve().parents[1]


def _agent(name: str, engine: str = "llm", **model) -> Agent:
    return Agent(id=f"agent-{name}", name=name, model=ModelRef(engine=engine, config="tiny", **model))


def _engine_env(backend: LocalBackend, agent: Agent, chips: tuple[int, ...]) -> dict:
    return backend._recs[backend.create_engine(agent, chips)].env


def _warm_marker_in_child(monkeypatch, env: dict) -> Path:
    """The warm-boot marker as the engine host started with ``env`` places it."""
    from agentainer_tpu.engine.llm_serve import LLMServeApp

    with monkeypatch.context() as child:
        child.setenv("JAX_COMPILATION_CACHE_DIR", env["JAX_COMPILATION_CACHE_DIR"])
        return Path(LLMServeApp(env=env)._warm_marker_path({"max_batch": 2}))


@pytest.mark.parametrize("placed", ["from-outside", "default"])
def test_compile_cache_rule(tmp_path, monkeypatch, placed):
    if placed == "from-outside":
        want = str(tmp_path / "placed-cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        want = str(CHECKOUT / ".jax_cache")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache_dir() == want
    for data_dir in (tmp_path / "data-a", tmp_path / "data-b"):
        backend = LocalBackend(data_dir=data_dir)
        try:
            env = _engine_env(backend, _agent(data_dir.name), (0,))
        finally:
            backend.close()
        # one cache is named, it is the rule's, and nothing else names one
        assert env["JAX_COMPILATION_CACHE_DIR"] == want
        named = {k for k, v in env.items() if "CACHE" in k and "COMPIL" in k.upper()}
        assert named == {"JAX_COMPILATION_CACHE_DIR"}, named
        assert str(data_dir) not in env["JAX_COMPILATION_CACHE_DIR"]
        assert _warm_marker_in_child(monkeypatch, env).parent == Path(want)


def test_engines_on_different_chips_get_disjoint_visibility(tmp_path):
    backend = LocalBackend(data_dir=tmp_path, topology=SliceTopology(total_chips=4))
    try:
        a = _engine_env(backend, _agent("a"), (0,))
        b = _engine_env(backend, _agent("b"), (1,))
        echo = _engine_env(backend, _agent("e", engine="echo"), (0,))
        with pytest.raises(ValueError, match="outside the 4-chip slice"):
            backend.create_engine(_agent("lost"), (7,))
    finally:
        backend.close()
    assert (a["TPU_VISIBLE_CHIPS"], b["TPU_VISIBLE_CHIPS"]) == ("0", "1")
    assert a["AGENTAINER_CHIPS"] == "0" and b["AGENTAINER_CHIPS"] == "1"
    # each is its own one-process slice with its own controller port
    for env in (a, b):
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert a["TPU_MESH_CONTROLLER_PORT"] != b["TPU_MESH_CONTROLLER_PORT"]
    # an engine that opens no chip is kept off them altogether
    assert echo["JAX_PLATFORMS"] == "cpu" and "TPU_VISIBLE_CHIPS" not in echo


@pytest.mark.parametrize(
    "chips,bounds",
    [((0,), "1,1,1"), ((2, 3), "2,1,1"), ((0, 1, 2, 3), "2,2,1")],
)
def test_visibility_bounds_follow_the_grid(chips, bounds):
    env = chip_visibility_env(chips, SliceTopology(total_chips=4))
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds
    assert env["TPU_VISIBLE_CHIPS"] == ",".join(map(str, chips))


@pytest.mark.parametrize(
    "chips,why", [((0, 1, 2), "not a rectangle"), ((1, 3), "a column of the grid")]
)
def test_visibility_rejects_what_cannot_be_bound(chips, why):
    with pytest.raises(ValueError, match=why):
        chip_visibility_env(chips, SliceTopology(total_chips=4))


def test_replicas_and_other_models_get_their_own_chips():
    sched = SliceScheduler(MemoryStore(), SliceTopology(total_chips=4))
    a = _agent("a")
    a.resources = Resources(chips=1, hbm_bytes=1 << 30)
    placed = [sched.allocate(a, share_group="tiny", replica=i).chips for i in range(3)]
    assert placed == [(0,), (1,), (2,)]
    # same model, other agent: shares replica 0's process and chips
    b = _agent("b")
    b.resources = a.resources
    assert sched.allocate(b, share_group="tiny").chips == (0,)
    # another model is another process: it may not open a held chip
    c = _agent("c")
    c.resources = a.resources
    assert sched.allocate(c, share_group="tiny-moe").chips == (3,)
    d = _agent("d")
    d.resources = a.resources
    from agentainer_tpu.core.errors import ResourceExhausted

    with pytest.raises(ResourceExhausted, match="belong to other engine processes"):
        sched.allocate(d, share_group="bench-1b")
    # releasing the agent frees every replica's chips
    sched.release(a.id)
    assert sched.placement(a.id, 1) is None
    assert sched.allocate(d, share_group="bench-1b").chips == (1,)


@pytest.mark.parametrize("kind", ["cpu", "TPU v9 imaginary", ""])
def test_unknown_device_kind_has_no_peaks(kind):
    assert chip_spec(kind) is None
    assert chip_spec("TPU v5 lite").hbm_gbps == 819e9


def test_cpu_engine_reports_device_and_no_utilization():
    """On the CPU an engine names its device and attention path and prints
    no MFU/MBU: a utilization against an invented peak is worse than none."""
    from agentainer_tpu.engine.llm import LLMEngine

    eng = LLMEngine.create("tiny", options={"max_batch": 2, "max_seq": 64, "skip_warmup": True})
    try:
        m = eng.metrics()
    finally:
        eng.shutdown()
    assert m["device"]["platform"] == "cpu" and m["device"]["count"] >= 1
    for key in ("mfu_lifetime", "mbu_lifetime", "peak_tflops", "hbm_gbps_peak", "chip_kind"):
        assert key not in m, key
    assert m["attention"]["decode"] == "xla:attention_reference"
    assert "backend is cpu" in m["attention"]["reason"]
    # and how the steps hand the arena to it: the reference cannot address
    # the stacked arena by layer, so it says it slices the layer out
    assert m["attention"]["arena"] == "layer_slice"
    assert m["engine_devices"] == [{"id": 0, "coords": None}]


def test_engine_refuses_an_unasked_for_cpu(monkeypatch):
    from agentainer_tpu.engine.llm import LLMEngine

    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="no accelerator"):
        LLMEngine.create("tiny", options={"max_batch": 2, "max_seq": 64})


def test_chips_beyond_the_visible_devices_are_an_error():
    from agentainer_tpu.engine.llm import LLMEngine

    with pytest.raises(ValueError, match="do not map"):
        LLMEngine.create("tiny", options={"chips": list(range(64)), "skip_warmup": True})
