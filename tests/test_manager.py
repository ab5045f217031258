"""Lifecycle manager tests against the fake backend.

Exercises the reference's state machine semantics (SURVEY.md §2 #3):
deploy persists a record but creates no engine; start creates-or-starts;
resume rehydrates stopped/failed/vanished engines; remove cleans every key
including request queues.
"""

import pytest

from agentainer_tpu.core.errors import (
    AgentNotFound,
    InvalidInput,
    InvalidTransition,
    ResourceExhausted,
)
from agentainer_tpu.core.spec import AgentStatus, ModelRef, Resources
from agentainer_tpu.manager.agents import AgentManager
from agentainer_tpu.runtime.backend import EngineState, FakeBackend
from agentainer_tpu.runtime.scheduler import SliceScheduler, SliceTopology
from agentainer_tpu.store import Keys, MemoryStore


@pytest.fixture
def mgr():
    store = MemoryStore()
    backend = FakeBackend()
    scheduler = SliceScheduler(store, SliceTopology(total_chips=8))
    return AgentManager(store, backend, scheduler)


def test_deploy_creates_record_but_no_engine(mgr):
    agent = mgr.deploy("my-agent", "echo")
    assert agent.status == AgentStatus.CREATED
    assert agent.id.startswith("agent-")
    assert agent.engine_id == ""
    assert mgr.backend.list_engines() == []
    stored = mgr.store.get_json(Keys.agent(agent.id))
    assert stored["name"] == "my-agent"
    assert agent.id in mgr.store.smembers(Keys.AGENTS_LIST)


def test_deploy_validation(mgr):
    with pytest.raises(InvalidInput):
        mgr.deploy("", "echo")
    with pytest.raises(InvalidInput):
        mgr.deploy("x" * 65, "echo")
    with pytest.raises(InvalidInput):
        mgr.deploy("a", "no-such-engine")
    with pytest.raises(InvalidInput):
        mgr.deploy("a", "llm:no-such-model")


@pytest.mark.parametrize("axis", ["sp", "pp"])
def test_deploy_refuses_unserved_layout(mgr, axis):
    """What the REST deploy hands the manager: a layout no engine serves is
    a 400 at deploy, not an agent that starts on one chip; 1 names none."""
    model = {"engine": "llm", "config": "tiny", "options": {axis: 2}}
    with pytest.raises(InvalidInput, match=f"{axis}=2.*not served"):
        mgr.deploy("a", model)
    assert mgr.list_agents() == []
    model["options"][axis] = 1
    assert mgr.deploy("a", model).model.options == {axis: 1}


def test_start_stop_restart(mgr):
    agent = mgr.deploy("a", "echo")
    agent = mgr.start(agent.id)
    assert agent.status == AgentStatus.RUNNING
    info = mgr.backend.engine_info(agent.engine_id)
    assert info.state == EngineState.RUNNING
    assert mgr.scheduler.placement(agent.id) is not None

    agent = mgr.stop(agent.id)
    assert agent.status == AgentStatus.STOPPED
    assert mgr.backend.engine_info(agent.engine_id).state == EngineState.EXITED

    agent = mgr.restart(agent.id)
    assert agent.status == AgentStatus.RUNNING


def test_stop_requires_running(mgr):
    agent = mgr.deploy("a", "echo")
    with pytest.raises(InvalidTransition):
        mgr.stop(agent.id)


def test_pause_resume(mgr):
    agent = mgr.deploy("a", "echo")
    mgr.start(agent.id)
    agent = mgr.pause(agent.id)
    assert agent.status == AgentStatus.PAUSED
    assert mgr.backend.engine_info(agent.engine_id).state == EngineState.PAUSED
    agent = mgr.resume(agent.id)
    assert agent.status == AgentStatus.RUNNING


def test_resume_rehydrates_stopped(mgr):
    agent = mgr.deploy("a", "echo")
    mgr.start(agent.id)
    mgr.stop(agent.id)
    agent = mgr.resume(agent.id)
    assert agent.status == AgentStatus.RUNNING
    assert mgr.backend.engine_info(agent.engine_id).state == EngineState.RUNNING


def test_resume_recreates_vanished_engine(mgr):
    agent = mgr.deploy("a", "echo")
    agent = mgr.start(agent.id)
    old_engine = agent.engine_id
    mgr.backend.vanish_engine(old_engine)
    agent = mgr.resume(agent.id)
    assert agent.status == AgentStatus.RUNNING
    assert agent.engine_id != old_engine
    assert mgr.backend.engine_info(agent.engine_id).state == EngineState.RUNNING


def test_remove_cleans_all_keys(mgr):
    agent = mgr.deploy("a", "echo")
    mgr.start(agent.id)
    mgr.store.set(Keys.request(agent.id, "r1"), "{}")
    mgr.store.rpush(Keys.pending(agent.id), "r1")
    mgr.store.set(Keys.health(agent.id), "{}")
    engine_id = agent.id and mgr.get_agent(agent.id).engine_id
    mgr.remove(agent.id)
    assert mgr.store.keys(f"agent:{agent.id}*") == []
    assert agent.id not in mgr.store.smembers(Keys.AGENTS_LIST)
    assert mgr.backend.engine_info(engine_id) is None
    assert mgr.scheduler.placement(agent.id) is None
    with pytest.raises(AgentNotFound):
        mgr.get_agent(agent.id)


def test_list_agents(mgr):
    a = mgr.deploy("a", "echo")
    b = mgr.deploy("b", "echo")
    ids = {ag.id for ag in mgr.list_agents()}
    assert ids == {a.id, b.id}


def test_status_published_on_change(mgr):
    got = []
    mgr.store.on_message("agent:status:*", lambda ch, msg: got.append((ch, msg)))
    agent = mgr.deploy("a", "echo")
    mgr.start(agent.id)
    assert (Keys.status_channel(agent.id), "running") in got


def test_scheduler_adjacent_windows_and_exhaustion(mgr):
    """Two 4-chip agents get disjoint 2×2 sub-rectangles of the v5e-8 2×4
    grid (ICI-adjacent blocks, not 1-D id runs); a third agent exhausts."""
    topo = mgr.scheduler.topology
    a = mgr.deploy("a", "echo", resources=Resources(chips=4, hbm_bytes=4 * topo.hbm_per_chip))
    b = mgr.deploy("b", "echo", resources=Resources(chips=4, hbm_bytes=4 * topo.hbm_per_chip))
    mgr.start(a.id)
    mgr.start(b.id)
    pa, pb = mgr.scheduler.placement(a.id), mgr.scheduler.placement(b.id)
    assert pa.chips == (0, 1, 4, 5)  # 2×2 block: cols 0-1 of both rows
    assert pb.chips == (2, 3, 6, 7)  # the remaining 2×2 block
    c = mgr.deploy("c", "echo", resources=Resources(chips=1, hbm_bytes=topo.hbm_per_chip))
    with pytest.raises(ResourceExhausted):
        mgr.start(c.id)
    mgr.remove(a.id)
    mgr.start(c.id)
    assert mgr.scheduler.placement(c.id).chips == (0,)


def test_scheduler_too_many_chips(mgr):
    a = mgr.deploy("a", "echo", resources=Resources(chips=16))
    with pytest.raises(ResourceExhausted):
        mgr.start(a.id)


def test_scheduler_weight_sharing():
    store = MemoryStore()
    topo = SliceTopology(total_chips=8)
    sched = SliceScheduler(store, topo)
    mgr = AgentManager(store, FakeBackend(), sched)
    # two llm agents on the same model config share chips + weight HBM
    res = Resources(chips=2, hbm_bytes=12 * 1024**3)
    a = mgr.deploy("a", ModelRef(engine="llm", config="tiny"), resources=res)
    b = mgr.deploy("b", ModelRef(engine="llm", config="tiny"), resources=res)
    mgr.start(a.id)
    mgr.start(b.id)
    pa, pb = sched.placement(a.id), sched.placement(b.id)
    assert pa.chips == pb.chips  # co-located
    assert pa.share_group == pb.share_group == "tiny"
    # usage counts the shared weights once: 12 GiB per 2 chips = 6 GiB/chip
    free = sched.free_hbm()
    assert free[0] == topo.hbm_per_chip - 6 * 1024**3


def test_scheduler_persistence_across_restart():
    store = MemoryStore()
    sched1 = SliceScheduler(store, SliceTopology(total_chips=8))
    mgr = AgentManager(store, FakeBackend(), sched1)
    a = mgr.deploy("a", "echo", resources=Resources(chips=2))
    mgr.start(a.id)
    # new scheduler instance over the same store sees the allocation
    sched2 = SliceScheduler(store, SliceTopology(total_chips=8))
    assert sched2.placement(a.id).chips == sched1.placement(a.id).chips


def test_scheduler_share_group_respects_capacity():
    """Joining a share group must not overcommit the group's chips."""
    store = MemoryStore()
    topo = SliceTopology(total_chips=8)
    sched = SliceScheduler(store, topo)
    mgr = AgentManager(store, FakeBackend(), sched)
    gib = 1024**3
    a = mgr.deploy(
        "a", ModelRef(engine="llm", config="tiny"), resources=Resources(chips=4, hbm_bytes=8 * gib)
    )
    mgr.start(a.id)  # group claim 2 GiB/chip on the first 2×2 block (0,1,4,5)
    s = mgr.deploy("s", "echo", resources=Resources(chips=4, hbm_bytes=56 * gib))
    mgr.start(s.id)  # solo 14 GiB/chip fills the same block to 16 GiB
    assert sched.placement(s.id).chips == (0, 1, 4, 5)
    # b wants to join the group with a bigger claim (8 GiB/chip): the
    # group's block can't absorb it, so it must be placed solo elsewhere,
    # not overcommitted
    b = mgr.deploy(
        "b", ModelRef(engine="llm", config="tiny"), resources=Resources(chips=4, hbm_bytes=32 * gib)
    )
    mgr.start(b.id)
    pb = sched.placement(b.id)
    assert pb.chips == (2, 3, 6, 7)
    assert pb.share_group == ""
    free = sched.free_hbm()
    assert all(v >= 0 for v in free.values())


def test_topology_2d_windows():
    """v5e-8 is a 2×4 grid: windows are sub-rectangles, squarer first
    (shorter worst-case ICI hop), and row-pairs are vertical neighbors."""
    from agentainer_tpu.runtime.scheduler import SliceTopology

    topo = SliceTopology(total_chips=8, mesh_shape=(2, 4))
    w4 = topo.windows(4)
    assert w4[0] == (0, 1, 4, 5)  # 2×2 beats 1×4
    assert (0, 1, 2, 3) in w4  # row runs are still candidates
    # chips 3 and 4 are NOT neighbors (different rows, opposite corners):
    # no window may pair them without their rectangle closure
    assert all(not ({3, 4} <= set(w) and len(w) == 2) for w in topo.windows(2))
    # vertical pairs exist: (0, 4) is a 2×1 rectangle
    assert (0, 4) in topo.windows(2)
    # whole slice
    assert topo.windows(8) == [(0, 1, 2, 3, 4, 5, 6, 7)]
    # n with no exact rectangle falls back to id runs
    assert topo.windows(5)[0] == (0, 1, 2, 3, 4)


def test_topology_derives_grid_from_chip_count():
    """A mesh_shape inconsistent with total_chips (daemon configs only set
    the count) derives the squarest grid; primes degenerate to a row."""
    from agentainer_tpu.runtime.scheduler import SliceTopology

    assert SliceTopology(total_chips=4, mesh_shape=(2, 4)).mesh_shape == (2, 2)
    assert SliceTopology(total_chips=16).mesh_shape == (4, 4)
    topo = SliceTopology(total_chips=3)
    assert topo.mesh_shape == (1, 3)
    assert topo.windows(2) == [(0, 1), (1, 2)]


def test_open_store_refuses_silent_durability_downgrade(monkeypatch, tmp_path):
    """native:// with an AOF path must RAISE when the native library is
    unavailable — a daemon must never believe it has durability it lacks.
    Plain native:// (no AOF) may fall back, loudly."""
    import agentainer_tpu.store.native as native_mod
    from agentainer_tpu.store import MemoryStore, open_store

    def boom(*a, **k):
        raise OSError("libagentainer_native.so: not built")

    monkeypatch.setattr(native_mod, "NativeStore", boom)
    with pytest.raises(RuntimeError, match="Refusing to downgrade"):
        open_store(f"native://{tmp_path}/store.aof")
    s = open_store("native://")  # no AOF requested: loud fallback allowed
    assert isinstance(s, MemoryStore)
    s.close()
