"""Test harness config.

Multi-chip logic is tested without TPU hardware: force the JAX CPU platform
and fake 8 host devices so `jax.sharding.Mesh` tests exercise real SPMD
partitioning + collectives (the TPU-world analogue of the reference's
"single host by design, no multi-node tests" gap — SURVEY.md §4).

This must run before anything imports jax, hence conftest top-level.
"""

import os

# Unit tests stay on the CPU mesh whatever the machine holds: force, don't
# setdefault — engine subprocesses inherit this environment too.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Persistent compilation cache for the suite: dozens of tests build the
# SAME tiny-model engine, and each used to recompile the identical
# prefill/decode/verify programs from scratch — the single largest cost
# in the tier-1 wall clock (serve_pp alone: 54s -> 22s with a cold
# cache). The cache keys on HLO + compile options, so code changes that
# alter the computation miss naturally. Placed by the repo's one rule
# (utils/compile_cache.py), so tests, engine subprocesses and manual runs
# all share it.
from agentainer_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax  # noqa: E402

import faulthandler  # noqa: E402
import socket  # noqa: E402

import pytest  # noqa: E402

# A hung worker-loop test must print stacks, not silently eat the tier-1
# budget: faulthandler dumps EVERY thread's traceback (worker thread,
# readback waits, asyncio loop) to stderr if a single test exceeds the
# window, then the run continues — the dump is diagnosis, not a killer
# (timeout -k on the whole suite remains the hard stop).
faulthandler.enable()
_TEST_DUMP_S = float(os.environ.get("ATPU_TEST_DUMP_S", "300"))


@pytest.fixture(autouse=True)
def _dump_stacks_on_hang():
    faulthandler.dump_traceback_later(_TEST_DUMP_S, exit=False)
    yield
    faulthandler.cancel_dump_traceback_later()


# Every live XLA:CPU executable pins a handful of LLVM JIT mappings
# (code/rodata/guard pages), and the tier-1 process compiles thousands of
# programs across the suite — enough to cross vm.max_map_count (~65k), at
# which point the next mmap inside LLVM fails and the process SEGFAULTS
# mid-compile (observed at ~60k maps). Dropping executable references at a
# module boundary once the map count nears the limit keeps the process
# bounded; the persistent compilation cache above makes the resulting
# recompiles cheap disk reads, not fresh XLA compiles.
_MAP_GUARD = 40_000


@pytest.fixture(autouse=True, scope="module")
def _jit_map_guard():
    yield
    try:
        with open("/proc/self/maps") as f:
            n = sum(1 for _ in f)
    except OSError:
        return
    if n > _MAP_GUARD:
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture
def barrier_is_identity(monkeypatch):
    """``lax.optimization_barrier`` as the identity it computes, for a test
    that shows a barrier is ALL a change added to a program: what is traced
    under it has to be the program that was there before. Traces are cached
    by function and shapes, so the caches are dropped on both sides."""
    jax.clear_caches()
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    yield
    jax.clear_caches()


@pytest.fixture
def where_llvm_may_not_reorder():
    """``run(code)``: Python ``code`` in a process of its own (the suite's
    platform and flags, ``tests.conftest`` imported first) in which XLA:CPU
    cannot round one arithmetic two ways: SSE4.2 has no fused multiply-add
    for LLVM to contract ``a * b + c`` into, and at -O0 LLVM reorders none of
    the sums XLA marks ``reassoc``. Flags are read once a process, hence the
    process; it has to exit 0."""
    import platform
    import subprocess
    import sys

    if platform.machine() not in ("x86_64", "AMD64"):
        pytest.skip(f"--xla_cpu_max_isa names x86 instruction sets; this host is {platform.machine()}")

    def run(code: str):
        flags = f"{os.environ['XLA_FLAGS']} --xla_cpu_max_isa=SSE4_2 --xla_backend_optimization_level=0"
        done = subprocess.run(
            [sys.executable, "-c", "import tests.conftest\n" + code], cwd=os.path.dirname(os.path.dirname(__file__)),
            env={**os.environ, "XLA_FLAGS": flags}, capture_output=True, text=True, timeout=600,
        )
        assert done.returncode == 0, done.stderr[-3000:]

    return run


def _native_available() -> bool:
    try:
        from agentainer_tpu.native import available

        return available()
    except Exception:
        return False


@pytest.fixture(params=["memory", "native"])
def store(request):
    """Every store-semantics test runs against both implementations — the
    MemoryStore is the behavioral spec the C++ store must match."""
    if request.param == "native":
        if not _native_available():
            pytest.skip("native library unavailable")
        from agentainer_tpu.store.native import NativeStore

        s = NativeStore()
    else:
        from agentainer_tpu.store import MemoryStore

        s = MemoryStore()
    yield s
    s.close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture
def port() -> int:
    return free_port()
