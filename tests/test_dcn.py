"""DCN / multi-host skeleton (SURVEY §5.8): two REAL OS processes join a
jax.distributed cluster over loopback (the CPU stand-in for cross-host
DCN), build the canonical host mesh, and run a dp collective whose result
proves the reduction crossed the process boundary.

Also covers the scheduler's host awareness: multi-host topologies prefer
single-host (ICI-only) windows and report host spans.
"""

import socket
import subprocess
import sys
import textwrap

import pytest

from agentainer_tpu.runtime.scheduler import SliceTopology

_WORKER = textwrap.dedent(
    """
    import sys
    import jax

    from agentainer_tpu.parallel.dcn import DistConfig, host_mesh, init_distributed

    coordinator, pid = sys.argv[1], int(sys.argv[2])
    assert init_distributed(DistConfig(coordinator, 2, pid))
    assert jax.process_count() == 2

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = host_mesh()  # dp spans both processes
    dp = mesh.shape["dp"]
    assert dp == len(jax.devices()), mesh.shape

    # one global dp-sharded array: each process contributes its local rows;
    # the psum must therefore cross the process boundary (DCN stand-in)
    def summed(x):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P("dp"))).sum()

    local = jnp.arange(2, dtype=jnp.float32)  # this process's rows
    arrs = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), local, (4,)
    )
    total = jax.jit(summed, out_shardings=NamedSharding(mesh, P()))(arrs)
    # process 0 holds [0, 1], process 1 holds [0, 1] -> global [0,1,0,1]
    assert float(total) == 2.0, float(total)
    print(f"proc {pid}: cross-process sum OK -> {float(total)}", flush=True)
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_dp_collective(tmp_path):
    coordinator = f"127.0.0.1:{_free_port()}"
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": "/root/repo",
        "PATH": "/usr/bin:/bin",
    }
    import os

    env = {**os.environ, **env}
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), coordinator, str(i)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=150)
        outs.append(out.decode())
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
        assert "cross-process sum OK" in out


@pytest.mark.parametrize("n_devices", [2, 8])
def test_dryrun_multichip_exits_cleanly(n_devices):
    """The driver's multi-chip entry point, as the driver calls it: a tp
    engine and a MoE tp × ep engine over n virtual devices, then the
    two-process smoke. In a child, because it sets JAX's environment."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [
            sys.executable,
            "-c",
            f"import __graft_entry__ as g; g.dryrun_multichip({n_devices})",
        ],
        cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        timeout=280,
    )
    out = done.stdout.decode()
    assert done.returncode == 0, out
    assert out.count("dryrun_multichip OK") == 3, out


def test_topology_prefers_single_host_windows():
    topo = SliceTopology(total_chips=16, hosts=2, mesh_shape=(4, 4))
    assert topo.chips_per_host == 8
    assert topo.host_of(0) == 0 and topo.host_of(8) == 1
    wins = topo.windows(4)
    crossed = [topo.spans_hosts(w) for w in wins]
    assert not all(crossed), "expected some single-host windows"
    # every single-host window must rank before any cross-host window
    first_cross = crossed.index(True) if True in crossed else len(crossed)
    assert not any(crossed[:first_cross])
    assert all(crossed[first_cross:])


def test_topology_rejects_non_dividing_hosts():
    with pytest.raises(ValueError, match="must divide"):
        SliceTopology(total_chips=8, hosts=3)
