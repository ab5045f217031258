"""Engine subprocess entry point: ``python -m agentainer_tpu.runtime.engine_main``.

The analogue of a container's CMD (reference examples/gpt-agent/Dockerfile
runs gunicorn app:app). The LocalBackend spawns this with the agent's
identity, port, chip assignment, and control-plane URL in the environment.
Engine selection stays lazy so the echo engine never imports JAX.
"""

from __future__ import annotations

import logging
import os
import sys
import time


def main() -> None:
    # where the boot's timeline starts (utils/boot.py): both clocks, together
    started_ns, started_unix_ns = time.perf_counter_ns(), time.time_ns()
    # request lines (aiohttp.access) and engine warnings go to stdout, which
    # the backend captures into the engine's log file — the same visibility
    # a container gets from docker logs (agent.go:411-429 / logs --follow)
    logging.basicConfig(
        level=logging.INFO,
        stream=sys.stdout,
        format="%(asctime)s %(name)s %(message)s",
        force=True,
    )
    # fault plane: engine-side failpoints (engine.*, store_client.rpc) arm
    # from the env the daemon exported; unset = registry empty = no-ops
    if os.environ.get("ATPU_FAULTS"):
        from .. import faults

        faults.arm_from_env()
    engine = os.environ.get("AGENTAINER_ENGINE", "echo")
    from ..engine import is_tpu_engine

    boot = None
    if is_tpu_engine(engine):
        # boot.import runs from main's entry until the serve app is built;
        # the recorder's first span imports JAX (the echo engine never does)
        from ..utils.boot import BootTimeline

        boot = BootTimeline.at_main(started_ns, started_unix_ns, os.environ)
        # The platform and the chips this process may open come from its
        # environment alone (JAX_PLATFORMS, the TPU visibility variables
        # runtime/local.py sets): nothing has imported JAX before this
        # point, so the plain variables are honoured.
        # Multi-host: the ENGINE processes are the ones running JAX compute,
        # so they are what joins the jax.distributed cluster (one TPU engine
        # per host, ATPU_DIST_* set by the operator/scheduler). The control
        # plane never blocks on the cluster barrier.
        from ..parallel.dcn import init_distributed

        try:
            init_distributed()
        except Exception as e:
            # Loud failure (ADVICE r3): an engine explicitly configured to
            # join a multi-host cluster must not silently serve a local-only
            # topology the operator believes spans hosts.
            print(f"[engine] jax.distributed init failed: {e}", file=sys.stderr)
            sys.exit(3)
    import importlib

    from ..engine import engine_registry

    module = engine_registry().get(engine)
    if module is None:
        print(f"unknown engine {engine!r}", file=sys.stderr)
        sys.exit(2)
    serve = importlib.import_module(module).serve
    if boot is None:
        serve()
    else:
        serve(boot=boot)


if __name__ == "__main__":
    main()
