"""DCN / multi-host distributed backend (SURVEY §2.3 "collective backend",
§5.8): ``jax.distributed`` wiring so meshes span hosts — on-slice traffic
(tp/ep) rides ICI, cross-host data parallelism rides DCN, the same way
the reference's role would be filled by NCCL/MPI in a GPU stack (the
reference itself has neither — Docker bridge + Redis only).

Activation is explicit (config/env), because initialize() is process-global
and must happen before any jax computation:

    ATPU_DIST_COORDINATOR=host0:9911   # coordinator address (process 0's)
    ATPU_DIST_NUM_PROCESSES=2
    ATPU_DIST_PROCESS_ID=0             # this host's rank

``host_mesh`` builds the canonical multi-host mesh: the dp axis is laid out
over PROCESS boundaries first (outermost), so gradient all-reduces cross
DCN once per step while tp/ep collectives stay inside each host's ICI
domain — the scaling-book recipe.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

# jax imports stay function-local: the control-plane daemon calls
# init_distributed() at boot and must not pay (or trigger) jax/device
# initialization when distribution isn't configured.


@dataclass(frozen=True)
class DistConfig:
    coordinator: str = ""
    num_processes: int = 1
    process_id: int = 0

    @property
    def enabled(self) -> bool:
        return bool(self.coordinator) and self.num_processes > 1


def dist_config_from_env() -> DistConfig:
    return DistConfig(
        coordinator=os.environ.get("ATPU_DIST_COORDINATOR", ""),
        num_processes=int(os.environ.get("ATPU_DIST_NUM_PROCESSES", "1") or 1),
        process_id=int(os.environ.get("ATPU_DIST_PROCESS_ID", "0") or 0),
    )


_INITIALIZED = False


def init_distributed(cfg: DistConfig | None = None) -> bool:
    """Join the jax.distributed cluster when configured; no-op (False)
    otherwise. Safe to call more than once."""
    global _INITIALIZED
    cfg = cfg or dist_config_from_env()
    if not cfg.enabled:
        return False
    if _INITIALIZED:
        return True
    import jax

    jax.distributed.initialize(
        coordinator_address=cfg.coordinator,
        num_processes=cfg.num_processes,
        process_id=cfg.process_id,
    )
    _INITIALIZED = True
    return True


def host_count() -> int:
    import jax

    return jax.process_count()


def host_mesh(tp: int = 1, ep: int = 1):
    """Global mesh over every process's devices with dp spanning the host
    (DCN) dimension outermost — the one mesh with a dp axis; a served
    engine's mesh (parallel/mesh.make_mesh) is tp × ep. The model axes must
    fit within one host's device count so their collectives never cross
    DCN."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()  # global, ordered by process
    per_host = len(devs) // max(1, jax.process_count())
    denom = tp * ep
    if denom > per_host or per_host % denom:
        # divisibility matters, not just fit: a denom that doesn't divide
        # per_host would make consecutive-device model groups straddle a
        # host boundary, putting their collectives on DCN
        raise ValueError(
            f"tp*ep={denom} must divide one host's {per_host} devices — "
            "model-parallel collectives must stay on ICI, not DCN"
        )
    if len(devs) % denom:
        raise ValueError(f"{len(devs)} devices not divisible by {denom}")
    dp = len(devs) // denom
    return Mesh(np.array(devs).reshape(dp, tp, ep), axis_names=("dp", "tp", "ep"))
