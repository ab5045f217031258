"""TPU hardware envelope: peak FLOPs and HBM bandwidth per device kind.

Used for MFU/MBU accounting in the engine's metrics plane and bench_llm.py.
Numbers are public spec-sheet peaks per CHIP; ``jax.devices()``
reports one device per chip on v4+ (v2/v3 report per-core — the two-core
kinds below carry per-core numbers for that reason).

The engine divides its achieved FLOP rate by ``peak_flops × n_devices`` so
a TP-sharded engine is measured against the peak of every chip it spans.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipSpec:
    kind: str
    bf16_flops: float  # peak FLOP/s, bf16 into f32 MXU
    int8_ops: float  # peak OP/s, int8
    hbm_bytes: int
    hbm_gbps: float  # bytes/s


# substring match against jax device_kind, first hit wins — keep more
# specific names ("v5 lite", "v5p") ahead of any shorter prefix they contain.
_SPECS: tuple[ChipSpec, ...] = (
    ChipSpec("v6 lite", 918e12, 1836e12, 32 << 30, 1640e9),  # Trillium / v6e
    ChipSpec("v5 lite", 197e12, 394e12, 16 << 30, 819e9),  # v5e
    ChipSpec("v5p", 459e12, 918e12, 95 << 30, 2765e9),
    ChipSpec("v4", 275e12, 275e12, 32 << 30, 1228e9),
    ChipSpec("v3", 61.4e12, 61.4e12, 16 << 30, 450e9),  # per core
    ChipSpec("v2", 23e12, 23e12, 8 << 30, 350e9),  # per core
)


def chip_spec(device_kind: str) -> ChipSpec | None:
    """Spec for a jax ``device_kind``; None for a device that is not in the
    table (a CPU, an unlisted part). A utilization against an invented peak
    is worse than none, so callers report no MFU/MBU for an unknown kind."""
    kind = device_kind.lower()
    for spec in _SPECS:
        if spec.kind in kind:
            return spec
    return None
