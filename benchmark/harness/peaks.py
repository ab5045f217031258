"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip. A device
that is not in the table is an error, never a default: a share of an invented
peak is worse than none.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 394e12, "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 394e12, "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"device kind {device_kind!r} is not in the benchmark's table of peaks")
    return PEAKS[device_kind]
