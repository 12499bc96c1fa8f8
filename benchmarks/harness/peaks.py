"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` JAX reports. A device that is not here is an error, never a
default: a share of another chip's peak is a wrong number under a right
name. (ray_tpu/accelerators/flops.py has FLOP/s only, and a nominal CPU
row; see PERF.md's Open questions.)"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB HBM2e at 819 GB/s per chip
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks on record for device kind "
                       f"{device_kind!r}: add it to benchmarks/harness/"
                       f"peaks.py with its source")
    return PEAKS[device_kind]
