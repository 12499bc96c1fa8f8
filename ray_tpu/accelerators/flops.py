"""Peak-FLOP/s table: the single source of truth for MFU arithmetic.

Promoted out of bench.py so the offline bench and the live
accelerator-plane MFU gauge (`_internal/accel.py` report_step) divide by
the SAME denominator — two diverging tables would make "bench says 65%
MFU, the gauge says 40%" a permanent support thread.

Keys are device-kind substrings (matched against
``jax.Device.device_kind.lower()``, first match wins — more specific
generations first). Values are peak dense bf16 FLOP/s per chip from the
published TPU specs; "cpu" is a nominal 1 TFLOP/s so the CPU test runs
still fold a finite (and meaningless) MFU gauge. A device that is not
in the table is an error, not a default: a utilization divided by
another chip's peak is a wrong number under a right name.
"""

from __future__ import annotations

from typing import Optional

PEAK_FLOPS = {
    "v6e": 918e12,
    "v6": 918e12,
    "v5p": 459e12,
    "v5 lite": 197e12,  # device_kind spelling of v5e
    "v5litepod": 197e12,
    "v5e": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "cpu": 1e12,  # nominal: CPU test runs only, never a device metric
}


def peak_flops_for_kind(device_kind: Optional[str]) -> float:
    """Peak bf16 FLOP/s for a device-kind string (substring match)."""
    kind = (device_kind or "cpu").lower()
    for key, value in PEAK_FLOPS.items():
        if key in kind:
            return value
    raise KeyError(
        f"no peak FLOP/s on record for device kind {device_kind!r}; add "
        f"it to accelerators.flops.PEAK_FLOPS with its published source")


def peak_flops(device) -> float:
    """Peak bf16 FLOP/s for a ``jax.Device`` (or anything with a
    ``device_kind`` attribute)."""
    return peak_flops_for_kind(getattr(device, "device_kind", "cpu"))
