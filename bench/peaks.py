"""Peak figures of each chip the benchmark may run on, keyed by
``jax.Device.device_kind``.

The yardstick of every roofline share.  A kind that is not listed is an
error: a share is never computed against a guessed peak.
"""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB of HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises for a kind not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak figures for device kind {device_kind!r}: add them to "
            f"bench/peaks.py with their source") from None
