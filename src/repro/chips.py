"""Per-chip hardware figures, keyed by ``jax.Device.device_kind``.

The one table for every number the code takes from the hardware: the VMEM
budget of the SpMV layout choice (``sparse.device``), the roofline peaks
(``launch.roofline``) and the HBM rate of the overlap model
(``core.costmodel``).  A TPU kind that is not listed is an error: the code
never guesses a chip's figures.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Chip:
    vmem_bytes: int          # VMEM per TensorCore
    peak_flops: float        # dense bf16 FLOP/s per chip
    hbm_bytes_per_s: float   # HBM bandwidth per chip
    hbm_bytes: float         # HBM capacity per chip


CHIPS = {
    # VMEM: jax 0.9.0, jax/_src/pallas/mosaic/tpu_info.py, "TPU v5 lite"
    # (128 MiB per core).  Peak, HBM rate and capacity: Google Cloud
    # documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s, 16 GB).
    "TPU v5 lite": Chip(
        vmem_bytes=128 * 2 ** 20,
        peak_flops=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
    ),
}


def chip(device_kind: str) -> Chip:
    """The figures of ``device_kind``; raises for a kind not in the table."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no hardware figures for device kind {device_kind!r}: add it "
            f"to repro.chips.CHIPS with its source"
        ) from None
