"""Static Pallas kernel budget check for the SpMV device layout.

``spmv_ell`` keeps the whole per-device x VMEM-resident, so its footprint
grows with the operator.  This module computes that footprint straight
from the kernel's BlockSpec geometry in ``kernels/spmv_ell`` (block
shapes, constant-vs-streamed index maps, double buffering of
grid-varying blocks), without running a kernel, and requires it to fit
one core's VMEM.
"""
from __future__ import annotations

from ..kernels.spmv_ell import DEFAULT_BLOCK_ROWS
from ..sparse.device import _IDX_BYTES
from .invariants import _fail

#: VMEM per core that runs off the TPU (tests, CPU examples) model: a
#: 16 MiB core.  On a TPU the figure comes from ``repro.chips``.
MODELED_VMEM_BYTES = 16 * 2 ** 20


def vmem_bytes_per_core() -> int:
    """VMEM per core of the device this process runs on.

    A TPU's figure is looked up by ``device_kind`` in
    :data:`repro.chips.CHIPS`; a kind missing there raises.
    """
    import jax

    from ..chips import chip

    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return chip(dev.device_kind).vmem_bytes
    return MODELED_VMEM_BYTES


def flat_kernel_actual_bytes(
    ell, *, value_bytes: int = 8, block_rows: int = DEFAULT_BLOCK_ROWS
) -> int:
    """Residency of the SpMV straight from its BlockSpecs.

    ``spmv_ell`` runs twice (local + ghost matvec).  Per launch: cols and
    vals blocks are ``(br, K)`` and vary with the grid step (double
    buffered), x is a grid-constant ``(N, 1)`` block resident once
    (``N = pad + 1`` sentinel slot), and the output block is ``(br, 1)``.
    The ghost launch runs over the ghost block's own rows (``Rg`` of a
    compact block), which clamp its ``br``.  The two launches are summed
    with one shared output accumulator: inside the fused jitted program
    XLA is free to schedule them concurrently.  A local block stored by
    diagonals is no kernel launch; its ``D`` diagonals stand in for ``K``
    here, which over-counts it.
    """
    br = min(int(block_rows), ell.row_pad) if ell.row_pad else int(block_rows)
    brg = min(br, ell.ghost_cols.shape[1])
    kl = _local_width(ell)
    kg = ell.ghost_cols.shape[2]
    x_local = (ell.in_pad + 1) * value_bytes
    x_ghost = (ell.ghost_pad + 1) * value_bytes if ell.ghost_pad else 0
    stream = 2 * (br * kl + brg * kg) * (_IDX_BYTES + value_bytes)
    out = br * value_bytes
    return int(x_local + x_ghost + stream + out)


def _local_width(ell) -> int:
    """ELL width of a device form's local block, or its number of
    diagonals."""
    if ell.offsets is not None:
        return len(ell.offsets)
    return ell.local_cols.shape[2]


def verify_kernel_budget(
    ell,
    *,
    value_bytes: int = 8,
    block_rows: int = DEFAULT_BLOCK_ROWS,
) -> None:
    """The SpMV footprint of one device operator (a ``DeviceEll``) fits in
    a physical core's VMEM."""
    actual = flat_kernel_actual_bytes(
        ell, value_bytes=value_bytes, block_rows=block_rows
    )
    vmem = vmem_bytes_per_core()
    if actual > vmem:
        _fail("SpMV kernel's footprint exceeds physical VMEM",
              actual=actual, vmem=vmem)


__all__ = [
    "MODELED_VMEM_BYTES",
    "flat_kernel_actual_bytes",
    "verify_kernel_budget",
    "vmem_bytes_per_core",
]
