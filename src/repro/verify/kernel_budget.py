"""Static Pallas kernel budget + bucket-map coverage checks.

Two contracts are checked here, both without running a kernel:

* **Honest VMEM numbers.**  ``select_spmv_kernel`` picks flat vs blocked
  from the modeled ``spmv_flat/blocked_vmem_bytes`` estimators.  Those
  numbers are only trustworthy while they track the kernels' *actual*
  BlockSpec footprints — this module recomputes the footprint directly
  from the BlockSpec geometry in ``kernels/spmv_ell`` (block shapes,
  constant-vs-streamed index maps, double buffering of grid-varying
  blocks) and requires the estimator to agree within a tolerance, and the
  selected variant's actual residency to fit in a physical core's VMEM.
  If someone retiles a kernel and forgets the estimator, this is the
  tripwire.

* **Bucket-map exhaustiveness.**  The bucket-skipping kernel trusts
  ``row_block_bucket_map`` to enumerate, per row block, exactly the
  buckets holding nonzeros: a missing bucket silently drops values from
  the matvec, a duplicated bucket accumulates them twice.
  :func:`check_bucket_map` proves every nonzero is covered exactly once.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from ..kernels.spmv_ell import DEFAULT_BLOCK_COLS, DEFAULT_BLOCK_ROWS
from ..sparse.device import (
    _IDX_BYTES,
    row_block_bucket_map,
    spmv_blocked_vmem_bytes,
    spmv_flat_vmem_bytes,
    vmem_bytes_per_core,
)
from .invariants import VerifyError, _fail


# ---------------------------------------------------------------------------
# actual BlockSpec footprints (independent mirror of kernels/spmv_ell)
# ---------------------------------------------------------------------------


def flat_kernel_actual_bytes(
    ell, *, value_bytes: int = 8, block_rows: int = DEFAULT_BLOCK_ROWS
) -> int:
    """Residency of the flat path straight from its BlockSpecs.

    ``spmv_ell`` runs twice (local + ghost matvec).  Per launch: cols and
    vals blocks are ``(br, K)`` and vary with the grid step (double
    buffered), x is a grid-constant ``(N, 1)`` block resident once
    (``N = pad + 1`` sentinel slot), and the output block is ``(br, 1)``.
    The ghost launch runs over the ghost block's own rows (``Rg`` of a
    compact block), which clamp its ``br``.  The two launches are summed
    with one shared output accumulator, mirroring the estimator's
    both-resident assumption.  A local block stored by diagonals is no
    kernel launch; its ``D`` diagonals stand in for ``K`` here and in the
    estimate, which over-counts it.
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
    """ELL width of a flat form's local block, or its number of diagonals."""
    if ell.offsets is not None:
        return len(ell.offsets)
    return ell.local_cols.shape[2]


def blocked_kernel_actual_bytes(
    ell, *, value_bytes: int = 8, block_rows: int = DEFAULT_BLOCK_ROWS
) -> int:
    """Residency of the blocked path straight from its BlockSpecs.

    ``spmv_ell_blocked`` streams ``(br, K)`` cols/vals blocks and a
    ``(bc, 1)`` x bucket per grid step — all three vary with the grid, so
    all are double buffered — plus the ``(br, 1)`` output block.  Uses the
    *packed* per-bucket width ``ell.K`` (what the kernel actually loads),
    not the pre-packing upper bound the selector models with.
    """
    br = min(int(block_rows), ell.row_pad) if ell.row_pad else int(block_rows)
    stream = 2 * br * ell.K * (_IDX_BYTES + value_bytes)
    x_bytes = 2 * ell.block_cols * value_bytes
    out = br * value_bytes
    return int(stream + x_bytes + out)


def verify_kernel_budget(
    ell,
    selection=None,
    *,
    value_bytes: int = 8,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    rtol: float = 0.5,
) -> None:
    """Estimator honesty + hard VMEM fit for one device operator.

    ``ell`` is a ``DeviceEll`` (flat layout) or ``DeviceEllBlocked``
    (blocked layout), dispatched by shape fields.  Checks:

    1. the modeled estimator agrees with the BlockSpec-derived actual
       footprint within ``rtol`` (relative to the actual);
    2. for blocked layouts, the selector's recorded ``blocked_bytes`` is
       an upper bound on the actual (packing may shrink ``K``, never grow
       it) — a selector that under-reports would steer traffic into
       kernels that do not fit;
    3. the actual footprint of the laid-out kernel fits in a physical
       core's VMEM (the selection threshold is softer; this is the hard
       wall).
    """
    blocked = hasattr(ell, "bucket_K")
    if blocked:
        actual = blocked_kernel_actual_bytes(
            ell, value_bytes=value_bytes, block_rows=block_rows
        )
        modeled = spmv_blocked_vmem_bytes(
            bucket_k=ell.K, value_bytes=value_bytes,
            rows=ell.row_pad, block_rows=block_rows,
            block_cols=ell.block_cols,
        )
        variant = "blocked"
    else:
        actual = flat_kernel_actual_bytes(
            ell, value_bytes=value_bytes, block_rows=block_rows
        )
        modeled = spmv_flat_vmem_bytes(
            in_pad=ell.in_pad, ghost_pad=ell.ghost_pad,
            k_local=_local_width(ell),
            k_ghost=ell.ghost_cols.shape[2],
            value_bytes=value_bytes, rows=ell.row_pad,
            block_rows=block_rows, ghost_rows=ell.ghost_cols.shape[1],
        )
        variant = "flat"
    if abs(modeled - actual) > rtol * max(actual, 1):
        _fail("modeled VMEM estimator drifted from the kernel's BlockSpec "
              "footprint", variant=variant, modeled=modeled, actual=actual,
              rtol=rtol)
    if blocked and selection is not None and \
            selection.blocked_bytes < actual:
        _fail("kernel selection under-reports the blocked footprint",
              recorded=selection.blocked_bytes, actual=actual)
    vmem = vmem_bytes_per_core()
    if selection is not None and selection.variant == variant and \
            actual > vmem:
        _fail("selected kernel's actual footprint exceeds physical VMEM",
              variant=variant, actual=actual, vmem=vmem)


# ---------------------------------------------------------------------------
# bucket-map coverage (skip kernel)
# ---------------------------------------------------------------------------


def check_bucket_map(
    ell,
    lists: np.ndarray,
    counts: np.ndarray,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    bucket_lo: int = 0,
    bucket_hi: Optional[int] = None,
) -> None:
    """Prove a (lists, counts) pair covers every nonzero exactly once.

    The skip kernel visits, for row block ``i``, exactly the buckets
    ``lists[p, i, :counts[p, i]]``: a live bucket absent from its list is
    dropped from the matvec; a bucket listed twice is accumulated twice.
    Checks shapes against the kernel's row blocking, ascending unique
    in-window entries, inert ``bucket_lo`` padding, and exact agreement
    with the live set recomputed from ``ell.vals``.
    """
    C, K = ell.n_buckets, ell.K
    lo = int(bucket_lo)
    hi = C if bucket_hi is None else int(bucket_hi)
    R = ell.row_pad
    br = min(int(block_rows), R)
    nrb = (R + (-R) % br) // br
    if counts.shape != (ell.n_procs, nrb):
        _fail("bucket-map counts shape disagrees with the kernel grid",
              shape=counts.shape, expected=(ell.n_procs, nrb))
    if lists.shape[:2] != (ell.n_procs, nrb):
        _fail("bucket-map lists shape disagrees with the kernel grid",
              shape=lists.shape, expected_leading=(ell.n_procs, nrb))
    M = lists.shape[2]
    live = (ell.vals.reshape(ell.n_procs, R, C, K) != 0).any(-1)
    for p in range(ell.n_procs):
        for rb in range(nrb):
            n = int(counts[p, rb])
            if not 0 <= n <= M:
                _fail("bucket count outside the list capacity", rank=p,
                      row_block=rb, count=n, capacity=M)
            row = lists[p, rb]
            head = row[:n].astype(np.int64)
            if n and (head.min() < lo or head.max() >= hi):
                _fail("listed bucket outside the kernel's window", rank=p,
                      row_block=rb,
                      bucket=int(head[np.argmax(
                          (head < lo) | (head >= hi))]),
                      window=(lo, hi))
            if np.any(np.diff(head) == 0):
                dup = int(head[np.argmax(np.diff(head) == 0)])
                _fail("duplicated bucket in a row-block list (its values "
                      "would be accumulated twice)", rank=p, row_block=rb,
                      bucket=dup)
            if np.any(np.diff(head) < 0):
                _fail("bucket list not ascending", rank=p, row_block=rb)
            if np.any(row[n:] != lo):
                _fail("bucket-list padding is not the inert bucket_lo "
                      "value", rank=p, row_block=rb,
                      slot=int(n + np.argmax(row[n:] != lo)))
            rows = live[p, rb * br: min((rb + 1) * br, R), lo:hi]
            want = np.flatnonzero(rows.any(0)) + lo
            missing = np.setdiff1d(want, head)
            if len(missing):
                _fail("live bucket missing from the row-block list (its "
                      "nonzeros would be dropped)", rank=p, row_block=rb,
                      bucket=int(missing[0]))
            extra = np.setdiff1d(head, want)
            if len(extra):
                _fail("dead bucket listed for a row block", rank=p,
                      row_block=rb, bucket=int(extra[0]))


def verify_bucket_map(
    ell,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    bucket_lo: int = 0,
    bucket_hi: Optional[int] = None,
) -> None:
    """Build the map the kernels would use and prove it exhaustive."""
    lists, counts = row_block_bucket_map(
        ell, block_rows=block_rows, bucket_lo=bucket_lo,
        bucket_hi=bucket_hi,
    )
    check_bucket_map(
        ell, lists, counts, block_rows=block_rows, bucket_lo=bucket_lo,
        bucket_hi=bucket_hi,
    )


__all__ = [
    "VerifyError",
    "flat_kernel_actual_bytes",
    "blocked_kernel_actual_bytes",
    "verify_kernel_budget",
    "check_bucket_map",
    "verify_bucket_map",
]
