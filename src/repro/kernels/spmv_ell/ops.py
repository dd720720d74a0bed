"""Public ELL SpMV ops: CSR->ELL conversion, padding, backend dispatch."""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from .. import impl
from .ref import (
    spmv_ell_blocked_partial_ref,
    spmv_ell_blocked_ref,
    spmv_ell_ref,
)
from .spmv_ell import (
    DEFAULT_BLOCK_COLS,
    DEFAULT_BLOCK_ROWS,
    spmv_ell,
    spmv_ell_blocked,
    spmv_ell_blocked_partial,
    spmv_ell_blocked_skip,
)


def csr_to_ell(
    indptr: np.ndarray, indices: np.ndarray, data: np.ndarray,
    n_rows: int, pad_col: int, block_rows: int = DEFAULT_BLOCK_ROWS,
) -> Tuple[np.ndarray, np.ndarray]:
    """Pad rows to uniform K and pad the row count to the block size.
    ``pad_col`` must point at an x entry that is always zero."""
    lens = np.diff(indptr)
    K = max(int(lens.max()) if len(lens) else 1, 1)
    R = int(n_rows + ((-n_rows) % min(block_rows, max(n_rows, 1))))
    cols = np.full((R, K), pad_col, dtype=np.int32)
    vals = np.zeros((R, K), dtype=np.float32)
    for i in range(n_rows):
        lo, hi = int(indptr[i]), int(indptr[i + 1])
        cols[i, : hi - lo] = indices[lo:hi]
        vals[i, : hi - lo] = data[lo:hi]
    return cols, vals


def spmv(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Flat ELL SpMV: whole x VMEM-resident (kernel pads the row count)."""
    mode = impl("spmv_ell")
    if mode == "xla":
        return spmv_ell_ref(cols, vals, x)
    return spmv_ell(cols, vals, x, interpret=(mode == "pallas_interpret"))


def spmv_blocked(
    cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray,
    block_cols: int = DEFAULT_BLOCK_COLS,
) -> jnp.ndarray:
    """Column-blocked ELL SpMV over the bucketed [R, C*K] layout.

    ``x`` must be bucket-padded (length a multiple of ``block_cols``, as
    produced by the bucketed packing) — validated here so the reference
    and Pallas backends reject malformed input identically.
    """
    if x.shape[0] % block_cols:
        raise ValueError(
            f"x length {x.shape[0]} not a multiple of block_cols "
            f"{block_cols}: pack with partitioned_to_ell_blocked"
        )
    if cols.shape[1] % (x.shape[0] // block_cols):
        raise ValueError(
            f"cols width {cols.shape[1]} not divisible by the "
            f"{x.shape[0] // block_cols} x buckets"
        )
    mode = impl("spmv_ell")
    if mode == "xla":
        return spmv_ell_blocked_ref(cols, vals, x, block_cols)
    return spmv_ell_blocked(
        cols, vals, x, block_cols=block_cols,
        interpret=(mode == "pallas_interpret"),
    )


def spmv_blocked_partial(
    cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray, y0: jnp.ndarray,
    *,
    bucket_lo: int, bucket_hi: int, n_buckets: int,
    block_cols: int = DEFAULT_BLOCK_COLS,
) -> jnp.ndarray:
    """Blocked SpMV over buckets [lo, hi) accumulated into a carried ``y0``
    (the overlap schedule's per-phase entry point).  ``x`` holds only the
    range's slices: (hi - lo) * block_cols entries."""
    lo, hi = int(bucket_lo), int(bucket_hi)
    if not (0 <= lo <= hi <= n_buckets):
        raise ValueError(
            f"bucket range [{lo}, {hi}) outside [0, {n_buckets})"
        )
    if x.shape[0] != (hi - lo) * block_cols:
        raise ValueError(
            f"x length {x.shape[0]} != (hi-lo)*block_cols "
            f"{(hi - lo) * block_cols}"
        )
    if cols.shape[1] % n_buckets:
        raise ValueError(
            f"cols width {cols.shape[1]} not divisible by n_buckets "
            f"{n_buckets}"
        )
    mode = impl("spmv_ell")
    if mode == "xla":
        return spmv_ell_blocked_partial_ref(
            cols, vals, x, y0, lo, hi, block_cols, n_buckets
        )
    return spmv_ell_blocked_partial(
        cols, vals, x, y0, bucket_lo=lo, bucket_hi=hi, n_buckets=n_buckets,
        block_cols=block_cols, interpret=(mode == "pallas_interpret"),
    )


def spmv_blocked_skip(
    cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray,
    bucket_lists: jnp.ndarray, bucket_counts: jnp.ndarray,
    *,
    n_buckets: int, block_cols: int = DEFAULT_BLOCK_COLS,
    bucket_base: int = 0, y0: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Bucket-skipping blocked SpMV (per-row-block bucket lists, scalar
    prefetch).  ``x`` covers buckets [base, base + len(x)/block_cols).

    The reference backend exploits the packing invariant that unlisted
    buckets are all-zero (``row_block_bucket_map`` lists every bucket with
    a nonzero entry), so the dense partial sum over the covered window is
    the same value — keeping the CPU path one flat gather.
    """
    if x.shape[0] % block_cols:
        raise ValueError(
            f"x length {x.shape[0]} not a multiple of block_cols "
            f"{block_cols}"
        )
    if cols.shape[1] % n_buckets:
        raise ValueError(
            f"cols width {cols.shape[1]} not divisible by n_buckets "
            f"{n_buckets}"
        )
    mode = impl("spmv_ell")
    if mode == "xla":
        lo = int(bucket_base)
        hi = lo + x.shape[0] // int(block_cols)
        y0r = y0 if y0 is not None else jnp.zeros(cols.shape[0], vals.dtype)
        return spmv_ell_blocked_partial_ref(
            cols, vals, x, y0r, lo, hi, block_cols, n_buckets
        )
    return spmv_ell_blocked_skip(
        cols, vals, x, bucket_lists, bucket_counts, n_buckets=n_buckets,
        block_cols=block_cols, bucket_base=bucket_base, y0=y0,
        interpret=(mode == "pallas_interpret"),
    )
