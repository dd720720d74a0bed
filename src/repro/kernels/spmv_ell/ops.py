"""Public ELL SpMV ops: CSR->ELL conversion, padding, backend dispatch."""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from .. import impl
from .ref import spmv_ell_ref
from .spmv_ell import DEFAULT_BLOCK_ROWS, spmv_ell


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
    """ELL SpMV: whole x VMEM-resident (kernel pads the row count)."""
    mode = impl("spmv_ell")
    if mode == "xla":
        return spmv_ell_ref(cols, vals, x)
    return spmv_ell(cols, vals, x, interpret=(mode == "pallas_interpret"))
