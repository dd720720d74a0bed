from .ops import (
    csr_to_ell,
    spmv,
    spmv_blocked,
    spmv_blocked_partial,
    spmv_blocked_skip,
)
from .ref import (
    spmv_dia,
    spmv_ell_blocked_partial_ref,
    spmv_ell_blocked_ref,
    spmv_ell_ref,
)
from .spmv_ell import DEFAULT_BLOCK_COLS, DEFAULT_BLOCK_ROWS

__all__ = [
    "csr_to_ell", "spmv", "spmv_blocked",
    "spmv_blocked_partial", "spmv_blocked_skip", "spmv_dia",
    "spmv_ell_ref", "spmv_ell_blocked_ref", "spmv_ell_blocked_partial_ref",
    "DEFAULT_BLOCK_COLS", "DEFAULT_BLOCK_ROWS",
]
