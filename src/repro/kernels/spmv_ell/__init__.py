from .ops import csr_to_ell, spmv
from .ref import spmv_dia, spmv_ell_ref
from .spmv_ell import DEFAULT_BLOCK_ROWS

__all__ = [
    "csr_to_ell", "spmv", "spmv_dia", "spmv_ell_ref", "DEFAULT_BLOCK_ROWS",
]
