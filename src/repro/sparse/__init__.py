from .csr import CSR
from .partition import (
    PartitionedCSR,
    block_offsets,
    distributed_spmv_numpy,
    partition_csr,
    partition_rect_csr,
    partitioned_from_blocks,
    split_rows,
    stack_blocks,
)
from .device import (
    DeviceEll,
    OverlapSelection,
    diagonal_offsets,
    distributed_spmv,
    make_distributed_spmv,
    overlap_decision,
    pack_vector,
    partitioned_to_device,
    partitioned_to_dia,
    partitioned_to_ell,
    select_spmv_overlap,
    unpack_vector,
)
from .spgemm import (
    RapResult,
    RowGather,
    gather_remote_rows,
    merge_row_sets,
    spgemm_local,
    spgemm_rap,
)

__all__ = [
    "CSR", "PartitionedCSR", "block_offsets", "distributed_spmv_numpy",
    "partition_csr", "partition_rect_csr", "partitioned_from_blocks",
    "split_rows", "stack_blocks",
    "DeviceEll", "OverlapSelection", "diagonal_offsets", "distributed_spmv",
    "make_distributed_spmv", "overlap_decision", "pack_vector",
    "partitioned_to_device", "partitioned_to_dia", "partitioned_to_ell",
    "select_spmv_overlap", "unpack_vector",
    "RapResult", "RowGather", "gather_remote_rows", "merge_row_sets",
    "spgemm_local", "spgemm_rap",
]
