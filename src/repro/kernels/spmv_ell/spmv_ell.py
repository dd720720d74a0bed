"""Local SpMV in ELL (padded-CSR) form as Pallas TPU kernels.

This is the per-device compute of the paper's workload: after the halo
exchange delivers ghost values, each device multiplies its local sparse
block.  CSR's ragged rows are hostile to the VPU's lane layout, so rows are
padded to a uniform K nonzeros (ELL): ``cols``/``vals`` are [R, K] with
padding entries pointing at a zero slot.  Two execution paths:

* :func:`spmv_ell` — the flat kernel: the whole x vector lives in VMEM,
  rows are tiled over a 1-D grid, and the inner product is a VMEM dynamic
  gather + multiply + row reduction.  Right whenever the per-device local +
  ghost vector fits comfortably in VMEM (coarse AMG levels, small blocks).

* :func:`spmv_ell_blocked` — the production path for levels whose x exceeds
  VMEM (paper-scale fine levels): x is column-tiled over a second grid
  dimension, each grid step gathers only its ``block_cols``-wide x slice,
  and the row block's output accumulates across the column steps (the
  second grid dim is ``arbitrary``/sequential, the row dim stays parallel).
  The matching column-bucketed packing lives in
  ``repro.sparse.device.partitioned_to_ell_blocked``: each row's nonzeros
  are reordered into per-column-block buckets (in-bucket column indices),
  so ``cols``/``vals`` are [R, C*K] with bucket ``j`` occupying columns
  [j*K, (j+1)*K) and referencing only x[j*bc:(j+1)*bc).

* :func:`spmv_ell_blocked_partial` — the blocked kernel restricted to a
  bucket range [lo, hi), accumulating into a *carried* output.  This is
  the overlap building block: the distributed SpMV runs the local buckets
  while the halo exchange is in flight, then consumes the ghost buckets
  from the carried partial result (``repro.sparse.device.
  make_distributed_spmv(..., overlap=True)``).

* :func:`spmv_ell_blocked_skip` — the blocked kernel driven by per-row-
  block bucket *lists* via scalar prefetch: grid step (i, j) visits bucket
  ``bucket_lists[i, j]`` and steps past ``bucket_counts[i]`` are masked,
  so banded operators stream only the buckets a row block actually
  touches instead of every bucket.  Shares the carried-output convention
  with the partial kernel so the overlap schedule can use either per
  phase.

Row counts need not divide ``block_rows``: the trailing row block is padded
(col 0 / val 0 — the product is exactly zero) and the padding rows are
sliced off the output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_COLS = 512


def _pad_rows(cols: jnp.ndarray, vals: jnp.ndarray, block_rows: int):
    """Pad the trailing row block; padding rows gather x[0] * 0.0 == 0."""
    R = cols.shape[0]
    br = min(block_rows, R)
    pad = (-R) % br
    if pad:
        cols = jnp.concatenate(
            [cols, jnp.zeros((pad, cols.shape[1]), cols.dtype)]
        )
        vals = jnp.concatenate(
            [vals, jnp.zeros((pad, vals.shape[1]), vals.dtype)]
        )
    return cols, vals, br


def _spmv_kernel(cols_ref, vals_ref, x_ref, y_ref):
    cols = cols_ref[...]          # [BR, K] int32
    vals = vals_ref[...]          # [BR, K]
    x = x_ref[...]                # [N, 1]
    gathered = x[cols, 0]         # [BR, K] VMEM dynamic gather
    y_ref[...] = jnp.sum(vals * gathered, axis=1, keepdims=True)


def spmv_ell(
    cols: jnp.ndarray,   # [R, K] int32 (padding -> index of a zero x entry)
    vals: jnp.ndarray,   # [R, K]
    x: jnp.ndarray,      # [N]  (local values ++ ghost values ++ one zero pad)
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
    R = cols.shape[0]
    N = x.shape[0]
    cols, vals, br = _pad_rows(cols, vals, block_rows)
    Rp, K = cols.shape
    return pl.pallas_call(
        _spmv_kernel,
        grid=(Rp // br,),
        in_specs=[
            pl.BlockSpec((br, K), lambda i: (i, 0)),
            pl.BlockSpec((br, K), lambda i: (i, 0)),
            pl.BlockSpec((N, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, 1), vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(cols, vals, x[:, None])[:R, 0]


def _spmv_blocked_kernel(cols_ref, vals_ref, x_ref, y_ref):
    j = pl.program_id(1)
    cols = cols_ref[...]          # [BR, K] in-bucket indices (< block_cols)
    vals = vals_ref[...]          # [BR, K]
    x = x_ref[...]                # [BC, 1] — only this bucket's x slice
    partial = jnp.sum(vals * x[cols, 0], axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = partial

    @pl.when(j > 0)
    def _accumulate():
        y_ref[...] = y_ref[...] + partial


def spmv_ell_blocked(
    cols: jnp.ndarray,   # [R, C*K] int32 in-bucket indices (padding -> 0)
    vals: jnp.ndarray,   # [R, C*K]     (padding -> 0.0)
    x: jnp.ndarray,      # [C * block_cols]
    *,
    block_cols: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
    """Column-blocked ELL SpMV: y[i] = sum_j sum_k vals[i,j*K+k] *
    x[j*bc + cols[i,j*K+k]].

    Grid is (row blocks, column buckets); the x BlockSpec is column-tiled so
    a grid step only holds one ``block_cols`` slice of x in VMEM, and the
    output row block accumulates over the sequential second grid dim.
    VMEM residency is therefore independent of ``len(x)`` — this is the
    paper-scale-fine-level path.
    """
    R = cols.shape[0]
    bc = int(block_cols)
    assert x.shape[0] % bc == 0, (x.shape, bc)
    C = x.shape[0] // bc
    assert cols.shape[1] % C == 0, (cols.shape, C)
    K = cols.shape[1] // C
    cols, vals, br = _pad_rows(cols, vals, block_rows)
    Rp = cols.shape[0]
    return pl.pallas_call(
        _spmv_blocked_kernel,
        grid=(Rp // br, C),
        in_specs=[
            pl.BlockSpec((br, K), lambda i, j: (i, j)),
            pl.BlockSpec((br, K), lambda i, j: (i, j)),
            pl.BlockSpec((bc, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, 1), vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(cols, vals, x[:, None])[:R, 0]


def _pad_vec(y: jnp.ndarray, n: int) -> jnp.ndarray:
    if y.shape[0] == n:
        return y
    return jnp.concatenate([y, jnp.zeros((n - y.shape[0],), y.dtype)])


def _spmv_blocked_partial_kernel(cols_ref, vals_ref, x_ref, y0_ref, y_ref):
    j = pl.program_id(1)
    cols = cols_ref[...]          # [BR, K] in-bucket indices (< block_cols)
    vals = vals_ref[...]          # [BR, K]
    x = x_ref[...]                # [BC, 1] — this bucket's x slice
    partial = jnp.sum(vals * x[cols, 0], axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = y0_ref[...] + partial

    @pl.when(j > 0)
    def _accumulate():
        y_ref[...] = y_ref[...] + partial


def spmv_ell_blocked_partial(
    cols: jnp.ndarray,   # [R, C*K] full bucketed layout (all buckets)
    vals: jnp.ndarray,   # [R, C*K]
    x: jnp.ndarray,      # [(hi-lo) * block_cols] — ONLY the range's x slices
    y0: jnp.ndarray,     # [R] carried output, accumulated into
    *,
    bucket_lo: int,
    bucket_hi: int,
    n_buckets: int,
    block_cols: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
    """Blocked SpMV over buckets [bucket_lo, bucket_hi), accumulating into a
    carried ``y0``: y = y0 + sum_{j in [lo,hi)} A_bucket_j @ x_bucket_j.

    This is the overlap building block: the distributed schedule runs the
    local-bucket range while the halo exchange is in flight, then a second
    call consumes the ghost-bucket range with the local partial as ``y0``.
    ``cols``/``vals`` stay the full [R, C*K] layout (the BlockSpec index map
    offsets into it); ``x`` covers exactly the requested range.
    """
    R = cols.shape[0]
    lo, hi = int(bucket_lo), int(bucket_hi)
    C = int(n_buckets)
    bc = int(block_cols)
    if not (0 <= lo <= hi <= C):
        raise ValueError(f"bucket range [{lo}, {hi}) outside [0, {C})")
    if hi == lo:
        return y0
    assert x.shape[0] == (hi - lo) * bc, (x.shape, hi - lo, bc)
    assert cols.shape[1] % C == 0, (cols.shape, C)
    K = cols.shape[1] // C
    cols, vals, br = _pad_rows(cols, vals, block_rows)
    Rp = cols.shape[0]
    y0p = _pad_vec(y0, Rp)
    return pl.pallas_call(
        _spmv_blocked_partial_kernel,
        grid=(Rp // br, hi - lo),
        in_specs=[
            pl.BlockSpec((br, K), lambda i, j: (i, j + lo)),
            pl.BlockSpec((br, K), lambda i, j: (i, j + lo)),
            pl.BlockSpec((bc, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, 1), vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(cols, vals, x[:, None], y0p[:, None])[:R, 0]


def _spmv_blocked_skip_kernel(bl_ref, cnt_ref, cols_ref, vals_ref, x_ref,
                              y0_ref, y_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    cols = cols_ref[...]          # [BR, K] — bucket bl_ref[i, j]'s columns
    vals = vals_ref[...]          # [BR, K]
    x = x_ref[...]                # [BC, 1] — bucket bl_ref[i, j]'s x slice
    partial = jnp.sum(vals * x[cols, 0], axis=1, keepdims=True)
    # steps past the row block's live-bucket count revisit a padding entry
    # of the list; mask their contribution to exactly zero
    live = (j < cnt_ref[i]).astype(vals.dtype)
    contrib = live * partial

    @pl.when(j == 0)
    def _init():
        y_ref[...] = y0_ref[...] + contrib

    @pl.when(j > 0)
    def _accumulate():
        y_ref[...] = y_ref[...] + contrib


def spmv_ell_blocked_skip(
    cols: jnp.ndarray,           # [R, C*K] full bucketed layout
    vals: jnp.ndarray,           # [R, C*K]
    x: jnp.ndarray,              # [n_x_buckets * block_cols]
    bucket_lists: jnp.ndarray,   # [NRB, M] int32 absolute bucket ids
    bucket_counts: jnp.ndarray,  # [NRB] int32 live entries per row block
    *,
    n_buckets: int,
    block_cols: int,
    bucket_base: int = 0,
    y0: jnp.ndarray | None = None,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
    """Bucket-skipping blocked SpMV: grid step (i, j) visits bucket
    ``bucket_lists[i, j]`` of row block ``i`` (scalar-prefetched, so the
    BlockSpec index maps are data-dependent); steps j >= bucket_counts[i]
    are masked to zero contribution.  Banded operators whose row blocks
    touch few buckets stream only those, instead of every bucket.

    ``x`` covers buckets [bucket_base, bucket_base + len(x)/block_cols);
    every listed (and padding) bucket id must fall in that window.  With
    ``y0`` the result accumulates into a carried output, so the kernel
    serves both the fused path (base 0, full x) and either phase of the
    overlap schedule (local range, then ghost range carrying y).
    """
    R = cols.shape[0]
    C = int(n_buckets)
    bc = int(block_cols)
    base = int(bucket_base)
    assert cols.shape[1] % C == 0, (cols.shape, C)
    K = cols.shape[1] // C
    cols, vals, br = _pad_rows(cols, vals, block_rows)
    Rp = cols.shape[0]
    nrb = Rp // br
    assert bucket_lists.shape[0] == nrb, (bucket_lists.shape, nrb, br)
    M = bucket_lists.shape[1]
    y0p = (jnp.zeros((Rp,), vals.dtype) if y0 is None
           else _pad_vec(y0, Rp).astype(vals.dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nrb, M),
        in_specs=[
            pl.BlockSpec((br, K), lambda i, j, bl, cnt: (i, bl[i, j])),
            pl.BlockSpec((br, K), lambda i, j, bl, cnt: (i, bl[i, j])),
            pl.BlockSpec((bc, 1), lambda i, j, bl, cnt: (bl[i, j] - base, 0)),
            pl.BlockSpec((br, 1), lambda i, j, bl, cnt: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, 1), lambda i, j, bl, cnt: (i, 0)),
    )
    return pl.pallas_call(
        _spmv_blocked_skip_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Rp, 1), vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(bucket_lists.astype(jnp.int32), bucket_counts.astype(jnp.int32),
      cols, vals, x[:, None], y0p[:, None])[:R, 0]
