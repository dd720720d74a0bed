"""Local SpMV in ELL (padded-CSR) form as a Pallas TPU kernel.

This is the per-device compute of the paper's workload: after the halo
exchange delivers ghost values, each device multiplies its local sparse
block.  CSR's ragged rows are hostile to the VPU's lane layout, so rows are
padded to a uniform K nonzeros (ELL): ``cols``/``vals`` are [R, K] with
padding entries pointing at a zero slot.

:func:`spmv_ell` holds the whole x vector in VMEM, tiles the rows over a
1-D grid, and forms each row's sum as a VMEM dynamic gather + multiply +
row reduction.  Mosaic refuses that gather, so on a TPU the op runs its jnp
body (``ref.spmv_ell_ref``, see ``kernels.IMPLS``); this kernel runs in
interpret mode, as the oracle's partner in the kernel tests.

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
