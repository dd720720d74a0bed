"""MoE dispatch pack / combine as Pallas TPU kernels.

The paper's aggregation steps (s: pack values for each destination region;
r: fan received values out to final consumers) are, on device, row
gather/scatter over token buffers — the compute hot spot of the
locality-aware MoE dispatch.  Both directions are expressed as *gathers*
(never scatter-add) so blocks race-free parallelize over the grid:

pack:     out[i]    = x[idx[i]]                     (build per-expert buffers)
combine:  out[t]    = sum_k w[t, k] * buf[idx[t, k]] (weighted un-pack, top-K)

Feature dim is tiled (BD) so arbitrarily wide hidden states stream through
VMEM; the row table (x / buf) is resident per feature tile.  For token
counts whose row table exceeds VMEM the production variant swaps the
BlockSpec of ``x`` to HBM (pltpu.ANY) + double-buffered ``make_async_copy``
row DMA; the AMG/LM shapes in this repo fit the resident form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_D = 512


def _pack_kernel(idx_ref, x_ref, o_ref):
    idx = idx_ref[...]            # [BM, 1] int32
    x = x_ref[...]                # [N, BD]
    o_ref[...] = x[idx[:, 0]]     # [BM, BD]


def gather_rows(
    x: jnp.ndarray,      # [N, D]  (append a zero row for pad indices = N-1)
    idx: jnp.ndarray,    # [M] int32
    *,
    block_m: int = DEFAULT_BLOCK_M,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
) -> jnp.ndarray:
    N, D = x.shape
    M = idx.shape[0]
    bm = min(block_m, M)
    bd = min(block_d, D)
    assert M % bm == 0 and D % bd == 0, (M, bm, D, bd)
    return pl.pallas_call(
        _pack_kernel,
        grid=(M // bm, D // bd),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((N, bd), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(idx[:, None].astype(jnp.int32), x)


def _combine_kernel(idx_ref, w_ref, buf_ref, o_ref, *, top_k: int):
    idx = idx_ref[...]            # [BM, K]
    w = w_ref[...]                # [BM, K]
    buf = buf_ref[...]            # [N, BD]
    acc = jnp.zeros((idx.shape[0], buf.shape[1]), jnp.float32)
    for k in range(top_k):        # K is small & static: unrolled
        rows = buf[idx[:, k]]     # [BM, BD]
        acc = acc + w[:, k:k + 1].astype(jnp.float32) * rows.astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def combine_rows(
    buf: jnp.ndarray,    # [N, D] expert outputs (+ zero pad row at N-1)
    idx: jnp.ndarray,    # [T, K] positions in buf
    w: jnp.ndarray,      # [T, K] combine weights
    *,
    block_m: int = DEFAULT_BLOCK_M,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
) -> jnp.ndarray:
    N, D = buf.shape
    T, K = idx.shape
    bm = min(block_m, T)
    bd = min(block_d, D)
    assert T % bm == 0 and D % bd == 0, (T, bm, D, bd)
    kernel = functools.partial(_combine_kernel, top_k=K)
    return pl.pallas_call(
        kernel,
        grid=(T // bm, D // bd),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((N, bd), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((T, D), buf.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(idx.astype(jnp.int32), w, buf)
