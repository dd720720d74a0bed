"""Mamba-2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

Per head: state S_t = exp(dt_t A) S_{t-1} + dt_t B_t (x) x_t;  y_t = C_t S_t.
The chunked (block-parallel) form computes, per chunk of length L:
  intra-chunk:  y[t] += sum_{s<=t} (C_t.B_s) exp(l_t - l_s) dt_s x_s
                (one [L,L] masked matmul feeding the MXU)
  inter-chunk:  y[t] += exp(l_t) C_t S_prev
  state update: S = exp(l_L) S_prev + sum_s exp(l_L - l_s) dt_s B_s (x) x_s

Grid = (heads, num_chunks) with the chunk dimension "arbitrary" (sequential)
so the running state lives in a VMEM scratch accumulator across chunk steps —
the TPU-native equivalent of Mamba-2's inter-chunk recurrence.  VMEM per
step: x/y [L,P] + B/C [L,N] + [L,L] intra matrix + state [N,P]; at the
default L=128, P=64, N=128 that is ~0.35 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_CHUNK = 128


def _ssd_kernel(
    x_ref,    # [1, L, P]
    dt_ref,   # [1, L]
    a_ref,    # [1, 1]   (A scalar for this head)
    b_ref,    # [1, L, N]
    c_ref,    # [1, L, N]
    y_ref,    # [1, L, P]
    state_scr,  # VMEM [N, P] float32
    *,
    chunk: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)     # [L, P]
    dt = dt_ref[0].astype(jnp.float32)   # [L]
    A = a_ref[0, 0].astype(jnp.float32)  # scalar
    B = b_ref[0].astype(jnp.float32)     # [L, N]
    C = c_ref[0].astype(jnp.float32)     # [L, N]

    log_a = dt * A                        # [L]  (A < 0)
    l_cum = jnp.cumsum(log_a)             # inclusive cumulative log decay
    l_tot = l_cum[-1]

    # intra-chunk: M[t,s] = (C_t . B_s) * exp(l_t - l_s) * dt_s, s <= t
    cb = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [L, L]
    li = l_cum[:, None]
    ls = l_cum[None, :]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(s_idx <= t_idx, jnp.exp(li - ls), 0.0)
    M = cb * decay * dt[None, :]
    y = jax.lax.dot(M, x, preferred_element_type=jnp.float32)  # [L, P]

    # inter-chunk: y[t] += exp(l_t) * C_t @ S_prev
    S_prev = state_scr[...]               # [N, P]
    y = y + jnp.exp(l_cum)[:, None] * jax.lax.dot(
        C, S_prev, preferred_element_type=jnp.float32
    )

    # state update: S = exp(l_tot) S_prev + sum_s exp(l_tot - l_s) dt_s B_s x_s
    w = jnp.exp(l_tot - l_cum) * dt       # [L]
    S_new = jnp.exp(l_tot) * S_prev + jax.lax.dot_general(
        B * w[:, None], x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [N, P]
    state_scr[...] = S_new
    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan_h(
    x: jnp.ndarray,    # [H, T, P]
    dt: jnp.ndarray,   # [H, T]
    A: jnp.ndarray,    # [H]
    B: jnp.ndarray,    # [H, T, N]
    C: jnp.ndarray,    # [H, T, N]
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-head SSD scan; T must be a multiple of ``chunk`` (ops.py pads)."""
    H, T, P = x.shape
    N = B.shape[-1]
    nc = T // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk), lambda h, c: (h, c)),
            pl.BlockSpec((1, 1), lambda h, c: (h, 0)),
            pl.BlockSpec((1, chunk, N), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda h, c: (h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda h, c: (h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((H, T, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, A[:, None], B, C)
