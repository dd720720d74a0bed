"""Blocked online-softmax attention (FlashAttention) as a Pallas TPU kernel.

Supports GQA (kv heads broadcast over query-head groups), causal masking,
and sliding-window attention (Mixtral/Gemma-3 local layers).

Tiling: grid = (batch*q_heads, num_q_blocks, num_kv_blocks); the KV-block
dimension is innermost and marked "arbitrary" so the (m, l, acc) online
softmax state lives in VMEM scratch across KV steps.  Q/K/V tiles are
MXU-aligned: block_q x head_dim and block_k x head_dim with head_dim padded
to a multiple of 128 by ops.py.  VMEM working set per step:
(block_q + 2*block_k) * d * 4B + acc (block_q * d * 4B) — ~0.4 MB at the
default 128/128/128 tiling, far under the ~16 MB VMEM budget, leaving room
for double-buffered pipelining of the K/V streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(
    lens_ref,                 # SMEM [2] int32: kv_len, q_offset
    q_ref, k_ref, v_ref,      # [1, bq, d], [1, bk, d], [1, bk, d]
    o_ref,                    # [1, bq, d]
    m_scr, l_scr, acc_scr,    # VMEM scratch: [bq, 1], [bq, 1], [bq, d]
    *,
    scale: float,
    causal: bool,
    window: int,
    block_q: int,
    block_k: int,
    num_kv_blocks: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    kv_len = lens_ref[0]
    q_offset = lens_ref[1]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # [bq, d]
    k = k_ref[0].astype(jnp.float32)  # [bk, d]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bk]

    # absolute positions (q_offset supports decode: query at position cache_len)
    q_pos = (
        q_offset
        + qi * block_q
        + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = k_pos < kv_len  # padding mask
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                      # [bq, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                   # [bq, bk]
    correction = jnp.exp(m_prev - m_new)     # [bq, 1]
    l_scr[...] = correction * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = correction * acc_scr[...] + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)


def flash_attention_bh(
    q: jnp.ndarray,   # [BH, Tq, d]
    k: jnp.ndarray,   # [BH, Tk, d]
    v: jnp.ndarray,   # [BH, Tk, d]
    *,
    scale: float,
    causal: bool,
    window: int = 0,
    kv_len=None,
    q_offset=0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    """Attention over flattened (batch*heads) with pre-padded shapes
    (ops.py guarantees Tq % block_q == 0, Tk % block_k == 0).

    ``kv_len`` and ``q_offset`` may be traced (a decode step's cache
    length): they reach the kernel as scalar-prefetch operands, not as
    constants of its body."""
    BH, Tq, d = q.shape
    Tk = k.shape[1]
    kv_len = Tk if kv_len is None else kv_len
    nq = Tq // block_q
    nk = Tk // block_k
    lens = jnp.stack([jnp.asarray(kv_len, jnp.int32),
                      jnp.asarray(q_offset, jnp.int32)])

    kernel = functools.partial(
        _attn_kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        num_kv_blocks=nk,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, lens: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, lens: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, lens: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda b, i, j, lens: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Tq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lens, q, k, v)
