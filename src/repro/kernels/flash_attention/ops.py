"""Public attention op: backend dispatch + GQA flattening + padding."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import impl
from .flash_attention import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention_bh
from .ref import attention_ref


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def attention(
    q: jnp.ndarray,   # [B, Hq, Tq, d]
    k: jnp.ndarray,   # [B, Hkv, Tk, d]
    v: jnp.ndarray,
    *,
    scale: float | None = None,
    causal: bool = True,
    window: int = 0,
    kv_len: int | None = None,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
) -> jnp.ndarray:
    """GQA attention; dispatches to the Pallas kernel or the jnp oracle."""
    mode = impl("flash_attention")
    if mode == "xla":
        return attention_ref(
            q, k, v, scale=scale, causal=causal, window=window,
            kv_len=kv_len, q_offset=q_offset,
        )
    B, Hq, Tq, d = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    scale = (d ** -0.5) if scale is None else scale
    kv_len = Tk if kv_len is None else kv_len
    group = Hq // Hkv

    bq = min(block_q, max(8, Tq))
    bk = min(block_k, max(8, Tk))
    qp = _pad_to(q, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    # broadcast kv heads across the query-head groups, flatten (B, Hq)
    kp = jnp.repeat(kp, group, axis=1)
    vp = jnp.repeat(vp, group, axis=1)
    qf = qp.reshape(B * Hq, qp.shape[2], d)
    kf = kp.reshape(B * Hq, kp.shape[2], d)
    vf = vp.reshape(B * Hq, vp.shape[2], d)
    out = flash_attention_bh(
        qf, kf, vf,
        scale=scale, causal=causal, window=window, kv_len=kv_len,
        q_offset=q_offset, block_q=bq, block_k=bk,
        interpret=(mode == "pallas_interpret"),
    )
    return out.reshape(B, Hq, qp.shape[2], d)[:, :, :Tq]
