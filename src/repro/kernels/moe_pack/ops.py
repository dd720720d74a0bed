"""Public MoE pack/combine ops with backend dispatch + padding."""
from __future__ import annotations

import jax.numpy as jnp

from .. import impl
from .moe_pack import combine_rows, gather_rows
from .ref import combine_rows_ref, gather_rows_ref


def _pad_rows(x, mult):
    rem = (-x.shape[0]) % mult
    return jnp.pad(x, [(0, rem)] + [(0, 0)] * (x.ndim - 1)) if rem else x


def pack(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """out[i] = x[idx[i]]; idx may contain N-1 pointing at a pad row."""
    mode = impl("moe_pack")
    if mode == "xla":
        return gather_rows_ref(x, idx)
    M, D = idx.shape[0], x.shape[1]
    bm = 256
    while M % bm and bm > 8:
        bm //= 2
    bd = 512
    while D % bd and bd > 8:
        bd //= 2
    if M % bm:
        bm = M
    if D % bd:
        bd = D
    return gather_rows(
        x, idx, block_m=bm, block_d=bd,
        interpret=(mode == "pallas_interpret"),
    )


def combine(buf: jnp.ndarray, idx: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    mode = impl("moe_pack")
    if mode == "xla":
        return combine_rows_ref(buf, idx, w)
    T, D = idx.shape[0], buf.shape[1]
    bm = 256
    while T % bm and bm > 8:
        bm //= 2
    bd = 512
    while D % bd and bd > 8:
        bd //= 2
    if T % bm:
        bm = T
    if D % bd:
        bd = D
    return combine_rows(
        buf, idx, w, block_m=bm, block_d=bd,
        interpret=(mode == "pallas_interpret"),
    )
