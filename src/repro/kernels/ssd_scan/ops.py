"""Public SSD op: backend dispatch, batching, group broadcast, padding."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import impl
from .ref import ssd_chunked_ref, ssd_ref
from .ssd_scan import DEFAULT_CHUNK, ssd_scan_h


def _pad_time(x, axis, mult):
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def ssd(
    x: jnp.ndarray,    # [Bt, T, H, P]
    dt: jnp.ndarray,   # [Bt, T, H]   (post-softplus)
    A: jnp.ndarray,    # [H]          (negative)
    B: jnp.ndarray,    # [Bt, T, G, N]
    C: jnp.ndarray,    # [Bt, T, G, N]
    *,
    chunk: int = DEFAULT_CHUNK,
) -> jnp.ndarray:
    """Batched SSD with B/C groups broadcast over heads (H % G == 0)."""
    Bt, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2)  # [Bt, T, H, N]
    Ch = jnp.repeat(C, rep, axis=2)

    # to per-head layout [H, T, *]
    xh = jnp.moveaxis(x, 2, 1)       # [Bt, H, T, P]
    dth = jnp.moveaxis(dt, 2, 1)     # [Bt, H, T]
    Bhh = jnp.moveaxis(Bh, 2, 1)
    Chh = jnp.moveaxis(Ch, 2, 1)

    mode = impl("ssd_scan")
    if mode == "xla":
        fn = lambda xx, dd, bb, cc: ssd_chunked_ref(
            xx, dd, A, bb, cc, chunk=min(chunk, max(8, xx.shape[1]))
        ) if xx.shape[1] % min(chunk, max(8, xx.shape[1])) == 0 else ssd_ref(
            xx, dd, A, bb, cc
        )
        y = jax.vmap(fn)(xh, dth, Bhh, Chh)
    else:
        ck = min(chunk, T) if T % min(chunk, T) == 0 else chunk
        Tp = T + ((-T) % ck)
        xh2 = _pad_time(xh, 2, ck)
        dth2 = _pad_time(dth, 2, ck)
        Bh2 = _pad_time(Bhh, 2, ck)
        Ch2 = _pad_time(Chh, 2, ck)
        y = jax.vmap(
            lambda xx, dd, bb, cc: ssd_scan_h(
                xx, dd, A, bb, cc, chunk=ck,
                interpret=(mode == "pallas_interpret"),
            )
        )(xh2, dth2, Bh2, Ch2)[:, :, :T]
    return jnp.moveaxis(y, 1, 2)     # [Bt, T, H, P]


def ssd_decode_step(
    S: jnp.ndarray,    # [Bt, H, N, P] running state
    x: jnp.ndarray,    # [Bt, H, P]
    dt: jnp.ndarray,   # [Bt, H]
    A: jnp.ndarray,    # [H]
    B: jnp.ndarray,    # [Bt, G, N]
    C: jnp.ndarray,    # [Bt, G, N]
):
    """Single-token recurrence for serving (O(1) per token — the reason SSMs
    run the long_500k shape). Returns (S_new, y)."""
    G = B.shape[1]
    H = x.shape[1]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=1)  # [Bt, H, N]
    Ch = jnp.repeat(C, rep, axis=1)
    a = jnp.exp(dt * A[None, :])[..., None, None]        # [Bt,H,1,1]
    S_new = a * S + (dt[..., None] * Bh)[..., None] * x[:, :, None, :]
    y = jnp.einsum("bhn,bhnp->bhp", Ch, S_new)
    return S_new, y.astype(x.dtype)
