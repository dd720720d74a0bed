"""Pure-jnp bodies for ELL SpMV and the diagonal SpMV of a banded block."""
from __future__ import annotations

import jax.numpy as jnp


def spmv_ell_ref(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray):
    """cols/vals: [R, K]; x: [N] -> y [R].

    The gather and the sum run over the transposed ``[K, R]`` view: XLA's
    TPU backend then keeps rows on the minor axis.  Gathering ``[R, K]``
    directly pads K (7 for the fine AMG level) to the 128-lane tile, which
    costs 9x the temporary memory at 2^20 rows and 10x the compile time at
    2^16 rows.
    """
    return jnp.sum(vals.T * x[cols.T], axis=0)


def spmv_dia(offsets: tuple, vals: jnp.ndarray, x: jnp.ndarray):
    """Banded block stored by diagonals: ``vals [D, R]``, ``x [N]`` -> y [R]
    with ``y[i] = sum_d vals[d, i] * x[i + offsets[d]]``.

    ``offsets`` is a static ascending tuple of column-minus-row offsets;
    entries whose column falls outside ``x`` hold 0.0.  With ``m`` the
    largest offset magnitude, x is zero-padded by ``m`` on each side and
    each diagonal multiplies one static shifted slice of it, summed in
    ascending-offset (CSR column) order: no gather, so XLA fuses the
    slices, products and adds into one streaming loop.
    """
    R = vals.shape[1]
    m = max(abs(o) for o in offsets)
    xp = jnp.concatenate([
        jnp.zeros((m,), x.dtype), x,
        jnp.zeros((m + max(R - x.shape[0], 0),), x.dtype),
    ])
    y = vals[0] * xp[m + offsets[0]: m + offsets[0] + R]
    for d in range(1, len(offsets)):
        o = m + offsets[d]
        y = y + vals[d] * xp[o: o + R]
    return y
