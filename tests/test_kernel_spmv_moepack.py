"""ELL and diagonal SpMV + MoE pack/combine kernels vs oracles (+ AMG
matrices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.amg import diffusion_2d
from repro.kernels.moe_pack import combine_rows_ref, gather_rows_ref
from repro.kernels.moe_pack.moe_pack import combine_rows, gather_rows
from repro.kernels.spmv_ell import csr_to_ell, spmv_dia, spmv_ell_ref
from repro.kernels.spmv_ell.spmv_ell import spmv_ell


@pytest.mark.parametrize("R,K,N,br", [(64, 4, 32, 16), (128, 7, 100, 32),
                                      (256, 11, 257, 64)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_spmv_random(R, K, N, br, dtype):
    rng = np.random.default_rng(0)
    cols = rng.integers(0, N, size=(R, K)).astype(np.int32)
    vals = rng.normal(size=(R, K)).astype(dtype)
    x = rng.normal(size=N).astype(dtype)
    want = spmv_ell_ref(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(x))
    got = spmv_ell(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(x),
                   block_rows=br, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("R,br", [(257, 64), (101, 32), (7, 8)])
def test_spmv_prime_rows_padded(R, br):
    """Regression: row counts not divisible by block_rows used to assert;
    the kernel must pad the trailing block and slice the output."""
    rng = np.random.default_rng(4)
    K, N = 5, 90
    cols = rng.integers(0, N, size=(R, K)).astype(np.int32)
    vals = rng.normal(size=(R, K)).astype(np.float32)
    x = rng.normal(size=N).astype(np.float32)
    want = spmv_ell_ref(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(x))
    got = spmv_ell(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(x),
                   block_rows=br, interpret=True)
    assert got.shape == (R,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("R,K,N", [(64, 12, 48), (97, 15, 160),
                                   (128, 6, 64)])
def test_spmv_ell_ref_matches_dense(R, K, N):
    """The jnp ELL body against the dense product: padding entries point
    at the sentinel x slot ``N`` (holding 0.0) with nonzero values, some
    rows are all padding, and a column repeats within a row (its products
    add)."""
    rng = np.random.default_rng(R)
    cols = rng.integers(0, N, size=(R, K)).astype(np.int32)
    vals = rng.normal(size=(R, K)).astype(np.float32)
    lens = rng.integers(0, K + 1, size=R)
    lens[:3] = 0                                  # all-padding rows
    cols[:, K - 1] = np.where(lens == K, cols[:, 0], cols[:, K - 1])
    cols[np.arange(K)[None, :] >= lens[:, None]] = N
    assert np.any(lens == K)                      # a repeated column
    x = np.concatenate([rng.normal(size=N), [0.0]]).astype(np.float32)
    dense = np.zeros((R, N))
    for i in range(R):
        for k in range(lens[i]):
            dense[i, cols[i, k]] += vals[i, k]
    got = spmv_ell_ref(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), dense @ x[:N],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got)[:3], 0.0)


def _offset_set(kind: str, R: int, rng) -> tuple:
    if kind == "zero":
        return (0,)
    if kind == "one_sided":
        return tuple(sorted({0, *rng.integers(1, R + 1, size=3).tolist()}))
    if kind == "symmetric":
        o = rng.integers(1, R + 1, size=2).tolist()
        return tuple(sorted({0, *o, *(-v for v in o)}))
    # beyond the row count on both sides
    return tuple(sorted({-R - int(rng.integers(0, 3)), -1, 0,
                         R + int(rng.integers(0, 3))}))


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("R", [1, 7, 64, 257])
@pytest.mark.parametrize("kind", ["one_sided", "symmetric", "beyond",
                                  "zero"])
def test_spmv_dia_matches_dense(kind, R, dtype):
    """The diagonal SpMV against the dense product of the same diagonals
    (entries whose column falls outside x hold 0.0, as the layout stores
    them)."""
    rng = np.random.default_rng(R + len(kind))
    offsets = _offset_set(kind, R, rng)
    vals = rng.normal(size=(len(offsets), R))
    dense = np.zeros((R, R))
    for d, o in enumerate(offsets):
        i = np.arange(R)
        inside = (i + o >= 0) & (i + o < R)
        vals[d, ~inside] = 0.0
        dense[i[inside], i[inside] + o] = vals[d, inside]
    x = rng.normal(size=R)
    with jax.enable_x64(True):
        got = np.asarray(spmv_dia(offsets, jnp.asarray(vals.astype(dtype)),
                                  jnp.asarray(x.astype(dtype))))
    assert got.dtype == dtype
    want = dense.astype(dtype).astype(np.float64) @ \
        x.astype(dtype).astype(np.float64)
    tol = 1e-5 if dtype == np.float32 else 1e-13
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_spmv_amg_matrix():
    """End-to-end on a real AMG matrix via csr_to_ell."""
    A = diffusion_2d(16, 16)
    rng = np.random.default_rng(1)
    x = rng.normal(size=A.ncols).astype(np.float32)
    # pad slot: one extra zero entry at index A.ncols
    cols, vals = csr_to_ell(A.indptr, A.indices, A.data, A.nrows,
                            pad_col=A.ncols, block_rows=64)
    xp = np.concatenate([x, [0.0]]).astype(np.float32)
    got = spmv_ell(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(xp),
                   block_rows=64, interpret=True)
    want = A.matvec(x.astype(np.float64))
    np.testing.assert_allclose(np.asarray(got)[: A.nrows], want,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("N,D,M,bm,bd", [(32, 16, 64, 16, 16),
                                         (100, 64, 128, 32, 32),
                                         (57, 128, 96, 48, 64)])
def test_gather_rows(N, D, M, bm, bd):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, N, size=M).astype(np.int32))
    want = gather_rows_ref(x, idx)
    got = gather_rows(x, idx, block_m=bm, block_d=bd, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("K", [1, 2, 6])
def test_combine_rows(K):
    rng = np.random.default_rng(3)
    N, D, T = 64, 32, 48
    buf = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, N, size=(T, K)).astype(np.int32))
    w = jnp.asarray(rng.random((T, K)).astype(np.float32))
    want = combine_rows_ref(buf, idx, w)
    got = combine_rows(buf, idx, w, block_m=16, block_d=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
