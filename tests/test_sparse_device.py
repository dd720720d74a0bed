"""Unit tests: rectangular partitioning + padded-ELL device conversion.

These run on the main (single-device) pytest process: ELL correctness is
checked against the CSR blocks with plain numpy gathers; the shard_map
device path is exercised end-to-end in test_distributed_amg.py.
"""
import numpy as np
import pytest

from repro.amg import build_hierarchy, diffusion_2d
from repro.core import Topology, build_plan
from repro.sparse import (
    block_offsets,
    distributed_spmv_numpy,
    overlap_decision,
    pack_vector,
    partition_csr,
    partition_rect_csr,
    partitioned_to_device,
    partitioned_to_ell,
    select_spmv_overlap,
    unpack_vector,
)
from repro.sparse.csr import CSR


def _ell_matvec(cols, vals, x_ext):
    """Reference ELL matvec: cols/vals [R, K], x_ext padded with sentinel."""
    return np.sum(vals * x_ext[cols], axis=1)


def _ghost_matvec(ell, p, g_ext):
    """Process ``p``'s ghost-block product on all ``row_pad`` rows: a
    compact block's rows scattered to ``ghost_rows``."""
    yg = _ell_matvec(ell.ghost_cols[p], ell.ghost_vals[p], g_ext)
    if ell.ghost_rows is None:
        return yg
    y = np.zeros(ell.row_pad)
    y[ell.ghost_rows[p]] = yg
    return y


def test_rect_partition_matches_serial_on_restriction():
    A = diffusion_2d(24, 18)
    h = build_hierarchy(A)
    R = h.levels[0].R
    assert R is not None and R.nrows < R.ncols
    n_procs = 6
    part = partition_rect_csr(
        R, block_offsets(R.nrows, n_procs), block_offsets(R.ncols, n_procs)
    )
    topo = Topology(n_procs, 3)
    rng = np.random.default_rng(0)
    x = rng.normal(size=R.ncols)
    for strategy in ("standard", "partial", "full"):
        plan = build_plan(part.pattern, topo, strategy)
        got = distributed_spmv_numpy(part, plan, x)
        np.testing.assert_allclose(got, R.matvec(x), rtol=1e-12, atol=1e-12)


def test_partitioned_to_ell_reproduces_blocks():
    A = diffusion_2d(16, 20)
    n_procs = 8
    part = partition_csr(A, n_procs)
    ell = partitioned_to_ell(part)
    assert ell.row_pad == int(np.diff(part.offsets).max())
    rng = np.random.default_rng(1)
    x = rng.normal(size=A.nrows)
    plan = build_plan(part.pattern, Topology(n_procs, 4), "standard")
    xs = [x[int(part.offsets[p]): int(part.offsets[p + 1])]
          for p in range(n_procs)]
    ghosts = plan.execute_numpy(xs)
    for p in range(n_procs):
        # local block: sentinel slot at index in_pad
        x_ext = np.zeros(ell.in_pad + 1)
        x_ext[: len(xs[p])] = xs[p]
        y = _ell_matvec(ell.local_cols[p], ell.local_vals[p], x_ext)
        g_ext = np.zeros(ell.ghost_pad + 1)
        g_ext[: len(ghosts[p])] = ghosts[p]
        y = y + _ghost_matvec(ell, p, g_ext)
        want = part.local[p].matvec(xs[p])
        if part.ghost[p].ncols:
            want = want + part.ghost[p].matvec(ghosts[p])
        n_rows = int(part.offsets[p + 1] - part.offsets[p])
        np.testing.assert_allclose(y[:n_rows], want, rtol=1e-12, atol=1e-12)
        # padded rows are exactly zero (they feed the next level's layout)
        np.testing.assert_array_equal(y[n_rows:], 0.0)


def test_pack_unpack_vector_roundtrip():
    off = block_offsets(37, 5)
    pad = int(np.diff(off).max())
    rng = np.random.default_rng(2)
    x = rng.normal(size=37)
    packed = pack_vector(off, pad, x)
    assert packed.shape == (5, pad)
    np.testing.assert_array_equal(unpack_vector(off, packed), x)


def _boundary_matrix(K: int, D: int, ncols: int = 16) -> CSR:
    """16 x ``ncols``: row 0 holds K entries at offsets 0..K-1, rows
    1..D-K one each in column 0 (offsets -1..-(D-K)): ELL width K, D
    offsets."""
    rows = [0] * K + list(range(1, D - K + 1))
    cols = list(range(K)) + [0] * (D - K)
    return CSR.from_coo(np.array(rows), np.array(cols),
                        np.arange(1.0, len(rows) + 1), (16, ncols))


@pytest.mark.parametrize("case", ["equal", "one_more", "rectangular"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_local_layout_rule_at_its_boundary(dtype, case):
    """Diagonals iff the partition is square and ``D * value_bytes <= K *
    (value_bytes + 4)``: at equality (K = 2, D = 3 in f64; K = 1, D = 2 in
    f32) diagonal, with one offset more ELL, and the same entries over one
    column more (a rectangular partition) ELL."""
    vb = np.dtype(dtype).itemsize
    K = {8: 2, 4: 1}[vb]
    D = K * (vb + 4) // vb
    assert D * vb == K * (vb + 4)
    ncols = 17 if case == "rectangular" else 16
    A = _boundary_matrix(K, D + (case == "one_more"), ncols)
    part = partition_rect_csr(A, np.array([0, 16]), np.array([0, ncols]))
    ell = partitioned_to_device(part, dtype)
    assert ell.local_vals.dtype == dtype
    if case == "equal":
        assert ell.offsets == tuple(range(-(D - K), K))
        assert ell.local_cols is None
    else:
        assert ell.offsets is None
        assert ell.local_cols.shape == (1, 16, K)


def test_ell_padding_points_at_sentinel():
    """Every structural padding entry must be (sentinel col, 0.0 val)."""
    A = diffusion_2d(10, 14)
    part = partition_csr(A, 4)
    ell = partitioned_to_ell(part)
    for p in range(4):
        m = part.local[p]
        lens = np.diff(m.indptr)
        lc, lv = ell.local_cols[p], ell.local_vals[p]
        for i in range(ell.row_pad):
            k = int(lens[i]) if i < m.nrows else 0
            np.testing.assert_array_equal(lc[i, k:], ell.in_pad)
            np.testing.assert_array_equal(lv[i, k:], 0.0)
            # live entries point strictly inside the owned block
            assert np.all(lc[i, :k] < ell.in_pad)


def test_overlap_decision_modes():
    """auto flips exactly when the hidden time beats the split overhead;
    forced modes are honored (except on, without ghosts to hide)."""
    from repro.core.costmodel import overlap_split_overhead

    rows = 2 ** 21
    overhead = overlap_split_overhead(rows)
    # paper-scale regime: tx and tl both dwarf the overhead -> on
    on = overlap_decision(100e-6, 300e-6, rows=rows)
    assert on.mode == "on" and not on.forced
    assert on.exposed_s == 0.0 and on.hidden_frac == 1.0
    assert on.overhead_s == overhead
    # smoke regime: local compute below the overhead -> off, fully exposed
    off = overlap_decision(100e-6, overhead / 10, rows=rows)
    assert off.mode == "off" and off.exposed_s == 100e-6
    assert off.hidden_frac == 0.0
    # partial hiding: tl < tx but still worth it
    part = overlap_decision(100e-6, 60e-6, rows=1000)
    assert part.mode == "on"
    np.testing.assert_allclose(part.exposed_s, 40e-6)
    np.testing.assert_allclose(part.hidden_frac, 0.6)
    # forced modes
    fon = overlap_decision(1e-9, 1e-12, rows=rows, mode="on")
    assert fon.mode == "on" and fon.forced
    foff = overlap_decision(1.0, 1.0, rows=rows, mode="off")
    assert foff.mode == "off" and foff.forced
    # no ghosts: nothing to hide, even when forced on
    none = overlap_decision(0.0, 1.0, rows=rows, mode="on", has_ghost=False)
    assert none.mode == "off" and none.exposed_s == 0.0
    with pytest.raises(ValueError):
        overlap_decision(1.0, 1.0, rows=rows, mode="banana")


def test_select_spmv_overlap_on_partition():
    """The operator-level selector: off at smoke scale (local compute is
    sub-overhead), on when the exchange estimate justifies the split; the
    selection string is describe()-ready."""
    A = diffusion_2d(24, 24)
    part = partition_csr(A, 4)
    off = select_spmv_overlap(part, 1e-3)
    assert off.mode == "off" and not off.forced
    assert off.exchange_s == 1e-3 and off.exposed_s == 1e-3
    forced = select_spmv_overlap(part, 1e-3, mode="on")
    assert forced.mode == "on" and forced.forced
    assert "overlap=on (forced)" in str(forced)
    assert "tx=1000.0us" in str(forced)
    # single process: no ghosts, auto and forced both stay off
    solo = select_spmv_overlap(partition_csr(A, 1), 1e-3, mode="on")
    assert solo.mode == "off"


# ------------------------------------------------ diagonal local layout
OPERATORS = {
    "rotated7": lambda: diffusion_2d(24, 24),
    "poisson5": lambda: diffusion_2d(24, 24, theta=0.0, eps=1.0),
}
SPLITS = {
    "one": [0, 576],
    "four_even": list(block_offsets(576, 4)),
    "four_uneven": [0, 100, 250, 400, 576],   # padded rows on 3 of 4
}


@pytest.mark.parametrize("overlap", [False, True])
@pytest.mark.parametrize("split", sorted(SPLITS))
@pytest.mark.parametrize("op", sorted(OPERATORS))
def test_diagonal_layout_matches_ell_gather(op, split, overlap):
    """The shifted-slice product of a stencil's diagonal local block equals
    the ELL gather's to 1e-14 relative in f64, in the fused and the
    overlapped schedule; padded rows stay exactly zero."""
    import jax

    from repro.core import PlanCache, Topology
    from repro.sparse import make_distributed_spmv
    from repro.verify import verify_device_ell

    A = OPERATORS[op]()
    off = np.asarray(SPLITS[split])
    P = len(off) - 1
    part = partition_rect_csr(A, off, off)
    dia = partitioned_to_device(part)
    assert len(dia.offsets) == (7 if op == "rotated7" else 5)
    verify_device_ell(dia, part)
    ell = partitioned_to_ell(part)
    mesh = jax.make_mesh((P,), ("proc",), devices=jax.devices()[:P])
    coll = PlanCache().collective(part.pattern, Topology(P, min(P, 2)),
                                  "standard")
    exchange = coll.bind(mesh, "proc") if ell.ghost_pad else None
    x = np.random.default_rng(4).normal(size=A.nrows)
    with jax.enable_x64(True):
        xg = pack_vector(off, ell.in_pad, x)
        y_dia, y_ell = (
            np.asarray(jax.jit(make_distributed_spmv(
                form, mesh, "proc", exchange, overlap=overlap))(xg))
            for form in (dia, ell))
    assert y_dia.dtype == np.float64
    scale = np.abs(y_ell).max()
    np.testing.assert_allclose(y_dia, y_ell, rtol=0, atol=1e-14 * scale)
    np.testing.assert_allclose(unpack_vector(off, y_dia), A.matvec(x),
                               rtol=0, atol=1e-14 * scale)
    for p in range(P):
        np.testing.assert_array_equal(y_dia[p, off[p + 1] - off[p]:], 0.0)


@pytest.mark.parametrize("n_procs", [1, 4])
def test_diagonal_selection_on_rotated_hierarchy(n_procs):
    """Only the stencil's fine A is banded enough for diagonals: every
    coarse A, every R and P keep the ELL."""
    h = build_hierarchy(diffusion_2d(24, 24))
    for k, lv in enumerate(h.levels):
        ops = [("A", lv.A, k, k)]
        if lv.P is not None:
            ops += [("R", lv.R, k + 1, k), ("P", lv.P, k, k + 1)]
        for name, M, kr, kc in ops:
            rows = h.levels[kr].A.nrows
            cols = h.levels[kc].A.nrows
            part = partition_rect_csr(M, block_offsets(rows, n_procs),
                                      block_offsets(cols, n_procs))
            ell = partitioned_to_device(part)
            diagonal = (k, name) == (0, "A")
            assert (ell.offsets is not None) == diagonal, (k, name)
            assert (ell.local_cols is None) == diagonal, (k, name)


def test_diagonal_offsets_of_rectangular_partition_is_none():
    from repro.sparse import diagonal_offsets

    h = build_hierarchy(diffusion_2d(16, 16))
    R = h.levels[0].R
    part = partition_rect_csr(R, block_offsets(R.nrows, 2),
                              block_offsets(R.ncols, 2))
    assert diagonal_offsets(part) is None
    sq = partition_csr(diffusion_2d(16, 16), 2)
    assert diagonal_offsets(sq) == (-17, -16, -1, 0, 1, 16, 17)


# ------------------------------------------------ compact ghost block
def _fractions(n: int, fr) -> np.ndarray:
    return np.round(np.asarray(fr) * n).astype(np.int64)


def _compact_case(op: str, split: str):
    """(operator, row offsets, column offsets) of a compact-ghost case."""
    from repro.amg import laplacian_27pt

    fr = {"even": [0, .25, .5, .75, 1], "uneven": [0, .17, .43, .7, 1]}[split]
    if op == "rotated7":
        M = diffusion_2d(24, 24)
    elif op == "lap27":
        M = laplacian_27pt(8, (2, 2, 1))
    else:
        lv = build_hierarchy(diffusion_2d(32, 32)).levels[0]
        M = lv.R if op == "R" else lv.P
    return M, _fractions(M.nrows, fr), _fractions(M.ncols, fr)


def _row_aligned(ell, part):
    """The same operator with its ghost block row-aligned, as it is stored
    where most rows hold a ghost."""
    import dataclasses

    from repro.sparse.device import _stack_ell

    gc, gv = _stack_ell(part.ghost, ell.row_pad, ell.ghost_pad,
                        ell.ghost_vals.dtype)
    return dataclasses.replace(ell, ghost_cols=gc, ghost_vals=gv,
                               ghost_rows=None)


@pytest.mark.parametrize("overlap", [False, True])
@pytest.mark.parametrize("split", ["even", "uneven"])
@pytest.mark.parametrize("op", ["rotated7", "lap27", "R", "P"])
def test_compact_ghost_block_matches_row_aligned(op, split, overlap):
    """A compact ghost block (only the rows that hold a ghost, added back
    at ``ghost_rows``) gives the row-aligned block's product bit for bit,
    in the fused and the overlapped schedule, on 4 even and 4 uneven
    blocks; the product matches the host CSR product to 1e-14 relative in
    f64, and padded rows are exactly zero.

    A block one slot wide is the exception: there XLA's CPU backend fuses
    the row-aligned product's multiply into its add to ``y`` (one FMA),
    which the compact form's scatter keeps apart, so the two agree to the
    rounding of one product: one unit in the last place of the largest
    entry."""
    import jax

    from repro.core import PlanCache, Topology
    from repro.sparse import make_distributed_spmv
    from repro.verify import verify_device_ell

    M, roff, coff = _compact_case(op, split)
    part = partition_rect_csr(M, roff, coff)
    ell = partitioned_to_device(part)
    assert ell.ghost_layout == "compact"
    Rg = ell.ghost_rows.shape[1]
    assert ell.ghost_cols.shape[:2] == (4, Rg) and 2 * Rg <= ell.row_pad
    verify_device_ell(ell, part)
    rows = _row_aligned(ell, part)
    assert rows.ghost_layout == "rows"
    verify_device_ell(rows, part)
    mesh = jax.make_mesh((4,), ("proc",), devices=jax.devices()[:4])
    exchange = PlanCache().collective(
        part.pattern, Topology(4, 2), "standard").bind(mesh, "proc")
    x = np.random.default_rng(6).normal(size=M.ncols)
    with jax.enable_x64(True):
        xg = pack_vector(coff, ell.in_pad, x)
        y, y_rows = (
            np.asarray(jax.jit(make_distributed_spmv(
                form, mesh, "proc", exchange, overlap=overlap))(xg))
            for form in (ell, rows))
    assert y.dtype == np.float64
    if ell.ghost_cols.shape[2] > 1:
        np.testing.assert_array_equal(y, y_rows)
    else:
        np.testing.assert_allclose(
            y, y_rows, rtol=0,
            atol=np.finfo(np.float64).eps * np.abs(y_rows).max())
    want = M.matvec(x)
    np.testing.assert_allclose(unpack_vector(roff, y), want, rtol=0,
                               atol=1e-14 * np.abs(want).max())
    for p in range(4):
        np.testing.assert_array_equal(y[p, roff[p + 1] - roff[p]:], 0.0)


def _tridiagonal(n: int):
    from repro.sparse.csr import CSR

    i = np.arange(n)
    rows = np.concatenate([i, i[1:], i[:-1]])
    cols = np.concatenate([i, i[1:] - 1, i[:-1] + 1])
    vals = np.concatenate([np.full(n, 2.0), -np.ones(2 * (n - 1))])
    return CSR.from_coo(rows, cols, vals, (n, n))


@pytest.mark.parametrize("rows_a_block,want", [(4, "compact"), (3, "rows")])
def test_ghost_block_compacts_where_at_most_half_the_rows_hold_one(
        rows_a_block, want):
    """A tridiagonal operator's inner blocks hold a ghost on their first
    and last rows (``Rg = 2``): compact at 4 rows a block (``2 Rg <=
    row_pad``), row-aligned at 3.  The end blocks, with one ghost row
    each, list their first ghost-free row besides."""
    A = _tridiagonal(4 * rows_a_block)
    part = partition_csr(A, 4)
    ell = partitioned_to_ell(part)
    assert ell.row_pad == rows_a_block
    assert ell.ghost_layout == want
    if want == "compact":
        last = rows_a_block - 1
        np.testing.assert_array_equal(
            ell.ghost_rows, [[0, last], [0, last], [0, last], [0, 1]])
        np.testing.assert_array_equal(ell.ghost_vals[0, 0], 0.0)
        np.testing.assert_array_equal(ell.ghost_vals[3, 1], 0.0)
        assert ell.ghost_cols.shape == (4, 2, 1)
    else:
        assert ell.ghost_rows is None
        assert ell.ghost_cols.shape == (4, rows_a_block, 1)


# ------------------------------------------------ irregular partitions
def _irregular_case(kind: str, n_procs: int, split: str):
    """(operator, row offsets) on 48 rows: ``random`` sparsity (ELL local
    blocks) or a ``banded`` one (diagonal), with block 0's entries kept
    inside its own columns, so that block has no ghost entry."""
    n = 48
    rng = np.random.default_rng(100 * n_procs + len(split))
    if split == "even":
        off = block_offsets(n, n_procs)
    else:
        cuts = np.sort(rng.choice(np.arange(2, n - 1), n_procs - 1,
                                  replace=False))
        off = np.concatenate([[0], cuts, [n]])
    if kind == "random":
        rows = rng.integers(0, n, size=4 * n)
        cols = rng.integers(0, n, size=4 * n)
    else:
        rows = np.repeat(np.arange(n), 5)
        cols = rows + np.tile([-7, -1, 0, 2, 5], n)
    keep = (cols >= 0) & (cols < n) & (
        (rows >= off[1]) | (cols < off[1]))
    rows, cols = rows[keep], cols[keep]
    A = CSR.from_coo(rows, cols, rng.normal(size=len(rows)), (n, n))
    return A, np.asarray(off, dtype=np.int64)


@pytest.mark.parametrize("overlap", [False, True])
@pytest.mark.parametrize("split", ["even", "uneven"])
@pytest.mark.parametrize("n_procs", [2, 3, 4])
@pytest.mark.parametrize("kind", ["random", "banded"])
def test_distributed_spmv_on_irregular_partitions(kind, n_procs, split,
                                                  overlap):
    """The device SpMV, fused and overlapped, on random irregular row
    blocks equals the host CSR product to 1e-14 relative in f64, with
    one block holding no ghost entry beside blocks that hold some;
    padded rows stay exactly zero."""
    import jax

    from repro.core import PlanCache, Topology
    from repro.sparse import make_distributed_spmv
    from repro.verify import verify_device_ell

    A, off = _irregular_case(kind, n_procs, split)
    part = partition_rect_csr(A, off, off)
    ghosts = [m.nnz for m in part.ghost]
    assert ghosts[0] == 0 and any(ghosts[1:]), ghosts
    ell = partitioned_to_device(part)
    assert (ell.offsets is not None) == (kind == "banded")
    verify_device_ell(ell, part)
    mesh = jax.make_mesh((n_procs,), ("proc",),
                         devices=jax.devices()[:n_procs])
    exchange = PlanCache().collective(
        part.pattern, Topology(n_procs, 1), "standard").bind(mesh, "proc")
    x = np.random.default_rng(7).normal(size=A.ncols)
    with jax.enable_x64(True):
        xg = pack_vector(off, ell.in_pad, x)
        y = np.asarray(jax.jit(make_distributed_spmv(
            ell, mesh, "proc", exchange, overlap=overlap))(xg))
    assert y.dtype == np.float64
    want = A.matvec(x)
    np.testing.assert_allclose(unpack_vector(off, y), want, rtol=0,
                               atol=1e-14 * np.abs(want).max())
    for p in range(n_procs):
        np.testing.assert_array_equal(y[p, off[p + 1] - off[p]:], 0.0)
