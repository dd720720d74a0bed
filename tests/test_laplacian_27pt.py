"""hypre's 3-D 27-point Laplacian (``ij -27pt``): the generator against a
brute-force loop, a four-device solve against the host solver, and the
set-up counters ``sparse/ghost_slots``, ``sparse/ghost_rows`` and
``amg/halo_values``, and the compact ghost block's histories against the
row-aligned block's."""
import dataclasses
import itertools

import jax
import numpy as np
import pytest

from repro.amg import (
    DistributedHierarchy,
    build_hierarchy,
    laplacian_27pt,
    solve,
)
from repro.obs import default_obs
from repro.sparse.partition import block_offsets


def brute_force(m: int, procs) -> np.ndarray:
    """The dense operator by a loop over every point and its 27-point cube,
    numbered as hypre's ``GenerateLaplacian27pt`` numbers rows."""
    px, py, pz = procs
    nx, ny, nz = px * m, py * m, pz * m

    def row(x, y, z):
        block = ((z // m) * py + y // m) * px + x // m
        return block * m ** 3 + ((z % m) * m + y % m) * m + x % m

    n = nx * ny * nz
    A = np.zeros((n, n))
    for z, y, x in itertools.product(range(nz), range(ny), range(nx)):
        for dz, dy, dx in itertools.product((-1, 0, 1), repeat=3):
            zz, yy, xx = z + dz, y + dy, x + dx
            if 0 <= xx < nx and 0 <= yy < ny and 0 <= zz < nz:
                A[row(x, y, z), row(xx, yy, zz)] = (
                    26.0 if (dx, dy, dz) == (0, 0, 0) else -1.0)
    return A


@pytest.mark.parametrize("m,procs", [(3, (2, 2, 1)), (2, (1, 2, 3))])
def test_matches_brute_force(m, procs):
    A = laplacian_27pt(m, procs)
    D = brute_force(m, procs)
    assert A.shape == D.shape == (m ** 3 * int(np.prod(procs)),) * 2
    np.testing.assert_array_equal(A.to_dense(), D)
    assert np.all(np.diff(A.indptr) <= 27)
    for i in range(A.nrows):      # canonical CSR: sorted, no duplicates
        cols, _ = A.row(i)
        assert np.all(np.diff(cols) > 0)


def test_symmetric_with_zero_interior_row_sums():
    m, procs = 3, (2, 2, 1)
    D = laplacian_27pt(m, procs).to_dense()
    np.testing.assert_array_equal(D, D.T)
    full = np.count_nonzero(D, axis=1) == 27
    # a 6 x 6 x 3 grid has 4 x 4 x 1 interior points
    assert full.sum() == 16
    np.testing.assert_array_equal(D[full].sum(axis=1), 0.0)
    assert np.all(D[~full].sum(axis=1) > 0)


def test_rows_numbered_block_by_block():
    """Contiguous row blocks are the process cubes: every row of block p
    couples only to blocks that touch p's cube, and p's own block holds
    its 27-point interior in local (lx, ly, lz) order."""
    m, procs = 3, (2, 2, 1)
    A = laplacian_27pt(m, procs)
    off = block_offsets(A.nrows, 4)
    assert np.array_equal(off, np.arange(5) * m ** 3)
    # the centre of block 0 is local (1, 1, 1): all 27 neighbours local
    cols, _ = A.row(13)
    assert np.array_equal(cols, np.arange(27))
    # the block 0 corner at the x-y edge sees both face blocks and block 3
    corner = (0 * m + (m - 1)) * m + (m - 1)
    owners = set(np.searchsorted(off, A.row(corner)[0], side="right") - 1)
    assert owners == {0, 1, 2, 3}


@pytest.fixture
def default_on():
    obs = default_obs()
    obs.reset()
    obs.enable()
    try:
        yield obs
    finally:
        obs.disable()
        obs.reset()


@pytest.fixture(scope="module")
def host_27pt():
    return build_hierarchy(laplacian_27pt(8, (2, 2, 1)), strength_theta=0.5)


def test_four_device_solve_matches_host(host_27pt):
    """8^3 points a process on a 2 x 2 x 1 grid: the fine A takes the
    diagonal layout with 27 offsets, every process's L0 plan has three
    peers (two faces and the diagonal block), and the device residual
    history tracks the host solver's."""
    mesh = jax.make_mesh((4,), ("proc",), devices=jax.devices()[:4])
    b = np.random.default_rng(5).standard_normal(host_27pt.levels[0].A.nrows)
    with jax.enable_x64(True):
        dh = DistributedHierarchy.setup(host_27pt, mesh)
        _, hist = dh.solve(b, tol=1e-8, max_iters=60)
    fine = dh.levels[0].A
    assert fine.local_layout == "diagonal"
    assert len(fine.ell.offsets) == 27
    assert max(abs(o) for o in fine.ell.offsets) == 8 * 8 + 8 + 1
    assert [len(fine.part.pattern.sends_for(q)) for q in range(4)] == [3] * 4
    _, hist_host = solve(host_27pt, b, tol=1e-8, max_iters=60)
    assert len(hist) == len(hist_host) and hist[-1] < 1e-8
    np.testing.assert_allclose(hist, hist_host, rtol=0, atol=1e-12)


def ghost_slots_stored(ell) -> int:
    return ell.ghost_cols.size if ell.ghost_pad else 0


def test_ghost_slot_and_halo_value_counters(host_27pt, default_on):
    """``sparse/ghost_slots`` counts the ghost entries and the slots the
    compact or row-aligned ghost blocks store for them;
    ``amg/halo_values`` each operator's plan values per application."""
    mesh = jax.make_mesh((4,), ("proc",), devices=jax.devices()[:4])
    dh = DistributedHierarchy.setup(host_27pt, mesh, dtype=np.float32,
                                    value_bytes=4)
    ops = [(lv.index, name, op) for lv in dh.levels
           for name, op in (("A", lv.A), ("R", lv.R), ("P", lv.P))
           if op is not None]
    slots = default_on.counter("sparse/ghost_slots")
    entries = sum(m.nnz for _, _, op in ops for m in op.part.ghost)
    stored = sum(ghost_slots_stored(op.ell) for _, _, op in ops)
    assert entries > 0
    assert slots.value(kind="entries") == entries
    assert slots.value(kind="stored") == stored >= entries
    # sparse/ghost_rows: the rows compact blocks store, beside every row
    # of the operators with ghosts
    rows = default_on.counter("sparse/ghost_rows")
    ghosted = [op for _, _, op in ops if op.ell.ghost_pad]
    compact = [op for op in ghosted if op.ghost_layout == "compact"]
    assert rows.value(kind="all") == sum(
        4 * op.ell.row_pad for op in ghosted)
    assert rows.value(kind="stored") == sum(
        op.ell.ghost_rows.size for op in compact)
    fine = dh.levels[0].A
    # the diagonal fine A and the ELL L0 R and P compact; each stores its
    # ghost rows' slots alone
    assert fine.local_layout == "diagonal"
    assert {(k, name) for k, name, op in ops
            if op.ghost_layout == "compact"} >= {
        (0, "A"), (0, "R"), (0, "P")}
    assert fine.ell.ghost_rows.shape == (4, 120)
    assert fine.ell.ghost_cols.shape[:2] == (4, 120)
    assert slots.value(kind="stored") < sum(
        4 * op.ell.row_pad * op.ell.ghost_cols.shape[2]
        for op in ghosted)
    halo = default_on.counter("amg/halo_values")
    for k, name, op in ops:
        # every ghost value arrives once, some relayed through a region
        assert halo.value(level=k, op=name) == op.halo_values
        assert op.halo_values >= op.part.pattern.total_ghosts() > 0


def test_counters_record_nothing_with_obs_off(host_27pt):
    obs = default_obs()
    obs.reset()
    assert not obs.enabled
    mesh = jax.make_mesh((4,), ("proc",), devices=jax.devices()[:4])
    DistributedHierarchy.setup(host_27pt, mesh, dtype=np.float32,
                               value_bytes=4)
    assert obs.counter("sparse/ghost_slots").total() == 0
    assert obs.counter("sparse/ghost_rows").total() == 0
    assert obs.counter("amg/halo_values").total() == 0


def row_aligned(op):
    """``op``'s device form with its ghost block row-aligned, as it is
    stored where most rows hold a ghost."""
    from repro.sparse.device import _stack_ell

    ell = op.ell
    gc, gv = _stack_ell(op.part.ghost, ell.row_pad, ell.ghost_pad,
                        ell.ghost_vals.dtype)
    return dataclasses.replace(ell, ghost_cols=gc, ghost_vals=gv,
                               ghost_rows=None)


@pytest.mark.parametrize("n_dev,overlap", [(1, "off"), (4, "off"),
                                           (4, "on")])
def test_compact_histories_equal_row_aligned(host_27pt, n_dev, overlap):
    """The solve's residual history with compact ghost blocks equals, bit
    for bit, the history with every ghost block row-aligned, in the fused
    and the overlapped schedule; one device has no ghost block."""
    mesh = jax.make_mesh((n_dev,), ("proc",), devices=jax.devices()[:n_dev])
    b = np.random.default_rng(9).standard_normal(
        host_27pt.levels[0].A.nrows)
    with jax.enable_x64(True):
        dh = DistributedHierarchy.setup(host_27pt, mesh,
                                        spmv_overlap=overlap)
        _, hist = dh.solve(b, tol=1e-8, max_iters=40)
        ops = [op for lv in dh.levels for op in (lv.A, lv.R, lv.P)
               if op is not None and op.ghost_layout == "compact"]
        assert bool(ops) == (n_dev > 1)
        assert all(op.overlap_mode == overlap for op in ops)
        for op in ops:
            op.ell = row_aligned(op)
        dh._build_device_fns()
        _, hist_rows = dh.solve(b, tol=1e-8, max_iters=40)
    assert hist[-1] < 1e-8
    assert hist == hist_rows
