"""The benchmark's own operator generator gives the program's matrices,
and its reference builds the program's hierarchy, on small grids."""
import json
import math

import numpy as np
import pytest

from bench.operators import diffusion_2d
from bench.reference import Reference

CONFIGS = ["paper2d-512k", "poisson2d-512k"]


def config(name, ny, nx):
    from bench.run import BENCH

    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return {**cfg, "ny": ny, "nx": nx}


@pytest.mark.parametrize("theta_deg,eps", [(45.0, 1e-3), (0.0, 1.0)])
def test_matches_program_stencil(theta_deg, eps):
    from repro.amg.stencil import diffusion_2d as program

    ny, nx = 12, 20
    A = program(ny, nx, theta=math.radians(theta_deg), eps=eps)
    indptr, indices, data = diffusion_2d.assemble(
        {"ny": ny, "nx": nx, "theta_deg": theta_deg, "eps": eps})
    assert np.array_equal(indptr, A.indptr)
    assert np.array_equal(indices, A.indices)
    assert np.array_equal(data, A.data)


def test_poisson_is_five_point():
    _, _, data = diffusion_2d.assemble(
        {"ny": 6, "nx": 6, "theta_deg": 0.0, "eps": 1.0})
    assert sorted(set(data.tolist())) == [-1.0, 4.0]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_hierarchy_is_the_programs(name):
    """Same operations on the same data: every level's matrix bit for bit,
    the Chebyshev interval to rounding."""
    from repro.amg.hierarchy import build_hierarchy
    from repro.sparse.csr import CSR

    cfg = config(name, 48, 64)
    arrays = diffusion_2d.assemble(cfg)
    n = len(arrays[0]) - 1
    s = cfg["solver"]
    h = build_hierarchy(CSR((n, n), *arrays), max_levels=s["max_levels"],
                        min_coarse=s["min_coarse"],
                        strength_theta=s["strength_theta"])
    ref = Reference(*arrays, s)
    assert len(ref.levels) == h.n_levels > 2
    for hl, rl in zip(h.levels, ref.levels):
        assert np.array_equal(hl.A.indptr, rl.A.indptr)
        assert np.array_equal(hl.A.indices, rl.A.indices)
        assert np.array_equal(hl.A.data, rl.A.data)
        assert rl.rho == pytest.approx(hl.rho, rel=1e-12)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_solve_tracks_the_host_solver(name):
    from repro.amg.hierarchy import build_hierarchy, solve
    from repro.sparse.csr import CSR

    cfg = config(name, 32, 32)
    arrays = diffusion_2d.assemble(cfg)
    n = len(arrays[0]) - 1
    h = build_hierarchy(CSR((n, n), *arrays))
    b = np.random.default_rng(3).standard_normal(n)
    x, hist = solve(h, b, tol=1e-8, max_iters=100)
    ref = Reference(*arrays, cfg["solver"])
    x_ref, h_ref = ref.solve(b, len(hist), converged=hist[-1] < 1e-8)
    assert np.max(np.abs(np.array(hist) - h_ref)) < 1e-14
    assert np.linalg.norm(x - x_ref) < 1e-12 * np.linalg.norm(x_ref)
    assert ref.resid(b, x) < 1e-8
