"""Subprocess program: exchange/compute-overlapped SpMV through the
distributed solve.

Run by tests/test_distributed_amg.py on 8 virtual host devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8, set before jax import).

Checks, on the 48x48 rotated anisotropic diffusion problem:
  1. hierarchies with the overlapped schedule FORCED on every level — as
     set up, the fine level's local block stored by diagonals, and with
     the fine level's local block replaced by its ELL gather — solve to
     the host solver's residual history (the split local-then-ghost
     accumulation is numerically identical to the fused path);
  2. the one-shot distributed SpMV agrees with the host oracle for both
     local layouts (ELL gather, diagonal) x every overlap mode on the fine
     operator;
  3. the default auto selection (off at this scale: local compute is below
     the split overhead) solves correctly and records its per-level
     decision on each operator;
  4. the decision is visible in kernel_table() and describe() (ov= column).
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro.amg import DistributedHierarchy, build_hierarchy, diffusion_2d, solve
from repro.core import PlanCache, Topology
from repro.sparse import (
    distributed_spmv,
    make_distributed_spmv,
    pack_vector,
    partition_csr,
    partitioned_to_ell,
    unpack_vector,
)


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = jax.make_mesh((8,), ("proc",))

    A = diffusion_2d(48, 48)
    h = build_hierarchy(A)
    rng = np.random.default_rng(0)
    b = rng.normal(size=A.nrows)

    # -- host reference -----------------------------------------------------
    x_host, hist_host = solve(h, b, tol=1e-8, max_iters=60)
    assert hist_host[-1] < 1e-8, hist_host[-5:]

    # (2) one-shot distributed SpMV: local layouts x overlap modes
    part = partition_csr(h.levels[0].A, 8)
    cache = PlanCache()
    coll = cache.collective(part.pattern, Topology(8, 4), "auto")
    exchange = coll.bind(mesh, "proc")
    for overlap in ("off", "on", "auto"):
        y = distributed_spmv(part, coll, mesh, "proc", b, overlap=overlap)
        np.testing.assert_allclose(y, h.levels[0].A.matvec(b),
                                   rtol=1e-12, atol=1e-12)
    ell = partitioned_to_ell(part)
    xg = pack_vector(part.col_offsets, ell.in_pad, b)
    for overlap in (False, True):
        fn = jax.jit(make_distributed_spmv(ell, mesh, "proc", exchange,
                                           overlap=overlap))
        y = unpack_vector(part.offsets, np.asarray(fn(xg)))
        np.testing.assert_allclose(y, h.levels[0].A.matvec(b),
                                   rtol=1e-12, atol=1e-12)
    print("spmv layout x overlap grid OK")

    # (1) forced-overlap hierarchies match the host residual history
    for fine in ("diagonal", "ell"):
        dh = DistributedHierarchy.setup(
            h, mesh, procs_per_region=4, cache=PlanCache(),
            spmv_overlap="on",
        )
        assert dh.levels[0].A.local_layout == "diagonal"
        if fine == "ell":
            dh.levels[0].A.ell = partitioned_to_ell(dh.levels[0].A.part)
            dh._build_device_fns()
        assert dh.levels[0].A.local_layout == fine
        ghosted = [lv for lv in dh.levels if lv.A.ell.ghost_pad > 0]
        assert ghosted, "test problem must have halo exchanges"
        for lv in ghosted:
            assert lv.A.overlap_mode == "on", (lv.index, lv.A.overlap)
            assert lv.A.overlap is not None and lv.A.overlap.forced
        x_dev, hist_dev = dh.solve(b, tol=1e-8, max_iters=60)
        assert len(hist_dev) == len(hist_host), (len(hist_dev),
                                                 len(hist_host))
        np.testing.assert_allclose(
            np.asarray(hist_dev), np.asarray(hist_host),
            rtol=1e-8, atol=1e-15,
        )
        print(f"forced-overlap {fine} residual history OK "
              f"({len(hist_dev)} iters, final={hist_dev[-1]:.3e})")

    # (3) auto: off at this scale (local compute < split overhead), the
    # decision recorded per level, and the solve still correct
    dh = DistributedHierarchy.setup(
        h, mesh, procs_per_region=4, cache=PlanCache(),
    )
    for lv in dh.levels:
        assert lv.A.overlap is not None and not lv.A.overlap.forced
        assert lv.A.overlap_mode == "off", (lv.index, lv.A.overlap)
    x_dev, hist_dev = dh.solve(b, tol=1e-8, max_iters=60)
    np.testing.assert_allclose(
        np.asarray(hist_dev), np.asarray(hist_host), rtol=1e-8, atol=1e-15
    )
    print("auto-overlap residual history OK")

    # (4) the decision is recorded and visible
    kt = dh.kernel_table()
    assert kt[0][:3] == (0, "A", "diagonal"), kt[0]
    assert all(lay in ("diagonal", "ell") for _, _, lay, *_ in kt)
    assert all(ov in ("on", "off") for _, _, _, _, ov, _ in kt)
    assert all(g in ("compact", "rows", "none") for _, _, _, g, _, _ in kt)
    assert all("overlap=" in rep for *_, rep in kt), kt
    desc = dh.describe()
    assert "ov=off" in desc
    print(desc)

    print("ALL_OK")


if __name__ == "__main__":
    main()
