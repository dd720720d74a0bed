"""Subprocess program: column-blocked SpMV through the distributed solve.

Run by tests/test_distributed_amg.py on 8 virtual host devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8, set before jax import).

Checks, on the 48x48 rotated anisotropic diffusion problem:
  1. a hierarchy with the blocked kernel FORCED on every level solves to
     the host solver's residual history (the blocked packing + accumulating
     kernel path is numerically identical to flat);
  2. auto-selection under a lowered VMEM threshold (standing in for a
     paper-scale fine level, whose x footprint exceeds the real threshold
     the same way) picks blocked on the fine level while at least one
     coarse level keeps flat, records the choice per operator, and the
     mixed-variant solve still matches the host;
  3. the one-shot distributed SpMV agrees with the host oracle for every
     variant on the fine operator;
  4. the kernel choice is visible in kernel_table() and describe().
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro.amg import DistributedHierarchy, build_hierarchy, diffusion_2d, solve
from repro.core import PlanCache, Topology
from repro.sparse import distributed_spmv, partition_csr, select_spmv_kernel


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

    # (3) one-shot distributed SpMV, all variants, vs host oracle
    part = partition_csr(h.levels[0].A, 8)
    cache = PlanCache()
    coll = cache.collective(part.pattern, Topology(8, 4), "auto")
    for variant in ("flat", "blocked", "auto"):
        y = distributed_spmv(part, coll, mesh, "proc", b,
                             variant=variant, block_cols=64)
        np.testing.assert_allclose(y, h.levels[0].A.matvec(b),
                                   rtol=1e-12, atol=1e-12)
    print("spmv variants OK")

    # (1) forced-blocked hierarchy matches the host residual history
    dh_blk = DistributedHierarchy.setup(
        h, mesh, procs_per_region=4, cache=PlanCache(),
        spmv_variant="blocked", spmv_block_cols=64,
    )
    assert all(lv.A.kernel_variant == "blocked" for lv in dh_blk.levels)
    assert all(lv.A.kernel and lv.A.kernel.forced for lv in dh_blk.levels)
    x_blk, hist_blk = dh_blk.solve(b, tol=1e-8, max_iters=60)
    assert len(hist_blk) == len(hist_host), (len(hist_blk), len(hist_host))
    np.testing.assert_allclose(
        np.asarray(hist_blk), np.asarray(hist_host), rtol=1e-8, atol=1e-15
    )
    print(f"forced-blocked residual history OK ({len(hist_blk)} iters, "
          f"final={hist_blk[-1]:.3e})")

    # (2) auto selection: threshold below the fine level's flat footprint
    # (a paper-scale fine level exceeds the *default* threshold the same
    # way — its x alone is ~17 MB; here we lower the threshold instead of
    # materializing 2M rows per device)
    flat_bytes = [
        select_spmv_kernel(partition_csr(lv.A, 8)).flat_bytes
        for lv in h.levels
    ]
    limit = (min(flat_bytes) + flat_bytes[0]) // 2
    assert flat_bytes[0] > limit > min(flat_bytes)
    dh = DistributedHierarchy.setup(
        h, mesh, procs_per_region=4, cache=PlanCache(),
        spmv_variant="auto", spmv_vmem_limit=limit, spmv_block_cols=64,
    )
    variants = {lv.index: lv.A.kernel_variant for lv in dh.levels}
    print(f"auto variants under {limit}B limit: {variants}")
    assert variants[0] == "blocked", variants     # fine level over budget
    assert "flat" in variants.values(), variants  # coarse keeps flat
    for lv in dh.levels:
        assert lv.A.kernel is not None and not lv.A.kernel.forced
    x_dev, hist_dev = dh.solve(b, tol=1e-8, max_iters=60)
    np.testing.assert_allclose(
        np.asarray(hist_dev), np.asarray(hist_host), rtol=1e-8, atol=1e-15
    )
    print("auto mixed-variant residual history OK")

    # (4) the choice is recorded and visible
    kt = dh.kernel_table()
    assert any(v == "blocked" for _, _, v, _, _, _ in kt)
    assert all(rep and "limit=" in rep for *_, rep in kt)
    desc = dh.describe()
    assert "kern=blocked" in desc and "kern=flat" in desc
    print(desc)

    print("ALL_OK")


if __name__ == "__main__":
    main()
