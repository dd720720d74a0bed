"""Subprocess program: device-resident distributed AMG V-cycle vs host solver.

Run by tests/test_distributed_amg.py on 8 virtual host devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8, set before jax import).

Checks, on the 64x64 rotated anisotropic diffusion problem:
  1. the jitted device V-cycle's residual history matches the host
     ``Hierarchy`` solver's to 1e-8 relative tolerance;
  2. the Section-5 auto-selector picks >= 2 distinct strategies across
     levels (fine -> standard, coarse -> aggregated);
  3. a second setup on the same hierarchy hits the plan cache only
     (no re-planning), and the bound executors are reused as-is;
  4. the device distributed SpMV matches the host oracle on the fine level,
     with its local block gathered (ELL) and applied by diagonals; the
     hierarchy stores the fine A's local block by diagonals, no other,
     and the fine A's ghost block compact;
  5. measured device exchange times are finite and positive.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro.amg import DistributedHierarchy, build_hierarchy, diffusion_2d, solve
from repro.core import PlanCache, Topology
from repro.sparse import distributed_spmv, partition_csr


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = jax.make_mesh((8,), ("proc",))

    A = diffusion_2d(64, 64)
    h = build_hierarchy(A)
    rng = np.random.default_rng(0)
    b = rng.normal(size=A.nrows)

    # -- host reference -----------------------------------------------------
    x_host, hist_host = solve(h, b, tol=1e-8, max_iters=60)
    assert hist_host[-1] < 1e-8, hist_host[-5:]

    # -- device hierarchy ---------------------------------------------------
    cache = PlanCache()
    dh = DistributedHierarchy.setup(
        h, mesh, procs_per_region=4, strategy="auto", cache=cache
    )
    print(dh.describe())

    # (4) fine-level device SpMV vs host oracle
    part = partition_csr(h.levels[0].A, 8)
    coll = cache.collective(part.pattern, Topology(8, 4), "auto")
    y_dev = distributed_spmv(part, coll, mesh, "proc", b)
    np.testing.assert_allclose(y_dev, A.matvec(b), rtol=1e-12, atol=1e-12)
    layouts = [lv.A.local_layout for lv in dh.levels]
    assert layouts == ["diagonal"] + ["ell"] * (len(layouts) - 1), layouts
    # the fine A's ghost entries sit on its strips' boundary rows alone
    assert dh.levels[0].A.ghost_layout == "compact"
    print("spmv OK")

    # (1) residual histories match to 1e-8 relative tolerance
    x_dev, hist_dev = dh.solve(b, tol=1e-8, max_iters=60)
    assert len(hist_dev) == len(hist_host), (len(hist_dev), len(hist_host))
    # atol = f64 machine epsilon on the unit-normalized initial residual:
    # summation-order roundoff puts an absolute noise floor of ~1e-16 under
    # every entry; above that floor the histories agree to 1e-8 relative.
    np.testing.assert_allclose(
        np.asarray(hist_dev), np.asarray(hist_host), rtol=1e-8, atol=1e-15
    )
    assert hist_dev[-1] < 1e-8
    rel_x = np.linalg.norm(x_dev - x_host) / np.linalg.norm(x_host)
    print(f"residual history OK ({len(hist_dev)} iters, "
          f"final={hist_dev[-1]:.3e}, |x_dev-x_host|/|x_host|={rel_x:.3e})")

    # (2) >= 2 distinct strategies across the levels' operator collectives
    per_level = {lv.index: lv.A.strategy for lv in dh.levels}
    strategies = set(per_level.values())
    print(f"per-level strategies: {per_level}")
    assert len(strategies) >= 2, strategies
    assert per_level[0] == "standard", per_level  # fine level is comm-light
    for lv in dh.levels:
        assert lv.A.selection is not None  # auto ran the selector
    print("selection OK")

    # (3) repeated setup: all plan lookups hit, zero new planning
    misses_before = cache.misses
    exec_misses_before = cache.exec_misses
    dh2 = DistributedHierarchy.setup(
        h, mesh, procs_per_region=4, strategy="auto", cache=cache
    )
    assert cache.misses == misses_before, (cache.misses, misses_before)
    assert cache.exec_misses == exec_misses_before
    assert cache.hits > 0 and cache.init_seconds_saved > 0.0
    # same persistent collective objects — init was skipped, not repeated
    for lv1, lv2 in zip(dh.levels, dh2.levels):
        assert lv1.A.coll is lv2.A.coll
    print(f"plan cache OK: {cache.stats()}")

    # (5) measured device exchange
    for lvl, strat, secs in dh.measure_exchange_seconds(iters=5, warmup=2):
        assert np.isfinite(secs) and secs >= 0.0
        print(f"  L{lvl} {strat:8s} measured exchange {secs * 1e6:8.1f}us")

    print("ALL_OK")


if __name__ == "__main__":
    main()
