"""Run ``repro.verify`` over the standard plan zoo on 8 virtual devices.

The CI static-analysis gate: every plan producer in the repo is exercised
with ``REPRO_VERIFY=1``, so each plan is verified on insertion into the
``PlanCache`` (structure + conservation + device plan), each bound
executor is jaxpr-audited against its DevicePlan, and the hierarchy-level
sweeps re-check partitions, ELL layouts and kernel budgets:

* ``DistributedHierarchy.setup`` — host lowering of the AMG smoke problem
  (solve halos, R/P transfer operators, diagonal + ELL local blocks);
* ``DistributedHierarchy.setup_partitioned`` — the distributed setup,
  whose SpGEMM gather patterns ride through the same cache;
* ``repartition`` — the elastic rebuild onto a different device count;
* ``moe_plan_for`` — every MoE dispatch mode (a2a / hier / hier_dedup and
  the auto selector), plus the token-conservation check per plan;
* ``PlanCache.dense_collective`` — the dense plan zoo: every collective
  (allreduce / allgatherv / reduce_scatter) in every variant the
  geometry admits (ring / rd / hier), on both 8-device geometries
  (4 regions x 2 and 2 regions x 4), verified on insertion
  (conflict-free rounds + symbolic conservation) with each bound
  executor jaxpr-audited round-for-round against its schedule.

Exit 0 with a per-producer summary, or the first ``VerifyError``
propagates and fails the job with its rank/slot diagnostic.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["REPRO_VERIFY"] = "1"

import numpy as np  # noqa: E402


def main() -> int:
    import jax

    assert jax.device_count() == 8, jax.devices()
    from repro.amg import (
        DistributedHierarchy,
        build_hierarchy,
        diffusion_2d,
        partition_fine_matrix,
    )
    from repro.configs import reduced
    from repro.core import PlanCache
    from repro.models.moe import moe_plan_for
    from repro.verify import verify_hierarchy, verify_moe_dispatch

    summary = {}
    mesh = jax.make_mesh((8,), ("proc",))
    A = diffusion_2d(32, 32)

    # -- host lowering: solve halos + R/P operators ------------------------
    cache = PlanCache()
    dh = DistributedHierarchy.setup(
        build_hierarchy(A), mesh, procs_per_region=4, cache=cache
    )
    summary["setup"] = verify_hierarchy(dh)

    # -- distributed setup: SpGEMM gather patterns through the cache ------
    blocks, off = partition_fine_matrix(A, 8)
    dhp = DistributedHierarchy.setup_partitioned(
        blocks, off, mesh, procs_per_region=4, cache=cache
    )
    summary["setup_partitioned"] = verify_hierarchy(dhp)

    # -- elastic repartition onto a different device count -----------------
    from jax.sharding import Mesh

    mesh4 = Mesh(np.array(jax.devices()[:4]), ("proc",))
    dh4 = dh.repartition(mesh4, procs_per_region=2, reason="verify_zoo")
    summary["repartition"] = verify_hierarchy(dh4)

    # -- MoE dispatch: every mode + the auto selector ----------------------
    cfg = reduced("mixtral-8x7b")
    moe_mesh = jax.make_mesh((1, 8), ("data", "model"))
    tokens = 64
    moe_counts = {}
    for mode in ("a2a", "hier", "hier_dedup", "auto"):
        plan = moe_plan_for(cfg, moe_mesh, tokens, mode=mode, cache=cache)
        verify_moe_dispatch(plan, tokens)
        moe_counts[mode] = plan.mode
    summary["moe"] = moe_counts

    # -- dense collectives: every variant on both 8-device geometries ------
    from repro.core import DENSE_COLLECTIVES, Topology
    from repro.core.dense import dense_variants

    dense_counts = {}
    rng = np.random.default_rng(0)
    for ppr in (2, 4):
        topo = Topology(8, ppr)
        for coll in DENSE_COLLECTIVES:
            # uneven counts so conservation is checked on a ragged wire
            counts = rng.integers(3, 17, size=8)
            for variant in dense_variants(coll, topo) + ["auto"]:
                plan, sel = cache.dense_collective(coll, counts, topo,
                                                   variant=variant)
                cache.dense_executor(plan, mesh, "proc")  # jaxpr audit
                if variant == "auto":
                    dense_counts[f"{coll}@ppr{ppr}"] = sel.chosen
    summary["dense"] = dense_counts

    stats = cache.stats()
    print("verify_zoo: all plan producers verified")
    for producer, counts in summary.items():
        print(f"  {producer}: {counts}")
    print(
        "  cache: "
        + ", ".join(
            f"{ns}={d['entries']}" for ns, d in stats["namespaces"].items()
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
