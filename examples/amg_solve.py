"""End-to-end driver (the paper's workload): rotated anisotropic diffusion
-> classical AMG -> device-resident distributed solve, with every level's
halo exchange executed through a locality-aware persistent neighborhood
collective — the Hypre + MPI Advance integration the paper evaluates, but
running as one jitted shard_map program.

Two communication sections are printed:

* *modeled* per-level times at the requested paper-scale process count
  (``--procs``, e.g. 2048) — exact plan message counts/bytes, max-rate model;
* *measured* device exchange + a full device V-cycle solve on the local
  mesh (``jax.device_count()`` processes) validated against the host solver.

    PYTHONPATH=src python examples/amg_solve.py --rows 65536 --procs 256
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/amg_solve.py --rows 16384
"""
import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=65_536)
    ap.add_argument("--procs", type=int, default=256,
                    help="modeled (paper-scale) process count")
    ap.add_argument("--procs-per-region", type=int, default=16)
    ap.add_argument("--strategy", default="auto",
                    choices=["auto", "standard", "partial", "full"])
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--setup", default="host",
                    choices=["host", "distributed"],
                    help="host: lower the host-built hierarchy; distributed: "
                    "build the hierarchy end-to-end from the partitioned "
                    "fine matrix (PMIS/interpolation/Galerkin SpGEMM over "
                    "sparse dynamic data exchanges) — no rank ever holds a "
                    "global operator")
    ap.add_argument("--no-device", action="store_true",
                    help="skip the device-resident solve")
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_enable_x64", True)

    from repro.amg import DistributedHierarchy, build_hierarchy, diffusion_2d, \
        partition_fine_matrix, solve
    from repro.core import LASSEN, NeighborAlltoallV, Topology, build_plan, \
        default_plan_cache, plan_time
    from repro.sparse import partition_csr

    nx = 1 << int(np.ceil(np.log2(np.sqrt(args.rows))))
    ny = args.rows // nx
    print(f"[amg] assembling {ny}x{nx} rotated anisotropic diffusion "
          f"(theta=45deg, eps=1e-3)")
    A = diffusion_2d(ny, nx)
    t0 = time.time()
    h = build_hierarchy(A)
    print(f"[amg] setup {time.time() - t0:.1f}s\n{h.describe()}")

    # ---- modeled section: paper-scale process count ------------------------
    topo = Topology(args.procs, min(args.procs_per_region, args.procs))
    print(f"\n[comm/modeled] {args.procs} processes in {topo.n_regions} "
          f"regions; persistent neighborhood collectives per level "
          f"(strategy={args.strategy}):")
    total_modeled = {"standard": 0.0, "chosen": 0.0}
    for lvl, level in enumerate(h.levels):
        if level.A.nrows < args.procs:
            break
        part = partition_csr(level.A, args.procs)
        coll = NeighborAlltoallV.init(part.pattern, topo, args.strategy,
                                      params=LASSEN)
        std = plan_time(build_plan(part.pattern, topo, "standard"), LASSEN)
        mine = coll.modeled_time(LASSEN)
        total_modeled["standard"] += std
        total_modeled["chosen"] += min(std, mine)
        t = coll.plan.stats.totals()
        print(f"  L{lvl}: strategy={coll.strategy:8s} "
              f"inter_msgs={t['inter_msgs']:6d} "
              f"inter_bytes={t['inter_bytes']:9d} "
              f"modeled={mine * 1e6:7.1f}us (standard {std * 1e6:7.1f}us)")
    sp = total_modeled["standard"] / max(total_modeled["chosen"], 1e-12)
    print(f"[comm/modeled] per-iteration speedup over standard: {sp:.2f}x")

    if args.no_device:
        return

    # ---- measured section: device-resident distributed solve ---------------
    n_dev = jax.device_count()
    mesh = jax.make_mesh((n_dev,), ("proc",))
    print(f"\n[device] {n_dev} device(s); setting up distributed hierarchy "
          f"(persistent init through the plan cache, {args.setup} setup)...")
    cache = default_plan_cache()
    t0 = time.time()
    if args.setup == "distributed":
        # end-to-end distributed setup: each rank owns a row block of A and
        # coarsens it in place — strength/PMIS/interp with halo'd rounds,
        # R = P^T and the Galerkin R*A*P over sparse dynamic data exchanges
        blocks, off = partition_fine_matrix(A, n_dev)
        dh = DistributedHierarchy.setup_partitioned(
            blocks, off, mesh, strategy=args.strategy, cache=cache,
        )
        print(f"[device] setup {time.time() - t0:.1f}s")
        print(dh.setup_info.describe())
    else:
        dh = DistributedHierarchy.setup(
            h, mesh, strategy=args.strategy, cache=cache,
        )
        print(f"[device] setup {time.time() - t0:.1f}s")
    print(dh.describe())
    for lvl, op, strat, rep in dh.selection_table():
        if op == "A" and rep:
            print(f"  L{lvl} {op}: {rep}")
    for lvl, op, layout, ghost, ov, rep in dh.kernel_table():
        if op == "A" and rep:
            print(f"  L{lvl} {op}: local={layout} ghost={ghost} {rep}")
    if n_dev > 1:
        print("[device] measured per-level exchange (jitted executor):")
        for lvl, strat, secs in dh.measure_exchange_seconds():
            print(f"  L{lvl}: strategy={strat:8s} "
                  f"measured={secs * 1e6:8.1f}us")

    rng = np.random.default_rng(0)
    b = rng.normal(size=A.nrows)
    t0 = time.time()
    x, hist = dh.solve(b, tol=1e-8, max_iters=args.iters)
    dt = time.time() - t0
    for it in range(0, len(hist), 5):
        print(f"[solve] iter {it:3d} rel_res={hist[it]:.3e}")
    print(f"[solve] device {dt:.1f}s, {len(hist)} iters, final rel_res="
          f"{np.linalg.norm(b - A.matvec(x)) / np.linalg.norm(b):.3e}")

    # cross-check against the host solver
    x_h, hist_h = solve(h, b, tol=1e-8, max_iters=args.iters)
    drift = max(
        abs(d - hh) / max(hh, 1e-300) for d, hh in zip(hist, hist_h)
    )
    print(f"[solve] host cross-check: {len(hist_h)} iters, max history "
          f"drift {drift:.2e} (plan cache: {cache.stats()})")


if __name__ == "__main__":
    main()
