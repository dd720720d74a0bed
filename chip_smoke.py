"""Smoke run of the paper's workload on TPU chips, through its normal path.

Rotated anisotropic diffusion (theta=45deg, eps=1e-3) -> classical AMG
(``amg.build_hierarchy``) -> ``DistributedHierarchy`` -> the jitted device
V-cycle, whose SpMV halos go through the persistent ``NeighborAlltoallV``.
The solve is checked against the host solver (``amg.solve``).

    python chip_smoke.py             # one chip, 2^20 rows, solved to 1e-8
    python chip_smoke.py --chips 4   # four chips, 2^20 rows per chip (weak
                                     # scaling: real halos between chips)

On four chips the solve runs the V-cycles the host solver repeats
(``HOST_CHECK_ITERS``), not all the way to 1e-8: with the SpMV as an XLA
gather, one V-cycle at 2^20 rows per chip takes about 1.7 s, and the host
reference about 6 s per V-cycle on the four-chip grid.

It runs on a TPU only: with no TPU it exits nonzero and prints no result.
Every time it prints is a smoke number from one run, not a benchmark
number.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every check passed; ``count`` is the number of chips used.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: grid side per chip: SIDE**2 = 2^20 rows, the per-device size that
#: benchmarks/roofline_report.py models
SIDE = 1024
TOL = 1e-8
#: the stationary AMG iteration contracts more slowly as the grid grows:
#: on the host, 1e-8 takes 83 steps at 2^18 rows and about 120 at 2^20
MAX_ITERS = 300
#: host-solver steps the device history is compared with: the numpy
#: reference takes 1-3 s per step at 2^20 rows and 4x that per step on the
#: four-chip grid, so the whole history would cost minutes of host time;
#: the final residual is recomputed on the host instead
HOST_CHECK_ITERS = 12
#: largest relative difference between a device residual and the host
#: solver's at the same step.  f64 on a v5e gave 2.2e-10 over the first
#: 25 steps at 2^20 rows; rounding moves a residual r by about 1e-16/r
#: relative, so the bound leaves room down to r = 1e-9 and catches any
#: change of arithmetic
DRIFT_TOL = 1e-7
#: largest relative difference between the final residual as the device
#: computed it and ||b - Ax|| recomputed on the host: at 1e-8, b - Ax
#: cancels eight digits of f64, and a v5e and the host differed by 4.1e-6
FINAL_DRIFT_TOL = 1e-4
SEED = 0


def require_tpu(n_chips: int) -> list:
    """The first ``n_chips`` TPU devices; exits nonzero where there are none."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX runs on "
                 f"{devices[0].platform!r}); this script runs on a TPU only")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: {n_chips} chips asked for, {len(devices)} "
                 f"found")
    return devices[:n_chips]


def rel_drift(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-300)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="chips to solve on, 2^20 rows each")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)

    import jax

    from repro import kernels
    from repro.amg import DistributedHierarchy, build_hierarchy, \
        diffusion_2d, solve
    from repro.launch.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    jax.config.update("jax_enable_x64", True)   # the solver moves f64
    dev = devices[0]
    n = len(devices)
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={n}")
    print(f"compile cache: {cache_dir}")
    print("kernel implementations by platform (repro.kernels.IMPLS):")
    print(kernels.impl_table())
    print("timings below are smoke numbers from one run, not benchmark "
          "numbers")

    side = SIDE * (2 if n == 4 else 1)       # 2^20 rows per chip
    t0 = time.perf_counter()
    A = diffusion_2d(side, side)
    h = build_hierarchy(A)
    host_setup = time.perf_counter() - t0
    print(f"host setup: {side}x{side} grid, {A.nrows} rows, "
          f"{h.n_levels} levels, {host_setup:.3f} s")

    mesh = jax.make_mesh((n,), ("proc",), devices=devices)
    t0 = time.perf_counter()
    dh = DistributedHierarchy.setup(h, mesh)
    dev_setup = time.perf_counter() - t0
    t = dh.topo
    print(f"topology assumed: {t.n_procs} procs, {t.n_regions} regions of "
          f"{t.procs_per_region}; device setup {dev_setup:.3f} s")

    spmv_impl = kernels.impl("spmv_ell")
    print(f"{'level':>5} {'rows':>9} {'nnz':>9} {'strategy':>9} "
          f"{'spmv':>5} {'layout':>8} {'overlap':>7}  R/P")
    for lv, hl in zip(dh.levels, h.levels):
        rp = f"{lv.R.strategy}/{lv.P.strategy}" if lv.R else "-"
        print(f"{lv.index:>5} {lv.n:>9} {hl.A.nnz:>9} {lv.A.strategy:>9} "
              f"{spmv_impl:>5} {lv.A.local_layout:>8} "
              f"{lv.A.overlap_mode:>7}  {rp}")

    b = np.random.default_rng(SEED).normal(size=A.nrows)
    t0 = time.perf_counter()
    dh.solve(b, tol=TOL, max_iters=1)
    first_call = time.perf_counter() - t0
    iters = MAX_ITERS if n == 1 else HOST_CHECK_ITERS
    t0 = time.perf_counter()
    x, hist = dh.solve(b, tol=TOL, max_iters=iters)
    steady = time.perf_counter() - t0
    print(f"first call (compile + 1 V-cycle): {first_call:.3f} s")
    print(f"steady solve: {len(hist)} iterations in {steady:.3f} s, final "
          f"relative residual {hist[-1]:.3e}")

    if n > 1:
        print("measured exchange per level "
              "(DistributedHierarchy.measure_exchange_seconds):")
        for lvl, strat, secs in dh.measure_exchange_seconds():
            print(f"  L{lvl}: strategy={strat:9s} {secs * 1e6:.1f} us")

    t0 = time.perf_counter()
    _, hist_h = solve(h, b, tol=TOL, max_iters=HOST_CHECK_ITERS + 1)
    host_rel = float(np.linalg.norm(b - A.matvec(x)) / np.linalg.norm(b))
    host_check = time.perf_counter() - t0
    k = min(len(hist), HOST_CHECK_ITERS)
    drift = max(rel_drift(d, q) for d, q in zip(hist[:k], hist_h[:k]))
    # a solve that converged returns the iterate whose residual it measured
    # last; one that ran out of iterations, the iterate a V-cycle past it
    converged = hist[-1] < TOL
    final_ref = hist[-1] if converged or len(hist) >= len(hist_h) \
        else hist_h[len(hist)]
    final_drift = rel_drift(host_rel, final_ref)
    print(f"host cross-check ({host_check:.3f} s): max history drift "
          f"{drift:.3e} over {k} iterations, final residual on host "
          f"{host_rel:.3e} (drift {final_drift:.3e}); tolerances "
          f"{DRIFT_TOL} and {FINAL_DRIFT_TOL}")

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    print(f"peak_bytes_in_use per chip: {peaks}")

    shard_devices = [s.device for s in dh.x_device.addressable_shards]
    failures = []
    if not np.all(np.isfinite(x)) or not np.all(np.isfinite(hist)):
        failures.append("non-finite solution or residual")
    if n == 1 and not converged:
        failures.append(f"residual {hist[-1]:.3e} not below {TOL} in "
                        f"{MAX_ITERS} iterations")
    if drift > DRIFT_TOL:
        failures.append(f"device and host histories differ by {drift:.3e} "
                        f"> {DRIFT_TOL}")
    if final_drift > FINAL_DRIFT_TOL:
        failures.append(f"final residual differs from the host's by "
                        f"{final_drift:.3e} > {FINAL_DRIFT_TOL}")
    if len(set(shard_devices)) != n or set(shard_devices) != set(devices):
        failures.append(f"row blocks held by {shard_devices}, not one on "
                        f"each of {devices}")
    for msg in failures:
        print(f"FAIL: {msg}")
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
