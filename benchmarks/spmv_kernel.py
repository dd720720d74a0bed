"""ELL SpMV kernel benchmark rows.

* :func:`measured_rows` — MEASURED wall-clock of the ELL SpMV on this
  host: the jnp reference path (CPU backend) on the benchmark fine level,
  and the Pallas kernel in interpret mode on a small problem.  Before
  timing, the product is asserted equal to the host matvec — the
  benchmark doubles as an equivalence gate in CI smoke.

Overlap row families (the exchange/compute-overlap schedule):

* :func:`overlap_rows` — DETERMINISTIC modeled overlap decisions per AMG
  level (exchange time from the plan model, local compute from the roofline
  compute model) plus the paper-scale analytic fine level, which must come
  out ``on`` (its local compute dwarfs both the exchange and the split
  overhead).  Exposed/hidden exchange times are exact cost-model arithmetic.

* :func:`measured_overlap_rows` — MEASURED wall-clock of the full
  distributed SpMV on the local device mesh under both schedules (overlap
  off vs on), next to the pure exchange and a kernel-only run, from which a
  measured exposed-exchange fraction is derived.  Both schedules are
  asserted equivalent to the host matvec before timing.  On the CPU host
  platform collectives are synchronous, so the measured fractions mainly
  document what XLA already hides; the modeled fields carry the v5e story.
"""
from __future__ import annotations

import time

import numpy as np

from repro.amg import diffusion_2d
from repro.core import LASSEN, TPU_V5E, build_plan, plan_time
from repro.core.costmodel import modeled_fine_exchange_time, spmv_compute_time
from repro.sparse import (
    overlap_decision,
    partition_csr,
    partitioned_to_device,
    partitioned_to_ell,
    select_spmv_overlap,
)

from .amg_comm import VALUE_BYTES, bench_topology, hierarchy_for

#: Paper-scale synthetic fine level: ~2M unknowns per device (the scale at
#: which the paper's BoomerAMG fine levels run), 9-point stencil, a
#: two-cell-deep halo — per-device x alone is ~17 MB, past any VMEM tier.
PAPER_ROWS_PER_PROC = 2 ** 21
PAPER_K = 9
PAPER_GHOST = 2 * 4096
#: Inter-device neighbors of the analytic fine level: a two-deep halo on a
#: 2-D decomposition touches all eight surrounding subdomains.
PAPER_NEIGHBORS = 8


def _time_fn(fn, x, iters: int, warmup: int) -> float:
    for _ in range(warmup):
        np.asarray(fn(x))
    t0 = time.perf_counter()
    for _ in range(iters):
        np.asarray(fn(x))
    return (time.perf_counter() - t0) / iters


def _check_and_time(A, backend_name: str, iters: int, warmup: int):
    """Assert the ELL SpMV of an unpartitioned operator (1-proc partition:
    no ghosts, so the kernel is exercised in isolation) equals the host
    matvec, then time it."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import use_backend
    from repro.kernels.spmv_ell.ops import spmv

    ell = partitioned_to_ell(partition_csr(A, 1))
    rng = np.random.default_rng(7)
    x = rng.normal(size=A.ncols)
    want = A.matvec(x)

    xf = jnp.asarray(np.concatenate([x, [0.0]]))        # sentinel slot
    lc = jnp.asarray(ell.local_cols[0])
    lv = jnp.asarray(ell.local_vals[0])

    with use_backend(backend_name):
        fn = jax.jit(lambda v: spmv(lc, lv, v))
        got = np.asarray(fn(xf))[: A.nrows]
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-8)
        t = _time_fn(fn, xf, iters, warmup)
    return t, ell


def measured_rows(rows: int):
    """Measured ELL SpMV timings: jnp reference path on the benchmark fine
    level, Pallas interpret mode on a small problem."""
    import jax

    # equivalence checks compare against the f64 host matvec
    jax.config.update("jax_enable_x64", True)
    out = []
    # -- CPU reference path on the fine level ------------------------------
    A = hierarchy_for(min(rows, 65_536)).levels[0].A
    t, ell = _check_and_time(A, backend_name="xla", iters=10, warmup=2)
    out.append((
        "spmv_kernel/measured/flat_ref", t * 1e6,
        f"kind=measured-host|backend=reference|rows={A.nrows}"
        f"|k={ell.local_cols.shape[2]}",
    ))
    # -- Pallas kernel in interpret mode (small: interpret is python) ------
    As = diffusion_2d(16, 16)
    t, ell = _check_and_time(As, backend_name="pallas_interpret",
                             iters=2, warmup=1)
    out.append((
        "spmv_kernel/measured/flat_interpret", t * 1e6,
        f"kind=measured-host|backend=pallas_interpret|rows={As.nrows}"
        f"|k={ell.local_cols.shape[2]}",
    ))
    return out


# ---------------------------------------------------------------------------
# exchange/compute overlap
# ---------------------------------------------------------------------------

def _overlap_fields(osel) -> str:
    return (
        f"mode={osel.mode}|tx_us={osel.exchange_s * 1e6:.3f}"
        f"|local_us={osel.local_s * 1e6:.3f}"
        f"|exposed_us={osel.exposed_s * 1e6:.3f}"
        f"|hidden_frac={osel.hidden_frac:.4f}"
        f"|overhead_us={osel.overhead_s * 1e6:.3f}"
    )


def overlap_rows(rows: int, n_procs: int):
    """Modeled overlap decision per level and at paper scale (deterministic).

    Per benchmark-problem level: exchange time from the standard-strategy
    plan under the Lassen postal/max-rate model, local compute from the
    roofline compute model — the same inputs ``DistributedHierarchy.setup``
    feeds ``select_spmv_overlap``.  The trailing ``paper_fine`` row models
    the analytic paper-scale fine level on v5e, where auto MUST choose
    ``on``: hiding the ~90us DCI exchange behind ~300us of local compute
    beats the split overhead (one carried-y HBM round trip).
    """
    out = []
    h = hierarchy_for(rows)
    topo = bench_topology(n_procs)
    for k, lvl in enumerate(h.levels):
        if lvl.A.nrows < n_procs:
            break
        part = partition_csr(lvl.A, n_procs)
        plan = build_plan(part.pattern, topo, "standard",
                          value_bytes=VALUE_BYTES)
        osel = select_spmv_overlap(
            part, plan_time(plan, LASSEN), value_bytes=VALUE_BYTES
        )
        out.append((
            f"spmv_overlap/select/L{k}", 0.0,
            f"kind=modeled-overlap|{_overlap_fields(osel)}",
        ))
    # paper-scale analytic fine level (never materialized): exchange from
    # the postal model, local compute from the roofline compute model
    tx = modeled_fine_exchange_time(
        PAPER_NEIGHBORS, PAPER_GHOST, value_bytes=VALUE_BYTES,
        params=TPU_V5E,
    )
    tl = spmv_compute_time(
        PAPER_ROWS_PER_PROC * PAPER_K, PAPER_ROWS_PER_PROC,
        PAPER_ROWS_PER_PROC + PAPER_GHOST, value_bytes=VALUE_BYTES,
    )
    osel = overlap_decision(
        tx, tl, rows=PAPER_ROWS_PER_PROC, value_bytes=VALUE_BYTES
    )
    assert osel.mode == "on", osel  # paper scale MUST overlap
    out.append((
        "spmv_overlap/select/paper_fine", 0.0,
        f"kind=modeled-overlap|rows_per_proc={PAPER_ROWS_PER_PROC}"
        f"|neighbors={PAPER_NEIGHBORS}|{_overlap_fields(osel)}",
    ))
    return out


def measured_overlap_rows(rows: int, tracer=None):
    """Measured overlap-off vs overlap-on distributed SpMV on the local mesh.

    Builds the benchmark fine level's device form over all host devices,
    asserts both schedules match the host matvec, then times the pure
    exchange, a kernel-only run (exchange stubbed to zeros), and the full
    SpMV under both schedules.  The derived ``exposed_frac`` is the measured
    exchange time left visible in the full run: ``(t_full - t_kernel)/t_x``
    clamped to [0, 1].  Full-SpMV timings recorded to ``tracer`` carry
    ``pure_exchange=False`` so they never enter wire-rate calibration.
    """
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)

    from repro.core import default_plan_cache, time_executor
    from repro.sparse import (
        make_distributed_spmv,
        pack_vector,
        unpack_vector,
    )

    n_procs = jax.device_count()
    mesh = jax.make_mesh((n_procs,), ("proc",))
    topo = bench_topology(n_procs)
    A = hierarchy_for(min(rows, 65_536)).levels[0].A
    part = partition_csr(A, n_procs)
    cache = default_plan_cache()
    coll = cache.collective(part.pattern, topo, "auto",
                            value_bytes=VALUE_BYTES, params=LASSEN)
    exchange = cache.executor(part.pattern, topo, mesh, "proc", "auto",
                              value_bytes=VALUE_BYTES, params=LASSEN)
    ell = partitioned_to_device(part)
    osel = select_spmv_overlap(
        part, plan_time(coll.plan, LASSEN), value_bytes=VALUE_BYTES
    )

    def kernel_only_exchange(v):
        # same gather geometry, no wire: isolates the kernel time
        return jnp.zeros((ell.n_procs, ell.ghost_pad, 1), v.dtype)

    fns = {
        "kernel_only": jax.jit(make_distributed_spmv(
            ell, mesh, "proc", kernel_only_exchange, overlap=False)),
        "off": jax.jit(make_distributed_spmv(
            ell, mesh, "proc", exchange, overlap=False)),
        "on": jax.jit(make_distributed_spmv(
            ell, mesh, "proc", exchange, overlap=True)),
    }

    # equivalence gate before any timing
    rng = np.random.default_rng(11)
    x = rng.normal(size=A.ncols)
    want = A.matvec(x)
    xg = jnp.asarray(pack_vector(part.col_offsets, ell.in_pad, x))
    for mode in ("off", "on"):
        got = unpack_vector(part.offsets, np.asarray(fns[mode](xg)))
        np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-12)

    t_x = time_executor(exchange, n_procs, ell.in_pad,
                        dtype=np.float64, iters=10, warmup=2)
    if tracer is not None:
        tracer.record_plan(coll.plan, t_x, label="spmv_overlap/exchange",
                           pure_exchange=True)
    times = {}
    for mode, fn in fns.items():
        times[mode] = _time_fn(fn, xg, iters=10, warmup=2)
        if tracer is not None and mode != "kernel_only":
            tracer.record_plan(
                coll.plan, times[mode], label=f"spmv_overlap/{mode}",
                pure_exchange=False,
            )
    t_k = times["kernel_only"]

    def exposed_frac(t_full: float) -> float:
        if t_x <= 0.0:
            return 0.0
        return min(max((t_full - t_k) / t_x, 0.0), 1.0)

    geom = f"rows={A.nrows}|n_procs={n_procs}|ghost_pad={ell.ghost_pad}"
    out = [
        ("spmv_overlap/measured/exchange", t_x * 1e6,
         f"kind=measured-device|{geom}"),
        ("spmv_overlap/measured/kernel_only", times["kernel_only"] * 1e6,
         f"kind=measured-device|{geom}"),
    ]
    modeled_exposed = {
        "off": osel.exchange_s,
        "on": max(0.0, osel.exchange_s - osel.local_s),
    }
    for mode in ("off", "on"):
        out.append((
            f"spmv_overlap/measured/{mode}", times[mode] * 1e6,
            f"kind=measured-device|overlap={mode}"
            f"|exposed_frac={exposed_frac(times[mode]):.4f}"
            f"|modeled_exposed_us={modeled_exposed[mode] * 1e6:.3f}"
            f"|modeled_mode={osel.mode}|{geom}",
        ))
    return out
