"""Flat vs column-blocked SpMV kernel benchmark rows.

Two row families, matching the repo's modeled/measured labeling:

* :func:`selection_rows` — DETERMINISTIC modeled-VMEM footprints and the
  resulting flat-vs-blocked choice, per AMG level of the benchmark problem
  plus a paper-scale synthetic fine level (per-device x far beyond VMEM)
  that must come out ``blocked``.  These rows are exact arithmetic on block
  geometry (no timing) and are gated tightly by ``benchmarks.compare``.

* :func:`measured_rows` — MEASURED wall-clock of both kernel variants on
  this host: the jnp reference path (CPU backend) on the benchmark fine
  level, and the real Pallas kernels in interpret mode on a small problem.
  Before timing, both variants are asserted equivalent to the host matvec —
  the benchmark doubles as an equivalence gate in CI smoke.

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
    default_spmv_vmem_limit,
    overlap_decision,
    partition_csr,
    partitioned_to_ell,
    partitioned_to_ell_blocked,
    select_spmv_kernel,
    select_spmv_overlap,
    spmv_blocked_vmem_bytes,
    spmv_flat_vmem_bytes,
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


def _kib(b: int) -> str:
    return f"{b / 2 ** 10:.1f}"


def selection_rows(rows: int, n_procs: int):
    """Modeled footprint + variant choice per level and at paper scale."""
    out = []
    h = hierarchy_for(rows)
    for k, lvl in enumerate(h.levels):
        if lvl.A.nrows < n_procs:
            break
        part = partition_csr(lvl.A, n_procs)
        sel = select_spmv_kernel(part, value_bytes=VALUE_BYTES)
        out.append((
            f"spmv_kernel/select/L{k}", 0.0,
            f"kind=modeled-vmem|flat_kib={_kib(sel.flat_bytes)}"
            f"|blocked_kib={_kib(sel.blocked_bytes)}"
            f"|limit_kib={_kib(sel.limit_bytes)}|variant={sel.variant}",
        ))
    # paper-scale fine level from analytic geometry (the matrix itself is
    # never materialized): x footprint alone exceeds the threshold, so the
    # selector must fall over to the column-blocked kernel
    limit = default_spmv_vmem_limit()
    flat = spmv_flat_vmem_bytes(
        in_pad=PAPER_ROWS_PER_PROC, ghost_pad=PAPER_GHOST,
        k_local=PAPER_K, k_ghost=PAPER_K, value_bytes=VALUE_BYTES,
        rows=PAPER_ROWS_PER_PROC,
    )
    blocked = spmv_blocked_vmem_bytes(
        bucket_k=PAPER_K, value_bytes=VALUE_BYTES, rows=PAPER_ROWS_PER_PROC,
    )
    variant = "flat" if flat <= limit else "blocked"
    assert variant == "blocked", (flat, limit)  # paper scale MUST block
    out.append((
        "spmv_kernel/select/paper_fine", 0.0,
        f"kind=modeled-vmem|rows_per_proc={PAPER_ROWS_PER_PROC}"
        f"|flat_kib={_kib(flat)}|blocked_kib={_kib(blocked)}"
        f"|limit_kib={_kib(limit)}|variant={variant}",
    ))
    return out


def _time_fn(fn, x, iters: int, warmup: int) -> float:
    for _ in range(warmup):
        np.asarray(fn(x))
    t0 = time.perf_counter()
    for _ in range(iters):
        np.asarray(fn(x))
    return (time.perf_counter() - t0) / iters


def _single_proc_layouts(A, block_cols: int):
    """Both device layouts of an unpartitioned operator (1-proc partition:
    no ghosts, so the kernels are exercised in isolation)."""
    part = partition_csr(A, 1)
    return partitioned_to_ell(part), partitioned_to_ell_blocked(
        part, block_cols=block_cols
    )


def _check_and_time(A, block_cols: int, backend_name: str,
                    iters: int, warmup: int):
    """Assert flat == blocked == host matvec, then time both variants."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import use_backend
    from repro.kernels.spmv_ell.ops import spmv, spmv_blocked

    ell, bell = _single_proc_layouts(A, block_cols)
    rng = np.random.default_rng(7)
    x = rng.normal(size=A.ncols)
    want = A.matvec(x)

    xf = jnp.asarray(np.concatenate([x, [0.0]]))        # flat sentinel slot
    xb = np.zeros(bell.x_len)
    xb[: A.ncols] = x
    xb = jnp.asarray(xb)
    lc = jnp.asarray(ell.local_cols[0])
    lv = jnp.asarray(ell.local_vals[0])
    bc_ = jnp.asarray(bell.cols[0])
    bv = jnp.asarray(bell.vals[0])

    with use_backend(backend_name):
        flat_fn = jax.jit(lambda v: spmv(lc, lv, v))
        blocked_fn = jax.jit(
            lambda v: spmv_blocked(bc_, bv, v, bell.block_cols)
        )
        got_flat = np.asarray(flat_fn(xf))[: A.nrows]
        got_blocked = np.asarray(blocked_fn(xb))[: A.nrows]
        np.testing.assert_allclose(got_flat, want, rtol=1e-6, atol=1e-8)
        np.testing.assert_allclose(got_blocked, got_flat,
                                   rtol=1e-6, atol=1e-8)
        t_flat = _time_fn(flat_fn, xf, iters, warmup)
        t_blocked = _time_fn(blocked_fn, xb, iters, warmup)
    return t_flat, t_blocked, bell


def measured_rows(rows: int):
    """Measured flat/blocked timings: jnp reference path on the benchmark
    fine level, Pallas interpret mode on a small problem."""
    import jax

    # equivalence checks compare against the f64 host matvec
    jax.config.update("jax_enable_x64", True)
    out = []
    # -- CPU reference path on the fine level ------------------------------
    A = hierarchy_for(min(rows, 65_536)).levels[0].A
    t_flat, t_blocked, bell = _check_and_time(
        A, block_cols=512, backend_name="xla", iters=10, warmup=2
    )
    geom = (f"rows={A.nrows}|buckets={bell.n_buckets}"
            f"|bucket_k={bell.K}")
    out.append((
        "spmv_kernel/measured/flat_ref", t_flat * 1e6,
        f"kind=measured-host|backend=reference|{geom}",
    ))
    out.append((
        "spmv_kernel/measured/blocked_ref", t_blocked * 1e6,
        f"kind=measured-host|backend=reference|{geom}"
        f"|vs_flat={t_blocked / max(t_flat, 1e-12):.2f}x",
    ))
    # -- Pallas kernels in interpret mode (small: interpret is python) -----
    As = diffusion_2d(16, 16)
    t_flat, t_blocked, bell = _check_and_time(
        As, block_cols=64, backend_name="pallas_interpret",
        iters=2, warmup=1,
    )
    geom = f"rows={As.nrows}|buckets={bell.n_buckets}|bucket_k={bell.K}"
    out.append((
        "spmv_kernel/measured/flat_interpret", t_flat * 1e6,
        f"kind=measured-host|backend=pallas_interpret|{geom}",
    ))
    out.append((
        "spmv_kernel/measured/blocked_interpret", t_blocked * 1e6,
        f"kind=measured-host|backend=pallas_interpret|{geom}",
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

    Builds the benchmark fine level's blocked layout over all host devices,
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
    bell = partitioned_to_ell_blocked(part, block_cols=512)
    osel = select_spmv_overlap(
        part, plan_time(coll.plan, LASSEN), value_bytes=VALUE_BYTES
    )

    def kernel_only_exchange(v):
        # same gather geometry, no wire: isolates the kernel time
        return jnp.zeros((bell.n_procs, bell.ghost_pad, 1), v.dtype)

    fns = {
        "kernel_only": jax.jit(make_distributed_spmv(
            bell, mesh, "proc", kernel_only_exchange, overlap=False)),
        "off": jax.jit(make_distributed_spmv(
            bell, mesh, "proc", exchange, overlap=False)),
        "on": jax.jit(make_distributed_spmv(
            bell, mesh, "proc", exchange, overlap=True)),
    }

    # equivalence gate before any timing
    rng = np.random.default_rng(11)
    x = rng.normal(size=A.ncols)
    want = A.matvec(x)
    xg = jnp.asarray(pack_vector(part.col_offsets, bell.in_pad, x))
    for mode in ("off", "on"):
        got = unpack_vector(part.offsets, np.asarray(fns[mode](xg)))
        np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-12)

    t_x = time_executor(exchange, n_procs, bell.in_pad,
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

    geom = (f"rows={A.nrows}|n_procs={n_procs}|buckets={bell.n_buckets}"
            f"|local_buckets={bell.n_local_buckets}|ghost_pad={bell.ghost_pad}")
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
