"""Benchmark harness: one function per paper table/figure + roofline report.

Prints ``name,us_per_call,derived`` CSV (and optionally writes the same rows
as JSON).  Network times are *modeled* (locality-aware max-rate, Lassen
parameters) — message counts and bytes are exact plan quantities; rows are
tagged with kind=measured-host / measured-device / modeled-lassen /
exact-plan / dryrun-roofline accordingly.

    PYTHONPATH=src python -m benchmarks.run                 # full paper problem
    PYTHONPATH=src python -m benchmarks.run --rows 65536    # smaller/faster
    PYTHONPATH=src python -m benchmarks.run --smoke         # CI smoke: tiny
        # problem, every section must succeed (exceptions are fatal), rows
        # written to benchmarks/results/smoke.json for artifact upload

``REPRO_BENCH_ROWS`` is honored when ``--rows`` is not given.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

SMOKE_ROWS = 4096
SMOKE_PROCS = 64          # modeled process count for the smoke problem
SCHEMA_VERSION = 2        # results-JSON schema (bump on layout changes)


def _git_sha() -> str | None:
    """Best-effort commit stamp so CI artifacts from different PRs are
    comparable; None outside a git checkout."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=5,
            cwd=pathlib.Path(__file__).parent,
        )
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else None
    except Exception:
        return None


def measured_exchange_rows(rows: int, tracer=None):
    """Per-level MEASURED device exchange (auto-selected strategy) on the
    local host-platform mesh; a small problem keeps setup fast.  kind=
    measured-device distinguishes these from the modeled network rows.
    ``tracer`` records every timing for the --calibrate fit (so the
    calibration section reuses these measurements instead of re-timing)."""
    import jax

    # measured exchanges must move 8-byte values to be comparable with the
    # VALUE_BYTES=8 plan model; without this jnp silently downcasts to f32
    jax.config.update("jax_enable_x64", True)

    from repro.core import LASSEN

    from .amg_comm import level_selection, measured_device_exchange

    bench_rows = min(rows, 65_536)
    n_procs = jax.device_count()
    # one machine model for BOTH the selector report and the measured run,
    # so the strategy column and modeled_us describe the same choice
    params = LASSEN
    selected = {
        lvl: rep
        for lvl, _chosen, rep in level_selection(bench_rows, n_procs, params)
    }
    out = []
    for lvl, strategy, secs in measured_device_exchange(
        bench_rows, n_procs, params=params, tracer=tracer
    ):
        rep = selected.get(lvl)
        modeled = (f"modeled_us={rep.modeled_times[strategy] * 1e6:.1f}"
                   if rep and strategy in rep.modeled_times else "")
        out.append(
            (f"measured_exchange/L{lvl}", secs * 1e6,
             f"kind=measured-device|strategy={strategy}|{modeled}")
        )
    return out


def setup_exchange_modeled(rows: int, n_procs: int):
    """Setup-phase SpGEMM gathers, standard vs aggregated (modeled)."""
    from .amg_comm import setup_exchange_rows

    return setup_exchange_rows(min(rows, 65_536), n_procs)


def measured_setup_exchange_rows(rows: int, tracer=None):
    """MEASURED setup-phase gather exchanges on the local mesh."""
    import jax

    jax.config.update("jax_enable_x64", True)

    from .amg_comm import measured_setup_exchange

    out = []
    for label, strategy, secs in measured_setup_exchange(
        min(rows, 65_536), tracer=tracer
    ):
        out.append(
            (f"measured_setup_exchange/{label}", secs * 1e6,
             f"kind=measured-device|strategy={strategy}|")
        )
    return out


def spmv_kernel_rows(rows: int):
    """ELL SpMV kernel: measured CPU-reference / Pallas-interpret timings
    with equivalence asserted."""
    from .spmv_kernel import measured_rows

    return measured_rows(rows)


def spmv_overlap_rows(rows: int, n_procs: int, tracer=None):
    """Exchange/compute overlap: deterministic modeled decisions (per level
    + paper-scale fine level, which must auto-select ``on``) and measured
    overlap-off vs overlap-on distributed SpMV with equivalence asserted;
    full-SpMV tracer samples carry pure_exchange=False."""
    from .spmv_kernel import measured_overlap_rows, overlap_rows

    return overlap_rows(rows, n_procs) + measured_overlap_rows(rows, tracer)


def dense_comm_rows(smoke: bool, tracer=None):
    """Dense plan-based collectives (allreduce/allgatherv/reduce_scatter):
    deterministic Section-5 selection rows at paper scale (hier must beat
    ring — the dense/select/* gate) plus measured 8-device executions with
    jnp-reference equivalence asserted; pure_exchange samples feed the
    --calibrate fit."""
    from .dense_comm import dense_rows

    return dense_rows(smoke, tracer)


def elastic_replan_rows(rows: int):
    """Elastic re-plan cost (cold setup vs shrink vs warm grow-back vs
    straggler rebalance) through one plan cache: measured-host wall times
    plus exact-plan cache miss/hit deltas — grow_warm is gated at 0
    misses (the warm-resize contract)."""
    from .elastic_bench import elastic_rows

    return elastic_rows(rows)


def moe_comm_rows(smoke: bool, tracer=None):
    """MoE dispatch exchange: modeled per-mode comparison on a paper-scale
    EP group plus MEASURED jitted dispatch (all transports + auto) on the
    local mesh, through the plan/executor cache."""
    from .moe_comm import measured_moe_dispatch, modeled_dispatch_rows

    if smoke:
        rows = modeled_dispatch_rows(tokens_per_lane=256, pods=2,
                                     lanes_per_pod=8)
        rows += measured_moe_dispatch(iters=3, warmup=1, tracer=tracer)
    else:
        rows = modeled_dispatch_rows()
        rows += measured_moe_dispatch(tracer=tracer)
    return rows


def calibration_rows(rows: int, out_dir: pathlib.Path, smoke: bool,
                     tracer=None):
    """The measure -> fit -> re-select loop (ROADMAP's measured-vs-modeled
    calibration item), as one benchmark section.

    Fits MachineParams (``repro.profile.calibrate``) from the trace the
    measured sections recorded earlier in this run (``tracer`` — the
    exchanges are timed once, not re-run), then re-runs Section-5
    selection under the *fitted* rates and reports it side by side with
    the shipped-constant selection — flagging every level/mode where the
    choice flips.  Standalone use (no pre-filled tracer) measures the
    per-level AMG and setup-phase gather exchanges itself.  The trace and
    the fitted params are written as JSON next to the results artifact.
    Non-finite fitted params or an unbounded residual raise (fatal in
    --smoke: the CI calibration gate).
    """
    import jax

    jax.config.update("jax_enable_x64", True)

    import numpy as np

    from repro.core import LASSEN
    from repro.models.moe import STRATEGY_OF_MODE, select_moe_mode
    from repro.profile import TraceRecorder, fit_trace, selection_flips

    from .amg_comm import (
        VALUE_BYTES,
        bench_topology,
        level_patterns,
        measured_device_exchange,
        measured_setup_exchange,
    )
    from .moe_comm import dispatch_plan, measured_moe_dispatch

    bench_rows = min(rows, 65_536)
    n_procs = jax.device_count()
    shipped = LASSEN
    if tracer is None:
        tracer = TraceRecorder()
    if not tracer.merged_rate_samples():
        # standalone: the measured sections did not run first — time the
        # pure exchanges here (MoE dispatch rows are reporting-only:
        # pure_exchange=False, they include expert compute)
        measured_device_exchange(bench_rows, n_procs, params=shipped,
                                 tracer=tracer)
        measured_setup_exchange(bench_rows, params=shipped, tracer=tracer)
        measured_moe_dispatch(iters=2, warmup=1, tracer=tracer)

    # --- fit --------------------------------------------------------------
    result = fit_trace(tracer, name=f"fitted-{shipped.name}", ref=shipped)
    fitted = result.params
    gof = result.gof
    # artifacts FIRST: a diverged fit is exactly when the trace must be
    # inspectable, so the JSONs exist even if the gate below raises
    out_dir.mkdir(parents=True, exist_ok=True)
    tracer.save(out_dir / "trace.json")
    result.save(out_dir / "fitted_params.json")
    # one definition of "converged" (CalibrationResult: gof flag + finite
    # params) plus a residual bound — the CI calibration gate
    if not result.converged or not np.isfinite(gof["rel_rmse"]) \
            or gof["rel_rmse"] > 10.0:
        raise RuntimeError(
            f"calibration fit did not converge: "
            f"converged={result.converged} gof={gof}"
        )

    out = []
    s = tracer.summary()
    out.append((
        "calibrate/trace", 0.0,
        f"kind=measured-device|samples={s['samples']}"
        f"|pure={s['pure_samples']}|patterns={s['patterns']}",
    ))
    for f in ("alpha_intra", "beta_intra", "alpha_inter", "beta_inter",
              "region_injection_bw"):
        a, b = float(getattr(shipped, f)), float(getattr(fitted, f))
        out.append((
            f"calibrate/params/{f}", 0.0,
            f"kind=measured-fit|shipped={a:.4g}|fitted={b:.4g}"
            f"|ratio={b / a:.3f}",
        ))
    out.append((
        "calibrate/fit", 0.0,
        f"kind=measured-fit|n={result.n_samples}"
        f"|rel_rmse={gof['rel_rmse']:.4f}|r2={gof['r2']:.4f}"
        f"|iters={int(gof['outer_iters'])}"
        f"|converged={bool(gof['converged'])}",
    ))

    # --- re-select: Section-5 under fitted vs shipped rates ---------------
    labeled = [
        (f"L{lvl}", pat)
        for lvl, (pat, _n) in enumerate(level_patterns(bench_rows, n_procs))
    ]
    flip_rows = selection_flips(labeled, bench_topology(n_procs), shipped,
                                fitted, value_bytes=VALUE_BYTES)
    flips = 0
    for r in flip_rows:
        flips += r["flip"] == "yes"
        out.append((
            f"calibrate/selection/{r['label']}", 0.0,
            f"kind=measured-fit|shipped={r['shipped']}"
            f"|fitted={r['fitted']}|flip={r['flip']}",
        ))
    # MoE dispatch mode selection under both parameter sets
    geom = dispatch_plan(tokens_per_lane=256, pods=2, lanes_per_pod=8) \
        if smoke else dispatch_plan()
    vb = 4096 * 2
    mode_s, _ = select_moe_mode(geom, 256 if smoke else 1024, vb, shipped)
    mode_f, _ = select_moe_mode(geom, 256 if smoke else 1024, vb, fitted)
    out.append((
        "calibrate/selection/moe", 0.0,
        f"kind=measured-fit|shipped={mode_s}|fitted={mode_f}"
        f"|flip={'yes' if mode_s != mode_f else 'no'}"
        f"|strategies={STRATEGY_OF_MODE[mode_s]}->"
        f"{STRATEGY_OF_MODE[mode_f]}",
    ))
    out.append((
        "calibrate/flips", float(flips),
        f"kind=measured-fit|levels={len(flip_rows)}"
        f"|topo={bench_topology(n_procs).n_regions}regions",
    ))

    return out


def verify_rows(rows: int):
    """Wall time of the static plan/kernel verifier (``repro.verify``) —
    what ``REPRO_VERIFY=1`` adds on top of plan construction.  Each row
    times one full verification sweep (structure + conservation + device
    plan + layouts + kernel budgets) over plans built beforehand, so the
    number is the verifier alone; kind=measured-host rows are
    band-compared by ``benchmarks.compare``, never exact."""
    import jax

    from repro.amg import DistributedHierarchy, build_hierarchy, diffusion_2d
    from repro.configs import reduced
    from repro.core import PlanCache
    from repro.models.moe import moe_plan_for
    from repro.verify import verify_hierarchy, verify_moe_dispatch

    n = max(int(round(rows ** 0.5)), 16)
    n_procs = jax.device_count()
    mesh = jax.make_mesh((n_procs,), ("proc",))
    A = diffusion_2d(n, n)
    cache = PlanCache()
    out = []

    dh = DistributedHierarchy.setup(
        build_hierarchy(A), mesh, procs_per_region=4, cache=cache,
    )
    t0 = time.perf_counter()
    counts = verify_hierarchy(dh)
    dt = time.perf_counter() - t0
    out.append((
        "verify/wall_seconds/hierarchy", dt * 1e6,
        f"kind=measured-host|seconds={dt:.4f}"
        f"|levels={counts.get('levels', 0)}"
        f"|collectives={counts.get('collectives', 0)}"
        f"|partitions={counts.get('partitions', 0)}",
    ))

    cfg = reduced("mixtral-8x7b")
    moe_mesh = jax.make_mesh((1, n_procs), ("data", "model"))
    modes = ("a2a", "hier", "hier_dedup")
    plans = [moe_plan_for(cfg, moe_mesh, 64, mode=m, cache=cache)
             for m in modes]
    t0 = time.perf_counter()
    for plan in plans:
        verify_moe_dispatch(plan, 64)
    dt = time.perf_counter() - t0
    out.append((
        "verify/wall_seconds/moe_dispatch", dt * 1e6,
        f"kind=measured-host|seconds={dt:.4f}|modes={len(modes)}",
    ))
    return out


def obs_rows(rows: int, out_dir: pathlib.Path):
    """Telemetry layer (``repro.obs``) smoke: deterministic plan-cache
    counter rows, a deterministic span-count row, measured disabled-path
    overhead, and a Perfetto trace artifact.

    The ``obs/plan_cache/*`` and ``obs/spans/*`` rows are kind=exact-plan
    and **exactly** gated by ``benchmarks.compare`` (rtol=0 for ``obs/*``):
    the same program must produce the same hit/miss/span counts on every
    machine.  The pattern set is built host-side against a fixed
    ``Topology(8, 4)``, independent of the real device count."""
    import numpy as np

    from repro.core import CommPattern, PlanCache, Topology
    from repro.obs import Obs, default_obs, now as _now

    out = []
    obs = default_obs()
    was_enabled = obs.enabled
    obs.reset().enable()
    try:
        topo = Topology(8, 4)
        n_per = max(rows // topo.n_procs, 16)
        rng = np.random.default_rng(0)
        offsets = np.arange(topo.n_procs + 1) * n_per
        patterns = []
        for seed in range(4):
            rng = np.random.default_rng(seed)
            needs = [np.sort(rng.choice(topo.n_procs * n_per, size=12,
                                        replace=False))
                     for _ in range(topo.n_procs)]
            patterns.append(CommPattern.from_block_partition(needs, offsets))

        cache = PlanCache()
        before = obs.snapshot()
        for pat in patterns:                      # cold: every plan misses
            for strat in ("standard", "full"):
                cache.collective(pat, topo, strat)
        cold = obs.delta(before)["counters"].get("plan_cache/misses", [])
        cold_misses = sum(r["value"] for r in cold
                          if r["labels"].get("ns") == "collective")
        before = obs.snapshot()
        for pat in patterns:                      # warm: every plan hits
            for strat in ("standard", "full"):
                cache.collective(pat, topo, strat)
        d = obs.delta(before)["counters"]
        warm_hits = sum(r["value"]
                        for r in d.get("plan_cache/hits", [])
                        if r["labels"].get("ns") == "collective")
        warm_misses = sum(r["value"]
                          for r in d.get("plan_cache/misses", [])
                          if r["labels"].get("ns") == "collective")
        out.append((
            "obs/plan_cache/cold_misses", cold_misses,
            f"kind=exact-plan|patterns={len(patterns)}|strategies=2",
        ))
        out.append((
            "obs/plan_cache/warm_hits", warm_hits,
            f"kind=exact-plan|warm_misses={warm_misses:.0f}",
        ))

        # span determinism: a fixed-iteration loop emits exactly that many
        # spans (the solver's vcycle_iter span contract, mesh-free here)
        iters = 5
        for it in range(iters):
            with obs.span("bench/obs_iter", iter=it):
                pass
        n_spans = sum(1 for e in obs.spans.events(kind="span")
                      if e.name == "bench/obs_iter")
        out.append((
            "obs/spans/loop_iters", float(n_spans),
            f"kind=exact-plan|iters={iters}",
        ))

        # disabled-path overhead: counter inc + span open on a DISABLED
        # private Obs, reported as ns/op (measured, band-compared)
        off = Obs()
        c_off = off.counter("bench/off", "")
        n = 200_000
        t0 = _now()
        for _ in range(n):
            c_off.inc()
        dt_counter = (_now() - t0) / n
        t0 = _now()
        for _ in range(n):
            off.span("bench/off")
        dt_span = (_now() - t0) / n
        out.append((
            "obs/overhead/counter_disabled", dt_counter * 1e6,
            f"kind=measured-host|ns_per_op={dt_counter * 1e9:.1f}",
        ))
        out.append((
            "obs/overhead/span_disabled", dt_span * 1e6,
            f"kind=measured-host|ns_per_op={dt_span * 1e9:.1f}",
        ))

        # the Perfetto artifact CI uploads next to the results JSON
        out_dir.mkdir(parents=True, exist_ok=True)
        trace_path = out_dir / "obs_trace.json"
        obs.export_perfetto(trace_path)
        out.append((
            "obs/export/trace_events",
            float(len(obs.to_perfetto()["traceEvents"])),
            f"kind=measured-host|path={trace_path.name}",
        ))
    finally:
        if not was_enabled:
            obs.disable()
    return out


def build_sections(rows: int, smoke: bool, tracer=None):
    """Section list; ``tracer`` (set by --calibrate) makes the measured
    sections record their timings so the calibration fit reuses them
    instead of re-timing the same exchanges."""
    from . import paper_figs, roofline_report

    if smoke:
        # tiny problem, reduced modeled process count / rows-per-proc:
        # every section of the full harness is exercised, nothing takes
        # longer than seconds
        return [
            ("fig6", lambda: paper_figs.fig6_graph_creation(rows)),
            ("fig12", lambda: paper_figs.fig12_strong_scaling(rows)),
            ("fig13", lambda: paper_figs.fig13_weak_scaling(16)),
            ("fig7", lambda: paper_figs.fig7_crossover(rows, SMOKE_PROCS)),
            ("fig8_9",
             lambda: paper_figs.fig8_9_message_counts(rows, SMOKE_PROCS)),
            ("fig10",
             lambda: paper_figs.fig10_message_sizes(rows, SMOKE_PROCS)),
            ("fig11",
             lambda: paper_figs.fig11_per_level_cost(rows, SMOKE_PROCS)),
            ("amg", lambda: paper_figs.amg_solver_convergence(rows)),
            ("setup_exchange",
             lambda: setup_exchange_modeled(rows, SMOKE_PROCS)),
            ("spmv_kernel", lambda: spmv_kernel_rows(rows)),
            ("spmv_overlap",
             lambda: spmv_overlap_rows(rows, SMOKE_PROCS, tracer)),
            ("measured_exchange",
             lambda: measured_exchange_rows(rows, tracer)),
            ("measured_setup_exchange",
             lambda: measured_setup_exchange_rows(rows, tracer)),
            ("moe_comm", lambda: moe_comm_rows(smoke=True,
                                               tracer=tracer)),
            ("dense_comm", lambda: dense_comm_rows(smoke=True,
                                                   tracer=tracer)),
            ("elastic", lambda: elastic_replan_rows(rows)),
            ("roofline", roofline_report.rows),
        ]
    return [
        ("fig6", lambda: paper_figs.fig6_graph_creation(rows)),
        ("fig7", lambda: paper_figs.fig7_crossover(rows)),
        ("fig8_9", lambda: paper_figs.fig8_9_message_counts(rows)),
        ("fig10", lambda: paper_figs.fig10_message_sizes(rows)),
        ("fig11", lambda: paper_figs.fig11_per_level_cost(rows)),
        ("fig12", lambda: paper_figs.fig12_strong_scaling(rows)),
        ("fig13", lambda: paper_figs.fig13_weak_scaling()),
        ("amg", paper_figs.amg_solver_convergence),
        ("setup_exchange", lambda: setup_exchange_modeled(rows, 256)),
        ("spmv_kernel", lambda: spmv_kernel_rows(rows)),
        ("spmv_overlap", lambda: spmv_overlap_rows(rows, 256, tracer)),
        ("measured_exchange",
         lambda: measured_exchange_rows(rows, tracer)),
        ("measured_setup_exchange",
         lambda: measured_setup_exchange_rows(rows, tracer)),
        ("moe_comm", lambda: moe_comm_rows(smoke=False, tracer=tracer)),
        ("dense_comm", lambda: dense_comm_rows(smoke=False, tracer=tracer)),
        ("elastic", lambda: elastic_replan_rows(rows)),
        ("roofline", roofline_report.rows),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--rows", type=int,
        default=int(os.environ.get("REPRO_BENCH_ROWS", 524_288)),
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny problem, strict mode: any section exception is fatal, "
        "results JSON written (CI gate for the perf paths)",
    )
    ap.add_argument(
        "--out", default=None,
        help="write results JSON here (default in --smoke mode: "
        "benchmarks/results/smoke.json)",
    )
    ap.add_argument(
        "--verify", action="store_true",
        help="time the static plan/kernel verifier (repro.verify) over the "
        "smoke hierarchy + MoE plans and report verify/wall_seconds/* rows "
        "(always on in --smoke)",
    )
    ap.add_argument(
        "--calibrate", action="store_true",
        help="run the measure->fit->re-select calibration loop: measure "
        "real exchanges, fit MachineParams (repro.profile), rerun the "
        "Section-5 selector under fitted rates, report any mode flips; "
        "writes trace.json + fitted_params.json next to the results JSON",
    )
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import configure_compile_cache

    print(f"# compile cache: {configure_compile_cache()}", file=sys.stderr)
    rows = SMOKE_ROWS if args.smoke else args.rows
    out_path = args.out
    if out_path is None and args.smoke:
        out_path = str(
            pathlib.Path(__file__).parent / "results" / "smoke.json"
        )

    t_start = time.time()
    collected = []
    failures = []
    tracer = None
    if args.calibrate:
        from repro.profile import TraceRecorder

        tracer = TraceRecorder()   # shared: measured sections feed the fit
    art_dir = (pathlib.Path(out_path).parent if out_path
               else pathlib.Path(__file__).parent / "results")
    sections = build_sections(rows, args.smoke, tracer)
    if args.smoke or args.verify:
        sections.append(("verify", lambda: verify_rows(rows)))
    sections.append(("obs", lambda: obs_rows(rows, art_dir)))
    if args.calibrate:
        sections.append(
            ("calibrate",
             lambda: calibration_rows(rows, art_dir, args.smoke, tracer))
        )
    print("name,us_per_call,derived")
    for section, fn in sections:
        t0 = time.time()
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.2f},{derived}")
                collected.append(
                    {"name": name, "us_per_call": us, "derived": derived}
                )
        except Exception as e:  # keep the harness running (strict in smoke)
            if args.smoke:
                raise
            failures.append(section)
            print(f"{section}/ERROR,0.00,kind=ERROR|{type(e).__name__}:"
                  f"{str(e)[:120]}")
        sys.stdout.flush()
        print(f"# section {section} took {time.time() - t0:.1f}s",
              file=sys.stderr)
    total = time.time() - t_start
    print(f"# total {total:.1f}s", file=sys.stderr)

    if out_path:
        payload = {
            "schema_version": SCHEMA_VERSION,
            "git_sha": _git_sha(),
            "rows_param": rows,
            "smoke": args.smoke,
            "total_seconds": total,
            "failed_sections": failures,
            "results": collected,
        }
        p = pathlib.Path(out_path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(payload, indent=2))
        print(f"# results JSON: {p}", file=sys.stderr)
    if failures:
        print(f"# failed sections: {', '.join(failures)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
