"""One run of a cell with the program's own instrumentation on: where the
V-cycle step's device time goes by level, phase and kind, what the host
did while the device was idle, and where the set-up's seconds go.

    python3 bench/scoped.py --workload <cell> --seed <n> --seconds <s> \\
        [--obs 0|1] [--trace 0|1] [--keep-trace <dir>]

It runs what ``bench/run.py`` runs up to the end of the window, with its
functions (operator, set-up, warm-up, window), and checks nothing against
the reference.  ``--obs 1`` enables ``repro.obs`` before the set-up: its
spans, JAX compile counters and ``py/gc`` spans are recorded, and with
``--trace 1`` they land in the profiler trace beside the device's
operations, which ``bench.scopes`` reduces with the compiled step's HLO
text (taken after the window).  ``--obs 0 --trace 0`` is the benchmark's
own untraced path, the baseline that prices the instrumentation.
``--keep-trace`` copies the trace file there.  Prints one JSON object,
with the per-V-cycle readings under ``per_vcycle``.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as bench, scopes, work, xplane  # noqa: E402
from bench.peaks import peaks  # noqa: E402


def compiles(obs, by: str = "event") -> dict:
    """JAX compile events so far: the ``by`` label's value (``event``, or
    ``fun`` for the function traced, lowered or compiled) -> [events,
    seconds]."""
    out: dict = {}
    for name, i in (("jax/compiles", 0), ("jax/compile_seconds", 1)):
        for labels, v in obs.counter(name)._series.items():
            out.setdefault(dict(labels)[by], [0, 0.0])[i] += v
    return out


def spmv_bytes(levels: list, solver: dict, vb: int) -> list:
    """Bytes of each level's SpMVs in one step: its A's applications, R
    and P once (``bench.work``'s rule)."""
    out = []
    for k, lv in enumerate(levels):
        b = work.applications(k, len(levels), solver) * work.spmv_bytes(
            lv["nnz"], lv["n"], lv["n"], vb)
        if "nc" in lv:
            b += work.spmv_bytes(lv["nnz_r"], lv["n"], lv["nc"], vb)
            b += work.spmv_bytes(lv["nnz_p"], lv["nc"], lv["n"], vb)
        out.append(b)
    return out


def per_vcycle(red: dict, levels: list, solver: dict, vb: int, n_chips: int,
               device_kind: str, n_vcycles: int) -> dict:
    """The readings of one traced window, per V-cycle step and averaged
    over the chips: SpMV time and its HBM roofline share, the levels below
    the finest, the exchange, the loop's idle time, and a table by level."""
    hbm = n_chips * peaks(device_kind)["hbm_bytes_per_s"]
    ms = 1e3 / n_vcycles
    bytes_ = spmv_bytes(levels, solver, vb)
    table = []
    for k, b in enumerate(bytes_):
        kinds = red["level_kind_s"].get(f"L{k}", {})
        spmv = kinds.get("spmv", 0.0) * ms
        table.append({
            "level": k, "ms": red["level_s"].get(f"L{k}", 0.0) * ms,
            "spmv_ms": spmv, "exchange_ms": kinds.get("exchange", 0.0) * ms,
            "other_ms": kinds.get("other", 0.0) * ms, "spmv_bytes": b,
            "spmv_roofline": 100 * b / hbm * 1e3 / spmv if spmv else None})
    spmv = red["kind_s"]["spmv"] * ms
    return {
        "spmv_device_ms": spmv,
        "spmv_hbm_roofline": 100 * sum(bytes_) / hbm * 1e3 / spmv
        if spmv else None,
        "coarse_levels_ms": sum(row["ms"] for row in table[1:]),
        "exchange_device_ms": red["kind_s"]["exchange"] * ms or None,
        "vcycle_gap_ms": red["loop_idle_s"] * ms,
        "outer_ms": red["level_s"].get("outer", 0.0) * ms,
        "unscoped_share": red["level_s"].get(scopes.UNSCOPED, 0.0)
        / red["busy_s"],
        "levels": table,
    }


def measure(cell, seed: int, seconds: float, obs_on: bool, trace: bool,
            keep: str, devices: list) -> dict:
    import jax
    import numpy as np

    from repro.obs import default_obs

    obs = default_obs()
    if obs_on:
        obs.enable()
    bench.configure_jax()
    arrays = bench.operator(cell.cfg)
    n = len(arrays[0]) - 1
    h, dh, host_s, device_s = bench.set_up(cell.cfg, devices, arrays)
    vcycle_s = bench.warm_up(dh, cell, seed, n)
    spans0 = {k: list(v) for k, v in obs.spans.totals.items()}
    comp0 = compiles(obs)
    gc0 = [g["collections"] for g in gc.get_stats()]
    trace_dir = bench.OUT / "scoped-trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    t_window = time.perf_counter()
    solves, window_s = bench.run_window(dh, cell, seed, seconds, vcycle_s, n)
    if trace:
        jax.profiler.stop_trace()
    n_vcycles = sum(len(s["hist"]) for s in solves)
    spans1 = obs.spans.totals
    rec = {
        "cell": cell.name, "seed": seed, "obs": obs_on, "trace": trace,
        "device_kind": devices[0].device_kind, "n_chips": len(devices),
        "host_setup_s": host_s, "device_setup_s": device_s,
        "setup_s": t_window - bench.T_START, "window_s": window_s,
        "n_solves": len(solves), "n_vcycles": n_vcycles,
        "window_s_per_vcycle": window_s / n_vcycles,
        "solve_s": bench.DIGITS * window_s / bench.digits(solves),
        "spans_setup": spans0,
        "spans_window": {k: [v[0] - spans0.get(k, [0, 0.0])[0],
                             v[1] - spans0.get(k, [0, 0.0])[1]]
                         for k, v in spans1.items()
                         if v[0] > spans0.get(k, [0])[0]},
        "compiles_setup": comp0,
        "compiles_setup_by_fun": dict(sorted(
            compiles(obs, "fun").items(), key=lambda kv: -kv[1][1])[:8]),
        "compiles_window": {k: [v[0] - comp0.get(k, [0, 0.0])[0],
                                v[1] - comp0.get(k, [0, 0.0])[1]]
                            for k, v in compiles(obs).items()},
        "gc_collections_window": [g["collections"] - c for g, c in
                                  zip(gc.get_stats(), gc0)],
    }
    if obs_on:
        rec["step_build_s"] = spans0.get("amg/step_program", [0, 0.0])[1]
        rec["compile_s"] = sum(v[1] for v in comp0.values())
    if trace:
        path = xplane.find(str(trace_dir))
        if keep:
            Path(keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, keep)
        x = dh.x_device
        b = jax.device_put(np.zeros(x.shape, x.dtype), x.sharding)
        hlo = dh._device_step().lower(dh._consts, x, b).compile().as_text()
        levels = bench.level_sizes(h, dh)
        red = scopes.read(str(trace_dir), hlo)
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec["scopes"] = red
        rec["per_vcycle"] = per_vcycle(
            red, levels, cell.cfg["solver"],
            np.dtype(cell.cfg["dtype"]).itemsize, len(devices),
            devices[0].device_kind, n_vcycles)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--obs", type=int, choices=(0, 1), default=1)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep-trace", default="")
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    devices = bench.require_devices(cell.chips)
    rec = measure(cell, args.seed, args.seconds, bool(args.obs),
                  bool(args.trace), args.keep_trace, devices)
    print(json.dumps(rec, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
