"""Benchmark harness: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<name>.json``: the
operator's family, its sizes, the solver and the limits of the check) and
a traffic mix (``bench/traffic/<name>.json``); the per-layer metrics are
read by ``bench/metrics/<name>.py``.  All are found by name, so a new cell
adds files and entries and edits none of this.

A run: assemble the operator with the benchmark's own generator
(``bench/operators/<family>.py``), ``build_hierarchy``,
``DistributedHierarchy.setup`` on a mesh of the cell's chips with its
default choices, and a warm-up on a right-hand side the window does not use
(``warm_up``); then, for ``--seconds``, solves back to back from one
caller, each from x0 = 0 to the configuration's tolerance, the last one
bounded to end near the window's end.  After the window the program's state is freed
and the reference re-runs a sample of the solves (``bench.check``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end with ``--trace 0``,
per-layer with ``--trace 1``), ``device``, ``breakdown`` (traced runs) and
``check``, the compared numbers beside their limits, which also end
standard error.  With no TPU, or fewer chips than the cell asks for, it
exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = BENCH / "out"
# the checkout's root for ``bench``, its ``src`` for the program; the
# script's own directory is dropped so that no bench module shadows a
# module of the standard library
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != BENCH]

from bench import check, traffic as mixes, xplane  # noqa: E402
from bench.reference import Reference  # noqa: E402

#: decimal digits of relative-residual reduction that solve_s is quoted at
DIGITS = 8


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> SimpleNamespace:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic mix and the per-layer metrics it reports."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return SimpleNamespace(
        name=name, chips=int(cell["chips"]),
        cfg=load_config(entry["file"]),
        traffic=load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        end_to_end=[m for m in spec["end_to_end"]
                    if name in m.get("workloads", [name])],
        per_layer=[m for m in spec["per_layer"]
                   if name in m.get("workloads", [name])],
    )


def load_config(file: str) -> dict:
    return load_json(ROOT / file)


def require_devices(n: int) -> list:
    """The first ``n`` TPU chips; exits nonzero where there are none."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU found (JAX runs on "
                 f"{devices[0].platform!r}); the benchmark runs on a TPU only")
    if len(devices) < n:
        sys.exit(f"bench: {n} chips asked for, {len(devices)} found")
    return devices[:n]


def configure_jax() -> None:
    """f64 on; the persistent compile cache where JAX_COMPILATION_CACHE_DIR
    says, else at ``<checkout>/.jax_cache``."""
    import jax

    jax.config.update("jax_enable_x64", True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def operator(cfg: dict):
    """CSR arrays of the configuration's operator, from its family's file."""
    return load_module(BENCH / "operators" / f"{cfg['family']}.py").assemble(cfg)


def set_up(cfg: dict, devices: list, arrays):
    """``build_hierarchy`` and ``DistributedHierarchy.setup``, each timed."""
    import jax

    from repro.amg import DistributedHierarchy, build_hierarchy
    from repro.sparse.csr import CSR

    indptr, indices, data = arrays
    n = len(indptr) - 1
    s = cfg["solver"]
    t0 = time.perf_counter()
    h = build_hierarchy(CSR((n, n), indptr, indices, data),
                        max_levels=s["max_levels"], min_coarse=s["min_coarse"],
                        strength_theta=s["strength_theta"])
    t1 = time.perf_counter()
    mesh = jax.make_mesh((len(devices),), ("proc",), devices=devices)
    dh = DistributedHierarchy.setup(h, mesh)
    t2 = time.perf_counter()
    return h, dh, t1 - t0, t2 - t1


#: seconds of timed V-cycles the window's estimate of a V-cycle rests on
ESTIMATE_S = 2.4


def warm_up(dh, cell, seed: int, n: int) -> float:
    """A call of two V-cycles, to compile (or load) the step for both of
    its inputs (a placed vector, then the step's own output); then calls of
    three V-cycles, timed, at least two and until ``ESTIMATE_S`` seconds
    have passed.  The least third of a call is the window's estimate of a
    V-cycle: within a third of a call's overhead, and steady against a call
    that a host hiccup slowed."""
    b = mixes.rhs(cell.traffic, n, seed, mixes.WARM_UP)
    tol = float(cell.cfg["tol"])
    dh.solve(b, tol=tol, max_iters=2)
    spent, best, calls = 0.0, math.inf, 0
    while spent < ESTIMATE_S or calls < 2:
        calls += 1
        t0 = time.perf_counter()
        dh.solve(b, tol=tol, max_iters=3)
        t = time.perf_counter() - t0
        spent += t
        best = min(best, t / 3)
    return best


def run_window(dh, cell, seed: int, seconds: float, vcycle_s: float,
               n: int) -> tuple:
    """Solves back to back for ``seconds``: (solves, seconds measured from
    the first call's start to the last call's return).  A call that reaches
    its bound unconverged is the last: it was bounded to end the window."""
    import jax

    tol = float(cell.cfg["tol"])
    solves = []
    with jax.profiler.TraceAnnotation("bench/window"):
        b = mixes.rhs(cell.traffic, n, seed, 1)
        t0 = time.perf_counter()
        while True:
            cap = mixes.call_cap(cell.traffic,
                                 seconds - (time.perf_counter() - t0), vcycle_s)
            if cap == 0 and solves:
                break
            cap = max(cap, 2)
            with jax.profiler.TraceAnnotation("bench/solve"):
                x, hist = dh.solve(b, tol=tol, max_iters=cap)
            solves.append({"b": b, "x": x, "hist": hist})
            t1 = time.perf_counter()
            if len(hist) == cap and hist[-1] >= tol:
                break
            if mixes.call_cap(cell.traffic, seconds - (t1 - t0), vcycle_s):
                with jax.profiler.TraceAnnotation("bench/rhs"):
                    b = mixes.rhs(cell.traffic, n, seed, len(solves) + 1)
    return solves, t1 - t0


def digits(solves: list) -> float:
    """Decimal digits of relative-residual reduction the solves made, each
    to its last checked residual."""
    return float(sum(-math.log10(max(s["hist"][-1], 1e-300)) for s in solves))


def level_sizes(h, dh) -> list:
    """Per level: rows, nonzeros and plan messages of A, R and P."""
    def msgs(op):
        if op is None or not op.ell.ghost_pad:
            return 0
        t = op.coll.plan.stats.totals()
        return t["intra_msgs"] + t["inter_msgs"]

    out = []
    for hl, dl in zip(h.levels, dh.levels):
        lv = {"n": hl.A.nrows, "nnz": hl.A.nnz, "msgs_a": msgs(dl.A)}
        if dl.R is not None:
            lv.update(nc=hl.P.ncols, nnz_r=hl.R.nnz, nnz_p=hl.P.nnz,
                      msgs_r=msgs(dl.R), msgs_p=msgs(dl.P))
        out.append(lv)
    return out


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def measure(cell, seed: int, seconds: float, trace: bool,
            devices: list) -> dict:
    """One run of ``cell``: the result line's fields and the run record."""
    import jax

    configure_jax()
    arrays = operator(cell.cfg)
    n = len(arrays[0]) - 1
    h, dh, host_s, device_s = set_up(cell.cfg, devices, arrays)
    vcycle_s = warm_up(dh, cell, seed, n)
    trace_dir = OUT / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    t_window = time.perf_counter()
    solves, window_s = run_window(dh, cell, seed, seconds, vcycle_s, n)
    if trace:
        jax.profiler.stop_trace()
    run = SimpleNamespace(
        cell=cell.name, solver=cell.cfg["solver"], n_chips=len(devices),
        device_kind=devices[0].device_kind,
        value_bytes=np.dtype(cell.cfg["dtype"]).itemsize,
        host_setup_s=host_s, device_setup_s=device_s,
        setup_s=t_window - T_START, window_s=window_s, vcycle_s=vcycle_s,
        n_solves=len(solves), n_vcycles=sum(len(s["hist"]) for s in solves),
        digits=digits(solves), levels=level_sizes(h, dh),
        memory_peak_bytes=peak_bytes(devices), trace=None,
    )
    del h, dh
    gc.collect()
    if trace:
        run.trace = xplane.read(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    t0 = time.perf_counter()
    ref = Reference(*arrays, cell.cfg["solver"])
    sample = mixes.check_sample(cell.traffic,
                                [len(s["hist"]) for s in solves], seed)
    worst, failed = check.compare(ref, solves, sample, cell.cfg)
    run.check_s = time.perf_counter() - t0
    run.compared = sample
    run.histories = [s["hist"] for s in solves]
    return {"run": run, "worst": worst, "failed": failed}


def end_to_end(run) -> dict:
    return {
        "solve_s": DIGITS * run.window_s / run.digits,
        "amg_setup_s": run.host_setup_s + run.device_setup_s,
        "setup_s": run.setup_s,
    }


def result_line(cell, out: dict, trace: bool, devices: list) -> dict:
    run, worst = out["run"], out["worst"]
    checked = check.report(worst, cell.cfg)
    correct = out["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checked.values())
    if trace:
        values = {m["name"]: load_module(
            BENCH / "metrics" / f"{m['name']}.py").read(run)
            for m in cell.per_layer}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.per_layer if values[m["name"]] is not None}
    else:
        values = end_to_end(run)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": bool(correct), "attempted": run.n_solves,
            "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    line["check"] = checked
    return line


def write_record(run, line: dict, seed: int) -> None:
    """The run's details beside the result, in ``bench/out`` (ignored by
    git): every history, the level sizes, the trace reduction."""
    OUT.mkdir(exist_ok=True)
    rec = dict(vars(run), seed=seed, result=line)
    with open(OUT / f"{run.cell}.seed{seed}.json", "w") as f:
        json.dump(rec, f, default=float)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devices = require_devices(cell.chips)
    out = measure(cell, args.seed, args.seconds, bool(args.trace), devices)
    line = result_line(cell, out, bool(args.trace), devices)
    write_record(out["run"], line, args.seed)
    for k, c in line["check"].items():
        print(f"check {k}: {c['value']:.6e} (limit {c['limit']:.1e})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
