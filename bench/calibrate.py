"""Readings that the check's limits are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n> ... --control-seeds <n> ...

In one process, so that set-up is paid once: the program as the benchmark
runs it, over ``--seeds``, and then the control, the program's own f32
path (``DistributedHierarchy.setup(dtype=float32)``), the nearest precision
below the configuration's f64, over ``--control-seeds``.  Each seed runs a
window of ``--seconds`` at the cell's load and the comparison of
``bench.check``.  One JSON line per seed, then a summary: per number, the
largest reading of the program (the lower reading) and the smallest of
the control (the upper one).  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import check, run, traffic as mixes  # noqa: E402
from bench.reference import Reference  # noqa: E402


def readings(dh, cell, ref, seeds, n: int, seconds: float, kind: str):
    for seed in seeds:
        vcycle_s = run.warm_up(dh, cell, seed, n)
        solves, window_s = run.run_window(dh, cell, seed, seconds, vcycle_s, n)
        sample = mixes.check_sample(
            cell.traffic, [len(s["hist"]) for s in solves], seed)
        worst, failed = check.compare(ref, solves, sample, cell.cfg)
        yield {
            "kind": kind, "seed": seed, "worst": worst, "failed": failed,
            "solves": len(solves),
            "vcycles": [len(s["hist"]) for s in solves],
            "final": [s["hist"][-1] for s in solves],
            "solve_s": run.DIGITS * window_s / run.digits(solves),
            "vcycle_s": vcycle_s, "window_s": window_s,
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    devices = run.require_devices(cell.chips)
    run.configure_jax()
    arrays = run.operator(cell.cfg)
    n = len(arrays[0]) - 1
    ref = Reference(*arrays, cell.cfg["solver"])
    h, dh, _, _ = run.set_up(cell.cfg, devices, arrays)
    lines = []
    for ln in readings(dh, cell, ref, args.seeds, n, args.seconds, "program"):
        lines.append(ln)
        print(json.dumps(ln), flush=True)
    if args.control_seeds:
        from repro.amg import DistributedHierarchy

        control = DistributedHierarchy.setup(h, dh.mesh, dtype=np.float32)
        del dh
        for ln in readings(control, cell, ref, args.control_seeds, n,
                           args.seconds, "control"):
            lines.append(ln)
            print(json.dumps(ln), flush=True)
    summary = {"workload": cell.name, "time": time.time()}
    for kind, pick in (("program", max), ("control", min)):
        got = [ln["worst"] for ln in lines if ln["kind"] == kind]
        keys = sorted({k for g in got for k in g})
        summary[kind] = {k: pick(g[k] for g in got if k in g) for k in keys}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
