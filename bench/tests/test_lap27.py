"""The 27-point cell (``lap27.solve.x4``) on small grids: the benchmark's
generator gives the program's matrix, its reference builds the program's
hierarchy and tracks its solve, and the harness, steered onto the CPU at
``m`` = 6, runs the cell correct while the f32 control and a solve without
its halo exchange come out not correct."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench.operators import laplacian_27pt
from bench.reference import Reference

ROOT = Path(__file__).resolve().parents[2]
CELL = "lap27.solve.x4"
CONFIG = "lap27-3d-1m"

#: bench/run.py or bench/calibrate.py at a small block, past the look for a
#: chip, with a fault of bench/tests/steer.py planted where one is named
STEERED = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import calibrate, run
from bench.tests import steer
config = run.load_config
run.load_config = lambda f: {{**config(f), "m": {m}}}
run.require_devices = lambda n: jax.devices()[:n]
steer.plant({fault!r})
entry = run.main if sys.argv[1] == "run" else calibrate.main
sys.exit(entry(sys.argv[2:]))
"""


def config(m: int) -> dict:
    from bench.run import BENCH

    cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    return {**cfg, "m": m}


@pytest.mark.parametrize("m,procs", [(3, [2, 2, 1]), (4, [1, 3, 2])])
def test_matches_program_generator(m, procs):
    from repro.amg.stencil import laplacian_27pt as program

    A = program(m, tuple(procs))
    indptr, indices, data = laplacian_27pt.assemble({"m": m, "procs": procs})
    assert np.array_equal(indptr, A.indptr)
    assert np.array_equal(indices, A.indices)
    assert np.array_equal(data, A.data)


def test_reference_hierarchy_is_the_programs():
    from repro.amg.hierarchy import build_hierarchy
    from repro.sparse.csr import CSR

    cfg = config(8)
    arrays = laplacian_27pt.assemble(cfg)
    n = len(arrays[0]) - 1
    s = cfg["solver"]
    h = build_hierarchy(CSR((n, n), *arrays), max_levels=s["max_levels"],
                        min_coarse=s["min_coarse"],
                        strength_theta=s["strength_theta"])
    ref = Reference(*arrays, s)
    assert len(ref.levels) == h.n_levels > 2
    for hl, rl in zip(h.levels, ref.levels):
        assert np.array_equal(hl.A.indptr, rl.A.indptr)
        assert np.array_equal(hl.A.indices, rl.A.indices)
        assert np.array_equal(hl.A.data, rl.A.data)
        assert rl.rho == pytest.approx(hl.rho, rel=1e-12)
    b = np.random.default_rng(3).standard_normal(n)
    from repro.amg.hierarchy import solve

    x, hist = solve(h, b, tol=1e-8, max_iters=100)
    x_ref, h_ref = ref.solve(b, len(hist), converged=hist[-1] < 1e-8)
    assert np.max(np.abs(np.array(hist) - h_ref)) < 1e-14
    assert np.linalg.norm(x - x_ref) < 1e-12 * np.linalg.norm(x_ref)


def steered(tmp_path, entry: str, args: list, fault: str = "none"):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    code = STEERED.format(root=str(ROOT), src=str(ROOT / "src"), m=6,
                          fault=fault)
    out = subprocess.run([sys.executable, "-c", code, entry, *args],
                         cwd=str(ROOT), env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return [json.loads(ln) for ln in out.stdout.strip().splitlines()]


def cell_args(seed: int, trace: int = 0) -> list:
    return ["--workload", CELL, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace)]


@pytest.mark.parametrize("fault", ["none", "exchange"])
def test_cell_on_cpu(tmp_path, fault):
    line = steered(tmp_path, "run", cell_args(2 ** 31 + 11), fault)[-1]
    assert line["correct"] is (fault == "none")
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"solve_s", "amg_setup_s", "setup_s"}


def test_control_fails(tmp_path):
    """The program's f32 path exceeds a limit on every seed; the f64 path,
    in the same process, on none."""
    lines = steered(tmp_path, "calibrate", [
        "--workload", CELL, "--seconds", "1", "--seeds", "11",
        "--control-seeds", "21", "22"])
    limits = config(6)["limits"]
    program = [ln for ln in lines if ln.get("kind") == "program"]
    control = [ln for ln in lines if ln.get("kind") == "control"]
    assert len(program) == 1 and len(control) == 2
    for ln in program:
        assert ln["failed"] == 0
        assert all(v <= limits[k] for k, v in ln["worst"].items())
    for ln in control:
        assert ln["failed"] > 0
        assert any(v > limits[k] for k, v in ln["worst"].items())
