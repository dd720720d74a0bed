"""The harness end to end on the CPU, at a 32x32 grid: it refuses to run
without a TPU or without the program; steered past its look for a chip it
runs every cell correct; the control (the program's f32 path) and each
fault a solve can have come out not correct."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
STEER = str(ROOT / "bench" / "tests" / "steer.py")
CELLS = ["paper2d.solve.x1", "paper2d.solve.x4", "poisson2d.solve.x1"]


def run(args, tmp_path, cwd=ROOT, timeout=600, pythonpath=True):
    env = dict(os.environ)
    if not pythonpath:
        env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return subprocess.run([sys.executable, *args], cwd=str(cwd), env=env,
                          capture_output=True, text=True, timeout=timeout)


def result(out) -> dict:
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def cell_args(cell, seed=2 ** 31 + 7, seconds=1):
    return ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0"]


def test_refuses_cpu(tmp_path):
    out = run(["bench/run.py", *cell_args(CELLS[0])], tmp_path)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"correct"' not in out.stdout


def test_refuses_without_program(tmp_path):
    """A checkout of only BENCHMARK.json and bench/ cannot run a cell, even
    past the look for a chip."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    out = run(["bench/tests/steer.py", "run", *cell_args(CELLS[0])],
              tmp_path, cwd=tmp_path, pythonpath=False)
    assert out.returncode != 0
    assert "No module named 'repro'" in out.stderr
    assert '"correct"' not in out.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_cell_correct_on_cpu(tmp_path, cell):
    out = run([STEER, "run", *cell_args(cell)], tmp_path)
    line = result(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert list(line)[-1] == "check"
    assert set(line["metrics"]) == {"solve_s", "amg_setup_s", "setup_s"}
    assert line["device"]["count"] == (4 if cell.endswith("x4") else 1)
    for name, c in line["check"].items():
        assert c["value"] <= c["limit"], name
        assert f"check {name}:" in out.stderr


def test_control_fails(tmp_path):
    """The program's f32 path exceeds a limit on every seed; the f64 path,
    in the same process, on none."""
    out = run([STEER, "calibrate", "--workload", CELLS[0], "--seconds", "1",
               "--seeds", "11", "--control-seeds", "21", "22", "23"],
              tmp_path)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    limits = json.loads((ROOT / "bench" / "configs" / "paper2d-512k.json")
                        .read_text())["limits"]
    program = [ln for ln in lines if ln.get("kind") == "program"]
    control = [ln for ln in lines if ln.get("kind") == "control"]
    assert len(program) == 1 and len(control) == 3
    for ln in program:
        assert ln["failed"] == 0
        assert all(v <= limits[k] for k, v in ln["worst"].items())
    for ln in control:
        assert ln["failed"] > 0
        assert any(v > limits[k] for k, v in ln["worst"].items())


@pytest.mark.parametrize("fault,cell", [
    ("unchanged", CELLS[0]),
    ("half", CELLS[0]),
    ("exchange", CELLS[1]),
    ("altered", CELLS[0]),
])
def test_fault_is_not_correct(tmp_path, fault, cell):
    out = run([STEER, "--fault", fault, "run", *cell_args(cell)], tmp_path)
    line = result(out)
    assert line["correct"] is False
    assert line["failed"] >= 1
