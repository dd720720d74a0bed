"""Integration: device-resident distributed AMG on 8 virtual host devices.

The heavy check (jitted V-cycle vs host solver, strategy selection, plan
cache) runs in a subprocess with XLA_FLAGS set at spawn so the main pytest
process keeps its device configuration.  Single-device sanity of the same
machinery (rect partition, ELL conversion) lives in test_sparse_device.py.
"""
import os
import pathlib
import subprocess
import sys

PROGS = pathlib.Path(__file__).parent / "multidevice_progs"
SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def run_prog(name: str, timeout=600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, str(PROGS / name)],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_distributed_amg_vcycle_matches_host():
    out = run_prog("check_distributed_amg.py")
    assert "ALL_OK" in out
    assert "residual history OK" in out
    assert "plan cache OK" in out
    # Section-5 selector: fine level standard, >=2 strategies over levels
    assert "A=standard" in out
    assert out.count("local=diagonal") == 1
    assert "ghost=compact" in out.splitlines()[1]
    assert "A=full" in out or "A=partial" in out


def test_overlap_spmv_hierarchy_matches_host():
    """Exchange/compute-overlapped schedule end to end: forced-overlap
    hierarchies (the fine level's local block by diagonals, and by its ELL
    gather) track the host solver, auto records its per-level decision,
    visible in describe()."""
    out = run_prog("check_overlap_spmv.py")
    assert "ALL_OK" in out
    assert "forced-overlap diagonal residual history OK" in out
    assert "forced-overlap ell residual history OK" in out
    assert "auto-overlap residual history OK" in out
    assert "ov=off" in out
