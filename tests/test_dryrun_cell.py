"""Integration: one real dry-run cell end-to-end in a subprocess (512
virtual devices), plus the skip rule."""
import json
import os
import pathlib
import subprocess
import sys

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def run_dryrun(*args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)  # dryrun.py sets its own
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", *args],
        env=env, capture_output=True, text=True, timeout=timeout,
        cwd=str(SRC.parent),
    )
    return out


def test_skipped_cell_reports_reason(tmp_path):
    out = run_dryrun("--arch", "nemotron-4-15b", "--shape", "long_500k",
                     "--mesh", "single", "--out-dir", str(tmp_path),
                     timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout)
    assert d["status"] == "skipped"
    assert "sub-quadratic" in d["reason"]


def test_train_cell_compiles_and_reports_roofline(tmp_path):
    out = run_dryrun("--arch", "qwen1.5-0.5b", "--shape", "train_4k",
                     "--mesh", "single", "--force",
                     "--out-dir", str(tmp_path))
    assert out.returncode == 0, out.stderr[-3000:]
    d = json.loads(out.stdout)
    assert d["status"] == "ok"
    assert d["chips"] == 256
    assert d["cost_method"] == "scan+ladder-extrapolation"
    assert d["hlo_flops_per_device"] > 0
    assert d["collective_bytes_total_per_device"] > 0
    assert d["bottleneck"] in ("compute", "memory", "collective")
    assert 0.05 < d["useful_flops_ratio"] <= 1.5
    assert d["memory_analytic"]["fits_16gb_v5e"] is True
    # the cell lands where it was told, never over the committed results
    assert json.loads(
        (tmp_path / "qwen1.5-0.5b__train_4k__single.json").read_text()
    ) == d
