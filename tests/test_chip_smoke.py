"""chip_smoke.py on the CPU: its TPU check holds, and with the check
steered off from here its whole path runs at a tiny grid, on one device
and on four."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

# the script's own flow, with its device check replaced by the CPU devices
# and its grid cut to 32x32 rows per device
STEERED = """
import sys
import jax
import chip_smoke
chip_smoke.SIDE = 32
chip_smoke.require_tpu = lambda n: jax.devices()[:n]
sys.exit(chip_smoke.main(sys.argv[1:]))
"""


def run(args, cache_dir, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    return subprocess.run(
        [sys.executable, *args], cwd=str(ROOT), env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def test_chip_smoke_refuses_cpu(tmp_path):
    out = run(["chip_smoke.py"], tmp_path)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok": true' not in out.stdout


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_path_on_cpu(tmp_path, chips):
    args = ["-c", STEERED] + (["--chips", "4"] if chips == 4 else [])
    out = run(args, tmp_path)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": chips,
    }}
    text = out.stdout
    assert "kernel implementations by platform" in text
    assert "spmv_ell" in text and "flash_attention" in text
    assert f"compile cache: {tmp_path}" in text
    assert "host cross-check" in text and "first call" in text
    assert ("measured exchange per level" in text) == (chips == 4)


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The checkout's .jax_cache by default; JAX_COMPILATION_CACHE_DIR, left
    for JAX to read, where it is set."""
    import jax

    from repro.launch.compile_cache import configure_compile_cache

    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert configure_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", old)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == old
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
