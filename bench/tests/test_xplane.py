"""The trace reduction on a synthetic event list: overlapping operations
count once, collective-permutes are the exchange, totals go per V-cycle,
and idle gaps take the name of the host span around them."""
from types import SimpleNamespace

import pytest

from bench import xplane
from bench.run import load_module, BENCH

MS = 1_000_000  # ns

SPANS = [
    ("bench/window", 0, 100 * MS),
    ("bench/solve", 0, 60 * MS),
    ("bench/rhs", 60 * MS, 70 * MS),
    ("bench/solve", 70 * MS, 100 * MS),
]
DEVICES = {
    0: [("fusion.1", 0, 20 * MS),
        ("collective-permute-start.3", 10 * MS, 12 * MS),
        ("collective-permute-done.3", 15 * MS, 30 * MS),   # 20..30 beyond
        ("fusion.1", 40 * MS, 60 * MS),
        ("fusion.2", 75 * MS, 100 * MS),
        ("fusion.2", 100 * MS, 120 * MS)],                  # after the window
    1: [("fusion.1", 0, 30 * MS),
        ("collective-permute-done.3", 25 * MS, 45 * MS),
        ("fusion.2", 80 * MS, 90 * MS)],
}


def test_op_name_is_the_hlo_name():
    text = ("%fusion.5 = f32[8]{0} fusion(f32[9]{0} %collective-permute-done.2,"
            " s32[8]{0} %p), kind=kCustom")
    assert xplane.op_name(text) == "fusion.5"
    assert xplane.op_name("collective-permute-done.2") == \
        "collective-permute-done.2"


def test_union_merges_overlaps():
    assert xplane.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


def test_reduce():
    got = xplane.reduce(DEVICES, SPANS)
    assert got["window_s"] == pytest.approx(0.1)
    # chip 0: [0,30] + [40,60] + [75,100] = 75 ms; chip 1: [0,45] + [80,90]
    assert got["busy_s"] == pytest.approx((75 + 55) / 2 / 1e3)
    # chip 0: [10,12] + [15,30] = 17 ms; chip 1: [25,45] = 20 ms
    assert got["exchange_s"] == pytest.approx((17 + 20) / 2 / 1e3)
    ops = dict(got["device_ops"])
    assert ops["fusion.1"] == pytest.approx((40 + 30) / 2 / 1e3)
    assert ops["fusion.2"] == pytest.approx((25 + 10) / 2 / 1e3)
    # chip 0's gaps: [30,40] in a solve, [60,75] across rhs and a solve
    assert got["idle_gaps"] == [["bench/rhs", pytest.approx(0.015)],
                                ["bench/solve", pytest.approx(0.010)]]


def test_no_exchange_reads_none():
    one = {0: [op for op in DEVICES[0] if "collective" not in op[0]]}
    assert xplane.reduce(one, SPANS)["exchange_s"] is None


def test_window_span_required():
    with pytest.raises(ValueError):
        xplane.reduce(DEVICES, SPANS[1:])


def read(name, run):
    return load_module(BENCH / "metrics" / f"{name}.py").read(run)


def test_metrics_go_per_vcycle():
    trace = xplane.reduce(DEVICES, SPANS)
    run = SimpleNamespace(trace=trace, n_vcycles=5)
    assert read("vcycle_device_ms", run) == pytest.approx(65 / 5)
    assert read("exchange_wait_ms", run) == pytest.approx(18.5 / 5)
    assert read("idle_share", run) == pytest.approx(35.0)
    untraced = SimpleNamespace(trace=None, n_vcycles=5)
    for name in ("vcycle_device_ms", "exchange_wait_ms", "idle_share",
                 "vcycle_hbm_roofline"):
        assert read(name, untraced) is None
