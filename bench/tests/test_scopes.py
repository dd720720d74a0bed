"""The scoped trace reduction on a synthetic trace of two chips, counted by
hand: busy time by kind, level, phase and exchange plan step, each instant
once; idle time by the innermost host span; the loop's idle time; and the
per-V-cycle readings built on them."""
import pytest

from bench import scopes, scoped

MS = 1_000_000  # ns
STEP = "jit(amg_vcycle_step)"

SPANS = [
    ("bench/window", 0, 100 * MS),
    ("bench/solve", 0, 100 * MS),
    ("amg/solve", 0, 100 * MS),
    ("amg/place", 0, 5 * MS),
    ("amg/vcycle_iter", 5 * MS, 50 * MS),
    ("amg/dispatch", 5 * MS, 8 * MS),
    ("amg/sync", 8 * MS, 50 * MS),
    ("py/gc", 42 * MS, 45 * MS),
    ("amg/vcycle_iter", 50 * MS, 88 * MS),
    ("amg/dispatch", 50 * MS, 53 * MS),
    ("amg/sync", 53 * MS, 88 * MS),
    ("amg/unpack", 88 * MS, 100 * MS),
]


def op(name, s, e):
    return (scopes.key(f"{STEP}/{name}" if name else None), s * MS, e * MS)


DEVICES = {
    0: [op("outer/spmv/gather", 6, 10),
        op("L0/pre/spmv/gather", 10, 20),
        op("L0/pre/mul", 20, 25),
        op("L1/coarse/spmv/reduce_sum", 25, 30),
        op(None, 30, 31),                                  # a prefetch copy
        op("L0/post/spmv/exchange/shard_map/step_p2p/ppermute", 31, 35),
        op("L0/post/spmv/gather", 33, 40),                 # starts later
        op("L0/pre/spmv/gather", 54, 70),
        op("L1/coarse/add", 70, 80),
        op("L0/pre/spmv/gather", 100, 120)],               # after the window
    1: [op("L0/pre/spmv/gather", 6, 46)],
}


def test_key_reads_level_phase_kind_and_plan_step():
    assert scopes.key(f"{STEP}/L3/restrict/spmv/exchange/shard_map/"
                      "step_l/gather") == ("L3", "restrict", "exchange",
                                           "step_l")
    assert scopes.key(f"{STEP}/outer/spmv/gather") == ("outer", "", "spmv", "")
    assert scopes.key(f"{STEP}/L0/post/mul") == ("L0", "post", "other", "")
    assert scopes.key("") == (scopes.UNSCOPED, "", "other", "")
    assert scopes.key(None) == (scopes.UNSCOPED, "", "other", "")


def test_op_scopes_from_hlo_text():
    text = "\n".join([
        "HloModule jit_amg_vcycle_step, is_scheduled=true",
        "%fused_computation (p: f32[4]) -> f32[4] {",
        '  %mul.1 = f32[4] multiply(%p, %p), metadata={op_name="inner"}',
        "}",
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        '  %fusion.5 = f32[4] fusion(%a), kind=kLoop, calls=%fused_'
        'computation, metadata={op_name="jit(amg_vcycle_step)/L0/pre/'
        'spmv/gather" stack_frame_id=3}',
        "  %copy-start.2 = (f32[4], f32[4]) copy-start(f32[4] %a)",
        '  ROOT %add.7 = f32[4] add(%fusion.5, %a), metadata={op_name='
        '"jit(amg_vcycle_step)/outer/add"}',
        "}",
    ])
    module, ops = scopes.op_scopes(text)
    assert module == "jit_amg_vcycle_step"
    assert ops["fusion.5"] == "jit(amg_vcycle_step)/L0/pre/spmv/gather"
    assert ops["add.7"] == "jit(amg_vcycle_step)/outer/add"
    assert "copy-start.2" not in ops
    with pytest.raises(ValueError):
        scopes.op_scopes("ENTRY %main () -> f32[] {")


def test_owned_counts_each_instant_once_to_the_latest_start():
    a, b = ("a", "", "other", ""), ("b", "", "other", "")
    got = scopes.owned([(a, 0, 30), (b, 10, 12), (a, 40, 50)])
    assert got == {a: 10 + 18 + 10, b: 2}


def test_reduce_by_hand():
    got = scopes.reduce(DEVICES, SPANS)
    ms = pytest.approx
    assert got["chips"] == 2
    assert got["window_s"] == ms(0.1)
    # chip 0 busy [6,40] + [54,80] = 60 ms, chip 1 [6,46] = 40 ms
    assert got["busy_s"] == ms(0.050)
    # chip 0: spmv 4 + 26 + 5 + 7, exchange 2 (31-33: the gather that
    # started at 33 owns 33-35), other 5 + 1 + 10; chip 1: spmv 40
    assert got["kind_s"] == {"spmv": ms(0.041), "exchange": ms(0.001),
                             "other": ms(0.008)}
    assert sum(got["kind_s"].values()) == ms(got["busy_s"])
    assert got["level_s"] == {"L0": ms(0.040), "L1": ms(0.0075),
                              "outer": ms(0.002),
                              scopes.UNSCOPED: ms(0.0005)}
    assert got["level_kind_s"]["L0"] == {"exchange": ms(0.001),
                                         "other": ms(0.0025),
                                         "spmv": ms(0.0365)}
    assert got["phase_s"] == {"L0/post": ms(0.0045), "L0/pre": ms(0.0355),
                              "L1/coarse": ms(0.0075)}
    assert got["exchange_step_s"] == {"step_p2p": ms(0.001)}
    # idle, chip 0: place 5, dispatch 1 + 3, sync 2 + 5 + 1 + 8, gc 3,
    # unpack 12; chip 1: place 5, dispatch 1 + 3, sync 4 + 35, unpack 12
    assert got["idle_by_span_s"] == {
        "amg/sync": ms(0.0275), "amg/unpack": ms(0.012),
        "amg/place": ms(0.005), "amg/dispatch": ms(0.004),
        "py/gc": ms(0.0015)}
    assert sum(got["idle_by_span_s"].values()) == ms(0.050)
    # inside amg/solve, outside place and unpack: chip 0 1 + 14 + 8,
    # chip 1 1 + 42
    assert got["loop_idle_s"] == ms(0.033)
    assert got["idle_gaps"][:3] == [["amg/unpack", ms(0.020)],
                                    ["amg/sync", ms(0.014)],
                                    ["amg/place", ms(0.006)]]


def test_reduce_needs_one_window():
    with pytest.raises(ValueError):
        scopes.reduce(DEVICES, SPANS[1:])


def test_per_vcycle_readings():
    # the two-level hierarchy of test_work: level 0's A applied 4 times
    # (pre, post, residual, the step's own), 208 bytes each, R and P 120
    # bytes each; level 1's A twice at 80 bytes
    levels = [{"n": 4, "nnz": 12, "nc": 2, "nnz_r": 6, "nnz_p": 6},
              {"n": 2, "nnz": 4}]
    solver = {"pre_degree": 1, "post_degree": 1, "coarse_degree": 2}
    assert scoped.spmv_bytes(levels, solver, 8) == [1072, 160]
    red = scopes.reduce(DEVICES, SPANS)
    got = scoped.per_vcycle(red, levels, solver, 8, 2, "TPU v5 lite", 2)
    assert got["spmv_device_ms"] == pytest.approx(20.5)
    assert got["spmv_hbm_roofline"] == pytest.approx(
        100 * 1232 / (2 * 819e9) / 20.5e-3)
    assert got["coarse_levels_ms"] == pytest.approx(3.75)
    assert got["exchange_device_ms"] == pytest.approx(0.5)
    assert got["vcycle_gap_ms"] == pytest.approx(16.5)
    assert got["outer_ms"] == pytest.approx(1.0)
    assert got["unscoped_share"] == pytest.approx(0.01)
    assert [row["ms"] for row in got["levels"]] == pytest.approx([20, 3.75])
    assert got["levels"][1]["spmv_ms"] == pytest.approx(1.25)
