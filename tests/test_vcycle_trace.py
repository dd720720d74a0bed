"""The V-cycle seen from inside, on host devices: named scopes per level,
phase, SpMV and exchange in the compiled step; ``repro.obs`` spans on the
JAX profiler's host plane; compile counters; ``py/gc`` spans."""
import gc
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.amg import DistributedHierarchy, build_hierarchy, diffusion_2d
from repro.obs import NULL_SPAN, Obs, default_obs

PHASES = ("pre", "residual", "restrict", "prolong", "post")


@pytest.fixture(scope="module")
def host_hierarchy():
    return build_hierarchy(diffusion_2d(24, 24))


def distributed(h, n_dev: int) -> DistributedHierarchy:
    mesh = jax.make_mesh((n_dev,), ("proc",), devices=jax.devices()[:n_dev])
    return DistributedHierarchy.setup(h, mesh, dtype=np.float32,
                                      value_bytes=4)


def rhs(n: int) -> np.ndarray:
    return np.random.default_rng(0).normal(size=n)


@pytest.fixture
def default_on():
    """The process-wide obs, enabled for one test and then cleared."""
    obs = default_obs()
    obs.reset()
    obs.enable()
    try:
        yield obs
    finally:
        obs.disable()
        obs.reset()


def step_op_names(dh: DistributedHierarchy) -> set:
    step = dh._device_step()
    x = jax.device_put(
        np.zeros((dh.topo.n_procs, dh.levels[0].pad), np.float32),
        jax.sharding.NamedSharding(dh.mesh, jax.sharding.PartitionSpec(
            dh.axis_name)))
    text = step.lower(dh._consts, x, x).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("n_dev", [1, 4])
def test_compiled_step_carries_level_phase_and_kernel_scopes(
        host_hierarchy, n_dev):
    dh = distributed(host_hierarchy, n_dev)
    names = step_op_names(dh)
    last = len(dh.levels) - 1
    assert any("/outer/spmv/" in n for n in names)
    for k in range(last + 1):
        mine = [n for n in names if f"/L{k}/" in n]
        assert any("/spmv/" in n for n in mine), k
        # levels do not nest: the recursion runs outside L<k>
        assert not any(re.search(r"/L\d+/.*/L\d+/", n) for n in mine)
        for ph in ("coarse",) if k == last else PHASES:
            assert any(f"/L{k}/{ph}/" in n for n in mine), (k, ph)
    exchange = [n for n in names if "/exchange/" in n]
    if n_dev == 1:
        assert exchange == []
    else:
        assert all("/spmv/exchange/" in n for n in exchange)
        assert any(re.search(r"/exchange/.*step_\w+/", n) for n in exchange)
        assert any("ppermute" in n for n in exchange)


def test_solve_bit_identical_with_obs_on(host_hierarchy):
    dh = distributed(host_hierarchy, 4)
    b = rhs(dh.levels[0].n)
    obs = default_obs().disable()
    x_off, h_off = dh.solve(b, tol=0.0, max_iters=4)
    obs.enable()
    try:
        x_on, h_on = dh.solve(b, tol=0.0, max_iters=4)
    finally:
        obs.disable()
        obs.reset()
    assert h_on == h_off
    assert np.array_equal(x_on, x_off)


def host_plane_spans(trace_dir) -> list:
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path)
    return [ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(("amg/", "py/"))]


def test_solve_spans_land_on_the_profiler_host_plane(host_hierarchy,
                                                     tmp_path):
    dh = distributed(host_hierarchy, 1)
    b = rhs(dh.levels[0].n)
    dh.solve(b, tol=0.0, max_iters=4)          # compile outside the traces
    obs = default_obs()

    def traced(d):
        with jax.profiler.trace(str(d)):
            dh.solve(b, tol=0.0, max_iters=4)
        return host_plane_spans(d)

    assert traced(tmp_path / "off") == []
    assert obs.span("amg/solve") is NULL_SPAN
    obs.enable()
    try:
        names = traced(tmp_path / "on")
    finally:
        obs.disable()
        obs.reset()
    count = {n: names.count(n) for n in set(names)}
    assert count["amg/solve"] == 1
    assert count["amg/place"] == count["amg/unpack"] == 1
    assert count["amg/vcycle_iter"] == count["amg/dispatch"] \
        == count["amg/sync"] == 4
    assert "amg/step_program" not in count     # built before the trace


def test_span_annotations_are_plain_names(tmp_path):
    obs = Obs().enable()
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("amg/solve", n=3, tol=1e-8):
            pass
    assert host_plane_spans(tmp_path) == ["amg/solve"]
    (ev,) = obs.spans.events(kind="span")
    assert ev.attrs == {"n": 3, "tol": 1e-8}


def test_step_program_and_hierarchy_spans(host_hierarchy, default_on):
    build_hierarchy(diffusion_2d(24, 24))
    dh = distributed(host_hierarchy, 1)
    dh.solve(rhs(dh.levels[0].n), tol=0.0, max_iters=1)
    tot = default_on.spans.totals
    n_coarsened = len(host_hierarchy.levels) - 1
    assert tot["amg/build_hierarchy"][0] == 1
    assert tot["amg/estimate_rho"][0] == 1
    for name in ("amg/coarsen_level", "amg/strength", "amg/pmis",
                 "amg/interp", "amg/galerkin"):
        assert tot[name][0] == n_coarsened, name
    assert tot["amg/step_program"][0] == 1
    assert tot["amg/build_level"][0] == len(dh.levels)
    by_name = {}
    for ev in default_on.spans.events(kind="span"):
        by_name.setdefault(ev.name, []).append(ev)
    (solve,) = by_name["amg/solve"]
    (build,) = by_name["amg/step_program"]
    assert solve.t0 <= build.t0 and build.t1 <= solve.t1
    assert build.depth == solve.depth + 1


def test_new_jit_counts_compiles_and_a_repeat_does_not():
    obs = Obs().enable()

    def triple(x):
        return 3 * x + 1

    f = jax.jit(triple)
    f(jnp.ones(5))
    compiles = obs.counter("jax/compiles")
    n = compiles.total()
    assert compiles.value(event="backend_compile", fun="jit(triple)") == 1
    assert compiles.value(event="jaxpr_to_mlir", fun="jit(triple)") == 1
    assert obs.counter("jax/compile_seconds").total() > 0
    f(jnp.ones(5))
    assert compiles.total() == n
    obs.disable()
    jax.jit(triple)(jnp.ones(7))
    assert compiles.total() == n


def test_gc_pauses_are_spans():
    obs = Obs().enable()
    gc.collect()
    obs.disable()
    gc.collect()
    (ev,) = [e for e in obs.spans.events(kind="span") if e.name == "py/gc"
             and e.attrs["generation"] == 2]
    assert ev.attrs["collected"] >= 0 and ev.duration >= 0
    assert obs.spans.totals["py/gc"][0] >= 1


@pytest.mark.parametrize("n_dev", [1, 4])
def test_spmv_nnz_counter_splits_by_layout(host_hierarchy, default_on,
                                           n_dev):
    """``sparse/spmv_nnz`` counts the fine A's local nonzeros under
    ``layout=diagonal`` and every other stored nonzero under ``ell``; the
    set-up's spans and ``describe()`` name the fine A's layout alone."""
    dh = distributed(host_hierarchy, n_dev)
    nnz = default_on.counter("sparse/spmv_nnz")
    fine = dh.levels[0].A.part
    diagonal = sum(m.nnz for m in fine.local)
    stored = sum(m.nnz for lv in dh.levels for op in (lv.A, lv.R, lv.P)
                 if op is not None for m in op.part.local + op.part.ghost)
    assert nnz.value(layout="diagonal") == diagonal
    assert nnz.value(layout="ell") == stored - diagonal
    if n_dev == 1:
        assert diagonal == host_hierarchy.levels[0].A.nnz
    layouts = [lv.A.local_layout for lv in dh.levels]
    assert layouts == ["diagonal"] + ["ell"] * (len(dh.levels) - 1)
    assert all(op.local_layout == "ell" for lv in dh.levels
               for op in (lv.R, lv.P) if op is not None)
    built = [ev.attrs["layout"] for ev in default_on.spans.events(kind="span")
             if ev.name == "amg/build_level"]
    assert built == layouts
    desc = dh.describe()
    assert desc.count("local=diagonal") == 1 and "  L0:" in desc
    assert "local=diagonal" in desc.splitlines()[1]
