"""Fast tier-1 subset of the static verifier (repro.verify).

Covers every pass once — pattern/plan structure + conservation, partition
and device-ELL layout checks, the kernel VMEM budget, the jaxpr audit of a bound executor, the PlanCache insertion
hook, the canonical pattern fingerprint, ServeEngine.verify(), and the
repo lint (self-test on seeded bugs + clean run over the tree).  The
exhaustive randomized accept/reject coverage is hypothesis P10 in
tests/test_property.py; the full plan zoo runs in CI's static-analysis
job (tools/verify_zoo.py).
"""
import dataclasses
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import CommPattern, Topology, build_plan
from repro.core.cache import PlanCache, pattern_fingerprint, plan_cache_key
from repro.core.collectives import build_device_plan
from repro.core.costmodel import TPU_V5E
from repro.core.neighborhood import NeighborAlltoallV
from repro.sparse import (
    CSR,
    diagonal_offsets,
    partition_csr,
    partitioned_to_dia,
    partitioned_to_ell,
)
from repro.verify import (
    VerifyError,
    audit_executor,
    verify_collective,
    verify_device_ell,
    verify_enabled,
    verify_kernel_budget,
    verify_moe_dispatch,
    verify_moe_plan,
    verify_partition,
    verify_pattern,
    verify_plan,
)

REPO = Path(__file__).resolve().parents[1]


def small_pattern():
    needs = [np.array([4, 5, 9]), np.array([0, 8]), np.array([2]),
             np.array([1, 6])]
    return CommPattern.from_block_partition(needs, np.arange(5) * 3)


def small_partition(seed=0, n=24, n_procs=3):
    rng = np.random.default_rng(seed)
    nnz = 4 * n
    A = CSR.from_coo(rng.integers(0, n, nnz), rng.integers(0, n, nnz),
                     rng.normal(size=nnz), (n, n))
    return partition_csr(A, n_procs)


# ---------------------------------------------------------------- patterns


def test_pattern_accepts_valid():
    verify_pattern(small_pattern())


def test_pattern_rejects_broken_ownership():
    pat = small_pattern()
    pat.owner_slot[4] = pat.owner_slot[5]    # two values share one slot
    with pytest.raises(VerifyError, match="share one local slot"):
        verify_pattern(pat)


def test_pattern_rejects_out_of_range_need():
    pat = small_pattern()
    pat.needs[2] = np.array([99])
    with pytest.raises(VerifyError, match="rank=2"):
        verify_pattern(pat)


# ------------------------------------------------------------------ plans


@pytest.mark.parametrize("strategy", ["standard", "partial", "full"])
def test_plan_accepts_all_strategies(strategy):
    pat = small_pattern()
    plan = build_plan(pat, Topology(4, 2), strategy)
    verify_plan(plan)


def test_plan_rejects_dropped_delivery():
    pat = small_pattern()
    plan = build_plan(pat, Topology(4, 2), "standard")
    wire = [m for s in plan.steps for m in s.messages
            if m.src != m.dst and m.size > 0]
    wire[0].src_idx = wire[0].src_idx[:-1]
    wire[0].dst_idx = wire[0].dst_idx[:-1]
    with pytest.raises(VerifyError, match="never written"):
        verify_plan(plan)


def test_plan_rejects_duplicated_delivery():
    pat = small_pattern()
    plan = build_plan(pat, Topology(4, 2), "standard")
    # aim two copies of one payload at the same ghost slot
    wire = [m for s in plan.steps for m in s.messages
            if m.src != m.dst and m.size > 1]
    m = wire[0]
    m.dst_idx = m.dst_idx.copy()
    m.dst_idx[1] = m.dst_idx[0]
    with pytest.raises(VerifyError, match="same slot|more than once"):
        verify_plan(plan)


def test_collective_accepts_and_device_plan_checked():
    pat = small_pattern()
    coll = NeighborAlltoallV.init(pat, Topology(4, 2), "partial")
    verify_collective(coll)
    step = next(s for s in coll.device_plan.steps if s.rounds)
    step.rounds[0].gather[0, 0] = 10 ** 6
    with pytest.raises(VerifyError, match="sentinel"):
        verify_collective(coll)


# ----------------------------------------------------- partitions + layouts


def test_partition_and_layouts_accept():
    part = small_partition()
    verify_partition(part)
    ell = partitioned_to_ell(part)
    verify_device_ell(ell, part)


def test_partition_rejects_dropped_ghost_column():
    part = small_partition()
    assert len(part.needs[0])
    part.needs[0] = part.needs[0][:-1]
    with pytest.raises(VerifyError, match="rank=0"):
        verify_partition(part)


def test_ell_rejects_moved_nonzero():
    part = small_partition()
    ell = partitioned_to_ell(part)
    live = np.argwhere(ell.local_vals[0] != 0)
    r, k = live[0]
    ell.local_vals[0, r, k] *= 2.0
    with pytest.raises(VerifyError, match="rank=0"):
        verify_device_ell(ell, part)


@pytest.mark.parametrize("fault,match", [
    ("omitted", "omitted from the ghost rows"),
    ("duplicated", "written twice"),
    ("out_of_range", "outside the padded rows"),
])
def test_compact_ghost_rows_reject_planted_fault(fault, match):
    """A compact ghost block's rows must be ascending, unique, in range
    and cover every row that holds a ghost entry."""
    from repro.amg import diffusion_2d

    part = partition_csr(diffusion_2d(24, 8), 3)
    ell = partitioned_to_ell(part)
    assert ell.ghost_layout == "compact"
    verify_device_ell(ell, part)
    rows = ell.ghost_rows[1]
    if fault == "omitted":
        # move a ghost row's slots to a row the block does not list
        rows[-1] = np.setdiff1d(np.arange(ell.row_pad), rows)[0]
        order = np.argsort(rows)
        ell.ghost_rows[1] = rows[order]
        ell.ghost_cols[1] = ell.ghost_cols[1][order]
        ell.ghost_vals[1] = ell.ghost_vals[1][order]
    elif fault == "duplicated":
        rows[1] = rows[0]
    else:
        rows[-1] = ell.row_pad
    with pytest.raises(VerifyError, match=match):
        verify_device_ell(ell, part)


def test_diagonal_layout_accepts_and_rejects_altered_nonzero():
    from repro.amg import diffusion_2d

    part = partition_csr(diffusion_2d(12, 10), 3)
    dia = partitioned_to_dia(part, diagonal_offsets(part))
    verify_device_ell(dia, part)
    verify_kernel_budget(dia)
    d, i = np.argwhere(dia.local_vals[1] != 0)[0]
    dia.local_vals[1, d, i] *= 2.0
    with pytest.raises(VerifyError, match="rank=1"):
        verify_device_ell(dia, part)


# ---------------------------------------------------------- kernel budgets


def test_kernel_budget_accepts_both_layouts():
    """The ELL and the diagonal local layout of a stencil both fit."""
    from repro.amg import diffusion_2d

    part = partition_csr(diffusion_2d(12, 10), 3)
    verify_kernel_budget(partitioned_to_ell(part))
    verify_kernel_budget(partitioned_to_dia(part, diagonal_offsets(part)))


@pytest.mark.parametrize("field", ["in_pad", "ghost_pad"])
def test_kernel_budget_rejects_footprint_over_the_core(field):
    """An operator whose VMEM-resident x alone, local (``in_pad``) or ghost
    (``ghost_pad``), exceeds the core's VMEM is rejected."""
    from repro.verify.kernel_budget import vmem_bytes_per_core

    ell = partitioned_to_ell(small_partition())
    verify_kernel_budget(ell)
    values = vmem_bytes_per_core() // 8
    too_big = dataclasses.replace(ell, **{field: values})
    with pytest.raises(VerifyError, match="exceeds physical VMEM"):
        verify_kernel_budget(too_big)


# -------------------------------------------------------------- jaxpr audit


def test_audit_accepts_bound_executor_and_rejects_foreign_plan():
    import jax

    pat = small_pattern()
    coll = NeighborAlltoallV.init(pat, Topology(4, 2), "partial")
    mesh = jax.make_mesh((4,), ("proc",),
                         devices=jax.devices()[:4])
    fn = coll.bind(mesh, "proc")
    records = audit_executor(fn, coll.device_plan, "proc")
    assert len(records) == coll.device_plan.n_rounds
    # the same traced program must NOT pass as some other plan
    other = NeighborAlltoallV.init(pat, Topology(4, 2), "standard")
    with pytest.raises(VerifyError):
        audit_executor(fn, other.device_plan, "proc")
    with pytest.raises(VerifyError, match="axis"):
        audit_executor(fn, coll.device_plan, "wrong_axis")


# ------------------------------------------------------- PlanCache wiring


def test_cache_insertion_verifies_under_env(monkeypatch):
    monkeypatch.setenv("REPRO_VERIFY", "1")
    assert verify_enabled()
    pat = small_pattern()
    cache = PlanCache()
    cache.collective(pat, Topology(4, 2), "partial")   # valid: inserts

    # a corrupted collective must be refused at the insertion choke point
    bad = NeighborAlltoallV.init(pat, Topology(4, 2), "standard")
    wire = [m for s in bad.plan.steps for m in s.messages if m.size > 0]
    wire[0].src_idx = wire[0].src_idx[:-1]
    wire[0].dst_idx = wire[0].dst_idx[:-1]
    key = plan_cache_key(pat, Topology(4, 2), "corrupt", 8, TPU_V5E)
    with pytest.raises(VerifyError):
        cache._insert(cache._colls, key, bad, "collective")

    monkeypatch.setenv("REPRO_VERIFY", "0")
    assert not verify_enabled()
    cache._insert(cache._colls, key, bad, "collective")   # hot path: no check


def test_cache_executor_audited_under_env(monkeypatch):
    import jax

    monkeypatch.setenv("REPRO_VERIFY", "1")
    pat = small_pattern()
    cache = PlanCache()
    mesh = jax.make_mesh((4,), ("proc",), devices=jax.devices()[:4])
    fn = cache.executor(pat, Topology(4, 2), mesh, "proc", "partial")
    assert fn is cache.executor(pat, Topology(4, 2), mesh, "proc", "partial")


# ------------------------------------------------------------ fingerprints


def test_fingerprint_stable_and_distinct():
    pat = small_pattern()
    fp = pattern_fingerprint(pat)
    assert fp == pattern_fingerprint(small_pattern())    # content hash
    # any content change moves the digest
    variants = []
    v = small_pattern()
    v.needs[0] = v.needs[0][:-1]
    variants.append(v)
    v = small_pattern()
    v.needs[0] = np.array([4, 5, 10])
    variants.append(v)
    v = small_pattern()
    v.owner_proc[0] = 1
    variants.append(v)
    # moving a need between procs (same multiset of values) must differ
    v = small_pattern()
    v.needs[1], v.needs[2] = v.needs[2], v.needs[1]
    variants.append(v)
    digests = {pattern_fingerprint(x) for x in variants}
    assert fp not in digests
    assert len(digests) == len(variants)


def test_fingerprint_deterministic_across_processes():
    """The digest is a pure content hash — a fresh interpreter computes
    the identical hex string (no id()/hash()/dict-order dependence)."""
    fp = pattern_fingerprint(small_pattern())
    prog = textwrap.dedent("""
        import numpy as np
        from repro.core import CommPattern
        from repro.core.cache import pattern_fingerprint
        needs = [np.array([4, 5, 9]), np.array([0, 8]), np.array([2]),
                 np.array([1, 6])]
        pat = CommPattern.from_block_partition(needs, np.arange(5) * 3)
        print(pattern_fingerprint(pat))
    """)
    import os

    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               PYTHONHASHSEED="17")
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        cwd=REPO, env=env, check=True,
    )
    assert out.stdout.strip().splitlines()[-1] == fp


# -------------------------------------------------------------------- MoE


def moe_mesh_stub(*shape):
    from types import SimpleNamespace

    names = ("pod", "data", "model")[-len(shape):] if len(shape) > 2 \
        else ("data", "model")[-len(shape):]
    return SimpleNamespace(axis_names=names, devices=np.empty(shape))


@pytest.mark.parametrize("mode", ["a2a", "hier", "hier_dedup"])
def test_moe_dispatch_verifies(mode):
    from repro.configs import reduced
    from repro.models.moe import make_moe_plan

    plan = make_moe_plan(reduced("mixtral-8x7b"), moe_mesh_stub(1, 8), 32,
                         mode=mode)
    verify_moe_dispatch(plan, 32)


def test_moe_plan_rejects_broken_geometry():
    from repro.configs import reduced
    from repro.models.moe import make_moe_plan

    plan = make_moe_plan(reduced("mixtral-8x7b"), moe_mesh_stub(1, 8), 32,
                         mode="hier")
    bad = dataclasses.replace(plan, e_per_dev=plan.e_per_dev + 1)
    with pytest.raises(VerifyError, match="e_per_dev"):
        verify_moe_plan(bad)


def test_serve_engine_verify():
    import jax.numpy as jnp

    from repro.configs import reduced
    from repro.models import Model
    from repro.serve import ServeEngine

    cfg0 = reduced("mixtral-8x7b")
    cfg = cfg0.__class__(**{**cfg0.__dict__, "dtype": jnp.float32})
    model = Model(cfg, moe_mode="auto", remat=False, moe_cap_factor=8.0)
    eng = ServeEngine(model, model.init_params(seed=0), batch_slots=2,
                      max_len=32)
    assert eng.verify() == {"moe_plans": 2}


# -------------------------------------------------------------------- lint


def test_lint_flags_seeded_bugs(tmp_path):
    sys.path.insert(0, str(REPO))
    try:
        from tools.lint_repro import lint_paths
    finally:
        sys.path.pop(0)
    bad = tmp_path / "seeded.py"
    bad.write_text(textwrap.dedent("""
        import dataclasses
        import hashlib

        @dataclasses.dataclass
        class Cfg:
            xs: list = []                      # R1
            n: int = 0

        def fingerprint(d):
            h = hashlib.blake2b()
            for k, v in d.items():             # R2
                h.update(str((k, v)).encode())
            return h.hexdigest()

        def run(tracer, plan):
            tracer.record_plan(plan, 1.0)      # R3
    """))
    rules = sorted(r for _, _, r, _ in lint_paths([bad]))
    assert rules == [
        "R1-mutable-dataclass-default",
        "R2-unsorted-hash-iteration",
        "R3-tracer-missing-pure-exchange",
    ]


def test_lint_clean_over_tree():
    """The regression guard: re-introducing any flagged pattern anywhere
    in src/ or benchmarks/ fails tier-1, not just the CI lint job."""
    sys.path.insert(0, str(REPO))
    try:
        from tools.lint_repro import lint_paths
    finally:
        sys.path.pop(0)
    findings = lint_paths([REPO / "src", REPO / "benchmarks",
                           REPO / "tools"])
    assert not findings, "\n".join(
        f"{p}:{line}: {rule} {msg}" for p, line, rule, msg in findings
    )
