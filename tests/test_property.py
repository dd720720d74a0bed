"""Property-based tests (hypothesis) for the system's invariants.

Invariants under test:
  P1  Every strategy delivers exactly the requested values (conservation +
      correctness) for ANY pattern/topology.
  P2  Aggregation never increases the max inter-region message count, and
      bounds it by the number of remote regions.
  P3  Dedup never increases inter-region bytes and never changes results.
  P4  Round schedules are valid partial permutations covering all wire
      messages exactly once.
  P5  Load balancing (LPT) is within 2x of the ideal max load.
  P6  The cost model is monotone in message sizes.
  P7  MoE capacity packing: slots are unique, within bounds, and respect
      per-expert capacity.
  P8  ELL packing (Pallas kernel and jnp oracle) and diagonal packing of
      the local block agree with each other and with the host matvec on
      ANY random sparsity/ghost pattern.
  P9  The overlap schedule's split execution (local block while the
      exchange runs, then the ghost block added on top) equals the fused
      schedule and the host matvec on ANY random sparsity/ghost pattern.
  P10 The static verifier (repro.verify) accepts every plan/partition/
      layout built from random patterns, and rejects every injected
      corruption — size-mismatched send, dropped ghost column,
      round-coloring conflict — with a diagnostic naming the offending
      rank.
  P11 Every dense-collective schedule (ring / rd / hier allreduce,
      allgatherv, reduce_scatter) verifies statically and its oracle
      equals the jnp reference (sum / concat / owned-segment) on ANY
      random geometry with uneven counts, including non-divisible
      region sizes.
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (
    CommPattern,
    LASSEN,
    Topology,
    build_plan,
    color_rounds,
    plan_time,
)
from repro.core.locality import balance_assignments
from repro.sparse import CSR


@st.composite
def patterns(draw):
    n_regions = draw(st.integers(2, 4))
    ppr = draw(st.integers(1, 4))
    n_procs = n_regions * ppr
    n_per = draw(st.integers(1, 12))
    n_global = n_procs * n_per
    seed = draw(st.integers(0, 2 ** 16))
    rng = np.random.default_rng(seed)
    needs = []
    for q in range(n_procs):
        k = int(rng.integers(0, min(n_global, 20)))
        needs.append(np.sort(rng.choice(n_global, size=k, replace=False)))
    offsets = np.arange(n_procs + 1) * n_per
    return CommPattern.from_block_partition(needs, offsets), \
        Topology(n_procs, ppr), seed


@settings(max_examples=40, deadline=None)
@given(patterns(), st.sampled_from(["standard", "partial", "full"]))
def test_p1_delivery_correct(pt, strategy):
    pattern, topo, seed = pt
    plan = build_plan(pattern, topo, strategy)
    rng = np.random.default_rng(seed + 1)
    vals = [rng.normal(size=(int(n),)) for n in pattern.n_local]
    got = plan.execute_numpy(vals)
    for q in range(pattern.n_procs):
        want = np.array([
            vals[pattern.owner_proc[g]][pattern.owner_slot[g]]
            for g in pattern.needs[q]
        ])
        np.testing.assert_array_equal(got[q], want.reshape(got[q].shape))


@settings(max_examples=40, deadline=None)
@given(patterns())
def test_p2_aggregation_bounds_inter_messages(pt):
    pattern, topo, _ = pt
    std = build_plan(pattern, topo, "standard")
    par = build_plan(pattern, topo, "partial")
    # per-proc inter messages bounded by remote region count
    assert par.stats.max_inter_msgs() <= topo.n_regions - 1 + 1
    assert (par.stats.totals()["inter_msgs"]
            <= max(std.stats.totals()["inter_msgs"],
                   topo.n_regions * (topo.n_regions - 1)))


@settings(max_examples=40, deadline=None)
@given(patterns())
def test_p3_dedup_never_worse_and_equal_results(pt):
    pattern, topo, seed = pt
    par = build_plan(pattern, topo, "partial")
    ful = build_plan(pattern, topo, "full")
    assert (ful.stats.totals()["inter_bytes"]
            <= par.stats.totals()["inter_bytes"])
    rng = np.random.default_rng(seed + 2)
    vals = [rng.normal(size=(int(n),)) for n in pattern.n_local]
    a = par.execute_numpy(vals)
    b = ful.execute_numpy(vals)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@settings(max_examples=40, deadline=None)
@given(patterns(), st.sampled_from(["standard", "partial", "full"]))
def test_p4_rounds_partition_wire_messages(pt, strategy):
    pattern, topo, _ = pt
    plan = build_plan(pattern, topo, strategy)
    for step in plan.steps:
        wire = [(m.src, m.dst, m.size) for m in step.messages
                if m.src != m.dst and m.size > 0]
        scheduled = []
        for rnd in color_rounds(step.messages):
            srcs = [s for s, _ in rnd.pairs]
            dsts = [d for _, d in rnd.pairs]
            assert len(set(srcs)) == len(srcs)
            assert len(set(dsts)) == len(dsts)
            scheduled.extend(
                (s, d, len(si)) for (s, d), si in zip(rnd.pairs, rnd.src_idx)
            )
        assert sorted(scheduled) == sorted(wire)


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(1, 1000), min_size=1, max_size=40),
       st.integers(1, 8))
def test_p5_lpt_balance(weights, n_workers):
    w = {i: v for i, v in enumerate(weights)}
    assign = balance_assignments(w, n_workers)
    loads = np.zeros(n_workers)
    for k, wk in assign.items():
        loads[wk] += w[k]
    ideal = max(sum(weights) / n_workers, max(weights))
    assert loads.max() <= 2 * ideal


@settings(max_examples=30, deadline=None)
@given(patterns())
def test_p6_costmodel_monotone(pt):
    pattern, topo, _ = pt
    plan8 = build_plan(pattern, topo, "standard", value_bytes=8)
    plan16 = build_plan(pattern, topo, "standard", value_bytes=16)
    assert plan_time(plan16, LASSEN) >= plan_time(plan8, LASSEN) - 1e-12


@st.composite
def sparse_partitions(draw):
    """A random square CSR (uneven blocks, random sparsity => random ghost
    pattern) plus an rng seed."""
    n_procs = draw(st.integers(1, 3))
    n = draw(st.integers(n_procs, 24))
    seed = draw(st.integers(0, 2 ** 16))
    rng = np.random.default_rng(seed)
    nnz = int(rng.integers(0, 4 * n))
    rows = rng.integers(0, n, size=nnz)
    cols = rng.integers(0, n, size=nnz)
    vals = rng.normal(size=nnz)
    return CSR.from_coo(rows, cols, vals, (n, n)), n_procs, seed


@settings(max_examples=15, deadline=None)
@given(sparse_partitions())
def test_p8_blocked_packing_matches_flat_and_ref(sp):
    """ELL packing through the Pallas kernel (interpret mode) and the jnp
    oracle, and diagonal packing of the local block, against the host
    matvec."""
    from repro.kernels.spmv_ell import spmv_dia, spmv_ell_ref
    from repro.kernels.spmv_ell.spmv_ell import spmv_ell
    from repro.sparse import (
        diagonal_offsets,
        partition_csr,
        partitioned_to_dia,
        partitioned_to_ell,
    )
    import jax.numpy as jnp

    A, n_procs, seed = sp
    part = partition_csr(A, n_procs)
    ell = partitioned_to_ell(part, dtype=np.float32)
    offsets = diagonal_offsets(part)
    dia = (partitioned_to_dia(part, offsets, dtype=np.float32)
           if offsets else None)
    plan = build_plan(part.pattern, Topology(n_procs, 1), "standard")
    rng = np.random.default_rng(seed + 1)
    x = rng.normal(size=A.ncols).astype(np.float32)
    xs = [x[int(part.offsets[p]): int(part.offsets[p + 1])]
          for p in range(n_procs)]
    ghosts = plan.execute_numpy(xs)
    want_all = A.matvec(x.astype(np.float64))
    for p in range(n_procs):
        n_rows = int(part.offsets[p + 1] - part.offsets[p])
        # local block with its sentinel slot at in_pad
        xf = np.zeros(ell.in_pad + 1, dtype=np.float32)
        xf[: len(xs[p])] = xs[p]
        flat = spmv_ell(
            jnp.asarray(ell.local_cols[p]), jnp.asarray(ell.local_vals[p]),
            jnp.asarray(xf), block_rows=8, interpret=True,
        )
        ref = spmv_ell_ref(
            jnp.asarray(ell.local_cols[p]), jnp.asarray(ell.local_vals[p]),
            jnp.asarray(xf),
        )
        by_dia = (spmv_dia(offsets, jnp.asarray(dia.local_vals[p]),
                           jnp.asarray(xf[: dia.in_pad]))
                  if dia is not None else ref)
        if ell.ghost_pad:
            gf = np.zeros(ell.ghost_pad + 1, dtype=np.float32)
            gf[: len(ghosts[p])] = ghosts[p].astype(np.float32)

            def on_rows(yg):
                # a compact ghost block's rows land at ghost_rows
                if ell.ghost_rows is None:
                    return yg
                return jnp.zeros(ell.row_pad, yg.dtype).at[
                    ell.ghost_rows[p]].set(yg)
            flat = flat + on_rows(spmv_ell(
                jnp.asarray(ell.ghost_cols[p]),
                jnp.asarray(ell.ghost_vals[p]),
                jnp.asarray(gf), block_rows=8, interpret=True,
            ))
            yg = on_rows(spmv_ell_ref(
                jnp.asarray(ell.ghost_cols[p]),
                jnp.asarray(ell.ghost_vals[p]), jnp.asarray(gf),
            ))
            ref, by_dia = ref + yg, by_dia + yg
        np.testing.assert_allclose(np.asarray(flat), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(by_dia), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        want = want_all[int(part.offsets[p]): int(part.offsets[p + 1])]
        for y in (flat, ref, by_dia):
            np.testing.assert_allclose(
                np.asarray(y)[:n_rows], want, rtol=1e-4, atol=1e-4
            )
            np.testing.assert_array_equal(np.asarray(y)[n_rows:], 0.0)


@settings(max_examples=15, deadline=None)
@given(sparse_partitions())
def test_p9_overlap_split_matches_blocked_and_host(sp):
    """The overlapped schedule equals the fused one, and both the host
    matvec, to 1e-14 relative in f64."""
    import jax

    from repro.core import PlanCache
    from repro.sparse import (
        make_distributed_spmv,
        pack_vector,
        partition_csr,
        partitioned_to_device,
        unpack_vector,
    )

    A, n_procs, seed = sp
    part = partition_csr(A, n_procs)
    ell = partitioned_to_device(part)
    mesh = jax.make_mesh((n_procs,), ("proc",),
                         devices=jax.devices()[:n_procs])
    exchange = None
    if ell.ghost_pad:
        exchange = PlanCache().collective(
            part.pattern, Topology(n_procs, 1), "standard"
        ).bind(mesh, "proc")
    x = np.random.default_rng(seed + 1).normal(size=A.ncols)
    with jax.enable_x64(True):
        xg = pack_vector(part.col_offsets, ell.in_pad, x)
        fused, split = (
            np.asarray(jax.jit(make_distributed_spmv(
                ell, mesh, "proc", exchange, overlap=ov))(xg))
            for ov in (False, True))
    want = A.matvec(x)
    scale = max(np.abs(want).max(initial=0.0), 1e-300)
    np.testing.assert_allclose(split, fused, rtol=0, atol=1e-14 * scale)
    np.testing.assert_allclose(unpack_vector(part.offsets, split), want,
                               rtol=0, atol=1e-14 * scale)


# ---------------------------------------------------------------------------
# P10: the verifier accepts everything the planners build, and rejects
# every injected corruption with a rank diagnostic
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(patterns(), st.sampled_from(["standard", "partial", "full"]))
def test_p10_verifier_accepts_built_plans(pt, strategy):
    from repro.core.collectives import build_device_plan
    from repro.verify import verify_device_plan, verify_pattern, verify_plan

    pattern, topo, _ = pt
    verify_pattern(pattern)
    plan = build_plan(pattern, topo, strategy)
    verify_plan(plan)
    verify_device_plan(build_device_plan(plan), pattern)


@settings(max_examples=15, deadline=None)
@given(sparse_partitions())
def test_p10_verifier_accepts_built_partitions(sp):
    from repro.sparse import (
        diagonal_offsets,
        partition_csr,
        partitioned_to_dia,
        partitioned_to_ell,
    )
    from repro.verify import (
        verify_device_ell,
        verify_kernel_budget,
        verify_partition,
    )

    A, n_procs, _ = sp
    part = partition_csr(A, n_procs)
    verify_partition(part)
    ell = partitioned_to_ell(part)
    verify_device_ell(ell, part)
    verify_kernel_budget(ell)
    offsets = diagonal_offsets(part)
    if offsets:
        dia = partitioned_to_dia(part, offsets)
        verify_device_ell(dia, part)
        verify_kernel_budget(dia)


@settings(max_examples=25, deadline=None)
@given(patterns(), st.sampled_from(["standard", "partial", "full"]))
def test_p10_rejects_size_mismatched_send(pt, strategy):
    """Truncating one wire message's payload (sizes still equal, so the
    Message invariant holds) must surface as a conservation failure naming
    the starved rank — the undelivered ghost slot."""
    from hypothesis import assume

    from repro.verify import VerifyError, verify_plan

    pattern, topo, _ = pt
    plan = build_plan(pattern, topo, strategy)
    wire = [m for st_ in plan.steps for m in st_.messages
            if m.src != m.dst and m.size > 0]
    assume(wire)
    m = wire[len(wire) // 2]
    m.src_idx = m.src_idx[:-1]
    m.dst_idx = m.dst_idx[:-1]
    with pytest.raises(VerifyError) as ei:
        verify_plan(plan)
    msg = str(ei.value)
    assert "rank=" in msg or "dst=" in msg, msg


@settings(max_examples=25, deadline=None)
@given(sparse_partitions())
def test_p10_rejects_dropped_ghost_column(sp):
    """Deleting the last exchange slot of a rank with ghosts must be
    rejected with a diagnostic naming that rank."""
    from hypothesis import assume

    from repro.sparse import partition_csr
    from repro.verify import VerifyError, verify_partition

    A, n_procs, _ = sp
    part = partition_csr(A, n_procs)
    victims = [p for p in range(n_procs) if len(part.needs[p])]
    assume(victims)
    p = victims[0]
    part.needs[p] = part.needs[p][:-1]
    with pytest.raises(VerifyError) as ei:
        verify_partition(part)
    assert f"rank={p}" in str(ei.value)


@settings(max_examples=25, deadline=None)
@given(patterns(), st.sampled_from(["standard", "partial", "full"]))
def test_p10_rejects_round_coloring_conflict(pt, strategy):
    """Merging two wire rounds re-creates the conflict the edge coloring
    exists to prevent (a rank doubly booked in one ppermute) — rejected
    naming the rank."""
    from hypothesis import assume

    from repro.core.plan import Round
    from repro.verify import VerifyError, verify_round_schedule

    pattern, topo, _ = pt
    plan = build_plan(pattern, topo, strategy)
    rounds = None
    for step in plan.steps:
        rs = color_rounds(step.messages)
        if len(rs) >= 2:
            rounds = rs
            break
    assume(rounds is not None)
    a, b = rounds[0], rounds[1]
    merged = Round(
        pairs=list(a.pairs) + list(b.pairs),
        src_idx=list(a.src_idx) + list(b.src_idx),
        dst_idx=list(a.dst_idx) + list(b.dst_idx),
    )
    with pytest.raises(VerifyError) as ei:
        verify_round_schedule([merged])
    assert "rank=" in str(ei.value)


# ---------------------------------------------------------------------------
# P11: dense collectives — every variant verifies and matches the jnp
# reference on random geometries with uneven counts
# ---------------------------------------------------------------------------


@st.composite
def dense_cases(draw):
    n_regions = draw(st.integers(1, 4))
    ppr = draw(st.integers(1, 4))
    n_procs = n_regions * ppr
    if n_procs < 2:
        n_procs, ppr = 2, 1
    coll = draw(st.sampled_from(["allreduce", "allgatherv",
                                 "reduce_scatter"]))
    seed = draw(st.integers(0, 2 ** 16))
    counts = np.random.default_rng(seed).integers(1, 13, size=n_procs)
    return coll, counts, Topology(n_procs, ppr), seed


@settings(max_examples=40, deadline=None)
@given(dense_cases())
def test_p11_dense_oracle_matches_reference(case):
    """Reference semantics computed independently of the schedule (f64
    host arithmetic — the device-vs-jnp equivalence at matching dtypes is
    asserted by check_dense_collectives.py and benchmarks.dense_comm)."""
    from repro.core import build_dense_plan
    from repro.core.dense import dense_variants
    from repro.verify import verify_dense_plan

    coll, counts, topo, seed = case
    rng = np.random.default_rng(seed + 1)
    if coll == "allgatherv":
        vals = [rng.normal(size=int(c)) for c in counts]
        ref = [np.concatenate(vals)] * topo.n_procs
    else:
        n = int(counts.sum())
        vals = [rng.normal(size=n) for _ in range(topo.n_procs)]
        total = np.sum(np.stack(vals), axis=0)
        if coll == "allreduce":
            ref = [total] * topo.n_procs
        else:
            segs = np.split(total, np.cumsum(counts)[:-1])
            ref = [segs[p] for p in range(topo.n_procs)]
    for variant in dense_variants(coll, topo):
        plan = build_dense_plan(coll, counts, topo, variant)
        verify_dense_plan(plan)
        got = plan.execute_numpy(vals)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(g, r, rtol=1e-12, atol=1e-12)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2 ** 16), st.integers(1, 64), st.integers(1, 4),
       st.integers(2, 16))
def test_p7_capacity_pack_invariants(seed, n_tokens, k, e_phys):
    import jax.numpy as jnp
    from repro.models.moe import MoEPlan, capacity_pack

    rng = np.random.default_rng(seed)
    k = min(k, e_phys)
    plan = MoEPlan(
        mode="a2a", ep_axes=("model",), ep_size=1, e_log=e_phys,
        e_phys=e_phys, e_per_dev=e_phys, top_k=k,
        capacity=int(rng.integers(1, 8)), region_axis="model",
        region_size=1, devs_per_region=1, uniq_capacity=8, cap_factor=1.0,
    )
    phys = np.stack([
        rng.choice(e_phys, size=k, replace=False) for _ in range(n_tokens)
    ]).astype(np.int32)
    slot, keep, slot_token = map(
        np.asarray, capacity_pack(jnp.asarray(phys), plan)
    )
    C = plan.capacity
    kept = slot[keep]
    # slots unique and in range
    assert len(np.unique(kept)) == len(kept)
    assert np.all(kept < e_phys * C)
    # per-expert occupancy <= capacity
    experts = kept // C
    _, counts = np.unique(experts, return_counts=True)
    assert np.all(counts <= C)
    # inverse map consistent
    tok = np.repeat(np.arange(n_tokens), k)[keep.reshape(-1)]
    assert np.all(slot_token[kept] == tok)
    # dropped slots point at sentinel
    assert np.all(slot[~keep] == e_phys * C)
