"""Structural verification of CommPattern / CommPlan / partition / MoE plans.

The paper's persistent neighborhood collectives hand the planner the *whole*
communication pattern, which makes whole-pattern checking possible: every
invariant the planners rely on implicitly is stated here as an explicit,
machine-checked predicate.  A violated invariant raises :class:`VerifyError`
with a diagnostic naming the offending rank / slot, instead of
manifesting downstream as a hang (a ppermute round with a doubly-booked
rank) or a silently wrong residual (a dropped or duplicated ghost value).

What each check proves:

* :func:`verify_pattern` — ownership is a bijection (every global value has
  exactly one (proc, slot) home and every local slot exactly one value) and
  every requested ghost index exists.
* :func:`verify_round_schedule` — conflict-freedom of the edge coloring: no
  rank sends or receives twice in one round, no self-pairs — the SPMD
  deadlock-freedom condition (each round is a partial permutation, i.e. one
  well-formed ``lax.ppermute``).
* :func:`verify_plan` — send/recv duality and end-to-end conservation of an
  arbitrary multi-step (aggregated / dedup'd) plan: the plan is executed
  symbolically with *global indices as the payload*, so every ghost slot
  must end up holding exactly the global index the pattern requested,
  written exactly once — no dropped, duplicated, or misrouted bytes.
* :func:`verify_partition` — every ghost column of a :class:`PartitionedCSR`
  is served by exactly one exchange slot (``needs[p][j]``), and the
  attached pattern agrees with the column ownership.
* :func:`verify_device_ell` — the device form carries exactly the
  partition's nonzeros: each nonzero lands in exactly one (row, column)
  or (diagonal, row) slot and all padding is inert.
* :func:`verify_collective` — plan checks plus the frozen device plan
  (round perms, index-array shapes and sentinel bounds).
* :func:`verify_moe_plan` / :func:`verify_moe_dispatch` — dispatch geometry
  arithmetic (replication, capacity, region factorization) and per-expert
  token conservation of the capacity-packed routing pattern.

Everything here is plain numpy over host-side plan metadata — no jax, no
devices — so the verifier can run in CI lint jobs and on plan-cache
insertion (``REPRO_VERIFY=1``) without touching the compiled hot path.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..core.plan import (
    CommPattern,
    CommPlan,
    Round,
    color_rounds,
)


class VerifyError(Exception):
    """A violated plan/kernel invariant.

    ``context`` carries the structured fields (rank, slot, ...)
    the message interpolates, so programmatic consumers need not parse
    the string.
    """

    def __init__(self, message: str, **context: Any):
        if context:
            message = f"{message} [{', '.join(f'{k}={v}' for k, v in sorted(context.items()))}]"
        super().__init__(message)
        self.context: Dict[str, Any] = context


def _fail(message: str, **context: Any) -> None:
    raise VerifyError(message, **context)


# ---------------------------------------------------------------------------
# patterns
# ---------------------------------------------------------------------------


def verify_pattern(pattern: CommPattern) -> None:
    """Ownership bijection + ghost-request validity of a CommPattern."""
    P = pattern.n_procs
    G = pattern.n_global
    if len(pattern.owner_proc) != G or len(pattern.owner_slot) != G:
        _fail("owner arrays disagree on n_global",
              owner_proc=len(pattern.owner_proc),
              owner_slot=len(pattern.owner_slot))
    if len(pattern.n_local) != P:
        _fail("n_local length != n_procs",
              n_local=len(pattern.n_local), n_procs=P)
    if G and (pattern.owner_proc.min() < 0 or pattern.owner_proc.max() >= P):
        bad = int(np.flatnonzero(
            (pattern.owner_proc < 0) | (pattern.owner_proc >= P))[0])
        _fail("owner_proc out of range", global_index=bad,
              owner=int(pattern.owner_proc[bad]), n_procs=P)
    if int(pattern.n_local.sum()) != G:
        _fail("n_local does not sum to n_global",
              sum=int(pattern.n_local.sum()), n_global=G)
    for p in range(P):
        mine = np.flatnonzero(pattern.owner_proc == p)
        slots = pattern.owner_slot[mine]
        n_p = int(pattern.n_local[p])
        if len(mine) != n_p:
            _fail("proc owns a different value count than n_local claims",
                  rank=p, owned=len(mine), n_local=n_p)
        if n_p and (slots.min() < 0 or slots.max() >= n_p):
            g = int(mine[np.argmax((slots < 0) | (slots >= n_p))])
            _fail("owner_slot out of range", rank=p, global_index=g,
                  slot=int(pattern.owner_slot[g]), n_local=n_p)
        if len(np.unique(slots)) != len(slots):
            dup = int(np.unique(slots, return_counts=True)[0][
                np.argmax(np.unique(slots, return_counts=True)[1] > 1)])
            _fail("two global values share one local slot", rank=p, slot=dup)
    for q, need in enumerate(pattern.needs):
        if len(need) and (need.min() < 0 or need.max() >= G):
            j = int(np.argmax((need < 0) | (need >= G)))
            _fail("ghost request outside the global index space",
                  rank=q, ghost_slot=j, global_index=int(need[j]),
                  n_global=G)


# ---------------------------------------------------------------------------
# round schedules (deadlock freedom)
# ---------------------------------------------------------------------------


def verify_round_schedule(rounds: Sequence[Round], step: str = "?") -> None:
    """Each round must be a partial permutation: no rank twice as a sender
    or receiver, no self-pairs — the conditions for one well-formed
    ``lax.ppermute`` (their violation is the SPMD deadlock analogue)."""
    for r, rnd in enumerate(rounds):
        seen_src: Dict[int, int] = {}
        seen_dst: Dict[int, int] = {}
        for src, dst in rnd.pairs:
            if src == dst:
                _fail("self-pair in a wire round", step=step, round=r,
                      rank=src)
            if src in seen_src:
                _fail("rank sends twice in one round", step=step, round=r,
                      rank=src)
            if dst in seen_dst:
                _fail("rank receives twice in one round", step=step,
                      round=r, rank=dst)
            seen_src[src] = dst
            seen_dst[dst] = src
        if len(rnd.src_idx) != len(rnd.pairs) or \
                len(rnd.dst_idx) != len(rnd.pairs):
            _fail("round index lists disagree with pair count", step=step,
                  round=r, pairs=len(rnd.pairs))
        for (src, dst), si, di in zip(rnd.pairs, rnd.src_idx, rnd.dst_idx):
            if len(si) != len(di):
                _fail("size-mismatched send: gather and scatter lengths "
                      "differ", step=step, round=r, src=src, dst=dst,
                      sent=len(si), received=len(di))


# ---------------------------------------------------------------------------
# plans (duality + conservation)
# ---------------------------------------------------------------------------


def _owned_ids(pattern: CommPattern) -> List[np.ndarray]:
    """Per proc: global index held at each local slot (ownership inverse)."""
    out = [np.full(int(n), -1, dtype=np.int64) for n in pattern.n_local]
    for g in range(pattern.n_global):
        out[int(pattern.owner_proc[g])][int(pattern.owner_slot[g])] = g
    return out


def verify_plan(plan: CommPlan, pattern: Optional[CommPattern] = None) -> None:
    """Full structural + conservation check of a CommPlan.

    Structural: message endpoints and buffer indices in range, step buffer
    sizes chain, every delivery slot written at most once per buffer, each
    step's wire rounds conflict-free.  Conservation: the plan is executed
    symbolically with global indices as payload — ghost slot ``j`` of rank
    ``q`` must receive exactly ``needs[q][j]``, exactly once, through every
    staging hop of an aggregated/dedup'd plan.
    """
    pattern = plan.pattern if pattern is None else pattern
    verify_pattern(pattern)
    P = plan.topo.n_procs
    if pattern.n_procs != P:
        _fail("plan topology and pattern disagree on n_procs",
              topo=P, pattern=pattern.n_procs)

    ids = _owned_ids(pattern)
    # staging buffers hold the global index of the value occupying each
    # slot (-1 = never written); writes counted per ghost slot
    bufs: List[Optional[np.ndarray]] = [None] * P
    ghost_ids = [np.full(len(need), -1, dtype=np.int64)
                 for need in pattern.needs]
    ghost_writes = [np.zeros(len(need), dtype=np.int64)
                    for need in pattern.needs]

    prev_out: Optional[np.ndarray] = None
    for step in plan.steps:
        if len(step.in_sizes) != P or len(step.out_sizes) != P:
            _fail("step buffer-size arrays not per-proc", step=step.name,
                  in_sizes=len(step.in_sizes), out_sizes=len(step.out_sizes))
        if not step.reads_local:
            if prev_out is None:
                _fail("step reads the staging chain before any step "
                      "produced it", step=step.name)
            if not np.array_equal(step.in_sizes, prev_out):
                _fail("step input sizes do not chain from the previous "
                      "step's outputs", step=step.name)
        src_bufs = ids if step.reads_local else bufs
        src_sizes = pattern.n_local if step.reads_local else step.in_sizes
        if step.writes_ghost:
            dst_bufs: List[np.ndarray] = ghost_ids
            dst_sizes = np.asarray([len(n) for n in pattern.needs])
        else:
            dst_bufs = [np.full(int(step.out_sizes[p]), -1, dtype=np.int64)
                        for p in range(P)]
            dst_sizes = step.out_sizes
        written = [np.zeros(int(dst_sizes[p]), dtype=np.int64)
                   for p in range(P)]
        for m in step.messages:
            if not (0 <= m.src < P and 0 <= m.dst < P):
                _fail("message endpoint outside the process group",
                      step=step.name, src=m.src, dst=m.dst, n_procs=P)
            if m.size == 0:
                continue
            if int(m.src_idx.min()) < 0 or \
                    int(m.src_idx.max()) >= int(src_sizes[m.src]):
                _fail("message gathers outside its source buffer",
                      step=step.name, src=m.src, dst=m.dst,
                      index=int(m.src_idx.max()),
                      buffer=int(src_sizes[m.src]))
            if int(m.dst_idx.min()) < 0 or \
                    int(m.dst_idx.max()) >= int(dst_sizes[m.dst]):
                _fail("message scatters outside its destination buffer",
                      step=step.name, src=m.src, dst=m.dst,
                      index=int(m.dst_idx.max()),
                      buffer=int(dst_sizes[m.dst]))
            src = src_bufs[m.src]
            if src is None:
                _fail("message reads a buffer no prior step wrote",
                      step=step.name, src=m.src)
            vals = src[m.src_idx]
            if np.any(vals < 0):
                j = int(m.src_idx[np.argmax(vals < 0)])
                _fail("message forwards an undefined staging slot",
                      step=step.name, src=m.src, dst=m.dst, slot=j)
            dst_bufs[m.dst][m.dst_idx] = vals
            np.add.at(written[m.dst], m.dst_idx, 1)
            if step.writes_ghost:
                np.add.at(ghost_writes[m.dst], m.dst_idx, 1)
        for p in range(P):
            if np.any(written[p] > 1):
                j = int(np.argmax(written[p] > 1))
                _fail("two messages deliver into the same slot (duplicated "
                      "bytes)", step=step.name, rank=p, slot=j)
        if not step.writes_ghost:
            bufs = dst_bufs
            prev_out = np.asarray(step.out_sizes)
        verify_round_schedule(color_rounds(step.messages), step=step.name)

    for q, need in enumerate(pattern.needs):
        for j in range(len(need)):
            if ghost_writes[q][j] == 0:
                _fail("ghost slot never written (dropped value)", rank=q,
                      ghost_slot=j, global_index=int(need[j]))
            if ghost_writes[q][j] > 1:
                _fail("ghost slot written more than once (duplicated "
                      "value)", rank=q, ghost_slot=j,
                      global_index=int(need[j]),
                      writes=int(ghost_writes[q][j]))
            if ghost_ids[q][j] != need[j]:
                _fail("ghost slot received the wrong value", rank=q,
                      ghost_slot=j, expected=int(need[j]),
                      got=int(ghost_ids[q][j]))


# ---------------------------------------------------------------------------
# bound collectives (frozen device plans)
# ---------------------------------------------------------------------------


def verify_device_plan(dplan, pattern: CommPattern) -> None:
    """The frozen per-device index arrays agree with the pattern padding
    and every wire round's perm is a partial permutation."""
    n_local_pad = int(pattern.n_local.max()) if len(pattern.n_local) else 0
    ghost_pad = int(max((len(n) for n in pattern.needs), default=0))
    if dplan.n_local_pad != n_local_pad or dplan.ghost_pad != ghost_pad:
        _fail("device plan padding disagrees with the pattern",
              n_local_pad=dplan.n_local_pad, expected_local=n_local_pad,
              ghost_pad=dplan.ghost_pad, expected_ghost=ghost_pad)
    for st in dplan.steps:
        for r, rnd in enumerate(st.rounds):
            srcs = [s for s, _ in rnd.perm]
            dsts = [d for _, d in rnd.perm]
            if len(set(srcs)) != len(srcs):
                _fail("device round has a doubly-booked sender",
                      step=st.name, round=r,
                      rank=[s for s in srcs if srcs.count(s) > 1][0])
            if len(set(dsts)) != len(dsts):
                _fail("device round has a doubly-booked receiver",
                      step=st.name, round=r,
                      rank=[d for d in dsts if dsts.count(d) > 1][0])
            for g, s, what, pad in ((rnd.gather, rnd.scatter, "gather",
                                     st.in_pad),):
                pass
            if rnd.gather.shape != (dplan.n_procs, rnd.width) or \
                    rnd.scatter.shape != (dplan.n_procs, rnd.width):
                _fail("round index arrays not [P, width]", step=st.name,
                      round=r, width=rnd.width)
            if rnd.width and int(rnd.gather.max()) > st.in_pad:
                _fail("gather index beyond the sentinel slot", step=st.name,
                      round=r, index=int(rnd.gather.max()),
                      sentinel=st.in_pad)
            if rnd.width and int(rnd.scatter.max()) > st.out_pad:
                _fail("scatter index beyond the sentinel slot",
                      step=st.name, round=r, index=int(rnd.scatter.max()),
                      sentinel=st.out_pad)


def verify_collective(coll) -> None:
    """Everything a cached ``NeighborAlltoallV`` promises: a conserving,
    conflict-free plan plus a consistent frozen device plan."""
    verify_plan(coll.plan)
    verify_device_plan(coll.device_plan, coll.plan.pattern)


# ---------------------------------------------------------------------------
# partitions + device ELL forms
# ---------------------------------------------------------------------------


def verify_partition(part) -> None:
    """Every ghost column served by exactly one exchange slot.

    ``needs[p]`` must be strictly increasing (slot -> global column is then
    injective), entirely off-block, and referenced exactly as the ghost CSR
    block's column space; the attached CommPattern must be the one
    ``from_block_partition`` derives from the same needs/ownership.
    """
    P = part.n_procs
    n_cols = int(part.col_offsets[-1])
    for p in range(P):
        clo, chi = int(part.col_offsets[p]), int(part.col_offsets[p + 1])
        need = part.needs[p]
        if len(need):
            if np.any(np.diff(need) <= 0):
                j = int(np.argmax(np.diff(need) <= 0)) + 1
                _fail("needs not strictly increasing (a ghost column is "
                      "served by two exchange slots)", rank=p, ghost_slot=j,
                      global_column=int(need[j]))
            if need.min() < 0 or need.max() >= n_cols:
                _fail("ghost column outside the global column space",
                      rank=p, global_column=int(need.max()), n_cols=n_cols)
            inblock = (need >= clo) & (need < chi)
            if np.any(inblock):
                j = int(np.argmax(inblock))
                _fail("owned column listed as a ghost", rank=p,
                      ghost_slot=j, global_column=int(need[j]))
        gh = part.ghost[p]
        if gh.ncols != len(need):
            _fail("ghost block width disagrees with the exchange slot "
                  "count", rank=p, ghost_cols=gh.ncols, slots=len(need))
        if gh.nnz:
            gidx = gh.indices.astype(np.int64)
            if gidx.min() < 0 or gidx.max() >= len(need):
                _fail("ghost nonzero references a column no exchange slot "
                      "serves (dropped ghost column)", rank=p,
                      ghost_slot=int(gidx.max()), slots=len(need))
            unused = np.setdiff1d(np.arange(len(need)), np.unique(gidx))
        else:
            unused = np.arange(len(need))
        if len(unused):
            _fail("exchange slot serves no nonzero (dead ghost column)",
                  rank=p, ghost_slot=int(unused[0]),
                  global_column=int(need[int(unused[0])]))
        loc = part.local[p]
        if loc.ncols != chi - clo:
            _fail("local block width disagrees with the column block",
                  rank=p, local_cols=loc.ncols, block=chi - clo)
        if loc.nnz and (loc.indices.min() < 0 or
                        int(loc.indices.max()) >= chi - clo):
            _fail("local nonzero outside the owned column block", rank=p,
                  column=int(loc.indices.max()), block=chi - clo)
    pat = part.pattern
    if pat.n_procs != P:
        _fail("partition pattern has the wrong process count",
              pattern=pat.n_procs, partition=P)
    if not np.array_equal(pat.n_local, np.diff(part.col_offsets)):
        _fail("pattern n_local disagrees with the column ownership")
    for p in range(P):
        if not np.array_equal(pat.needs[p], part.needs[p]):
            _fail("pattern needs disagree with the partition needs", rank=p)
    # ownership must be the block partition over col_offsets
    want_owner = np.searchsorted(part.col_offsets, np.arange(n_cols),
                                 side="right") - 1
    if not np.array_equal(pat.owner_proc, want_owner):
        g = int(np.argmax(pat.owner_proc != want_owner))
        _fail("pattern ownership disagrees with the column blocks",
              global_column=g, owner=int(pat.owner_proc[g]),
              expected=int(want_owner[g]))
    verify_pattern(pat)


def _csr_triples(m, rows_shift=0):
    """(row, col, val) triples of a CSR block's nonzero entries."""
    if not m.nnz:
        return np.zeros((0, 2), np.int64), np.zeros(0)
    rows = m.row_indices().astype(np.int64) + rows_shift
    cols = m.indices.astype(np.int64)
    keep = m.data != 0
    return np.stack([rows[keep], cols[keep]], 1), m.data[keep]


def _multiset_equal(where: str, p: int, keys_a, vals_a, keys_b, vals_b,
                    what_a: str, what_b: str) -> None:
    def order(keys, vals):
        idx = np.lexsort((vals, keys[:, 1], keys[:, 0]))
        return keys[idx], vals[idx]

    ka, va = order(keys_a, vals_a)
    kb, vb = order(keys_b, vals_b)
    if len(ka) != len(kb):
        _fail(f"{where}: nonzero counts differ", rank=p,
              **{what_a: len(ka), what_b: len(kb)})
    if len(ka) and (not np.array_equal(ka, kb) or
                    not np.array_equal(va, vb)):
        bad = np.flatnonzero(
            np.any(ka != kb, axis=1) | (va != vb))[0]
        _fail(f"{where}: nonzero multiset mismatch", rank=p,
              row=int(ka[bad, 0]), slot=int(ka[bad, 1]))


def verify_device_ell(ell, part) -> None:
    """The device ELL carries exactly the partition's nonzeros, once each,
    with padding entries pointing at the sentinel x slot with value zero; a
    local block stored by diagonals carries them at ``row + offsets[d]``; a
    compact ghost block's ``ghost_rows`` are ascending, unique, in range and
    omit no row that holds a ghost entry."""
    if ell.row_pad != int(np.diff(part.offsets).max()):
        _fail("ELL row padding disagrees with the partition",
              row_pad=ell.row_pad)
    ghost_rows = ell.ghost_rows
    if ghost_rows is None:      # row-aligned: every padded row
        ghost_rows = np.broadcast_to(np.arange(ell.row_pad),
                                     (part.n_procs, ell.row_pad))
    if ell.ghost_cols.shape[:2] != ghost_rows.shape:
        _fail("ghost block's shape disagrees with the rows it stores",
              rows=ghost_rows.shape, block=ell.ghost_cols.shape)
    for p in range(part.n_procs):
        _verify_ghost_rows(part.ghost[p], ghost_rows[p], ell.row_pad, p)
        blocks = [(part.ghost[p], ell.ghost_cols[p], ell.ghost_vals[p],
                   ell.ghost_pad, "ghost", ghost_rows[p])]
        if ell.offsets is None:
            blocks.insert(0, (part.local[p], ell.local_cols[p],
                              ell.local_vals[p], ell.in_pad, "local",
                              np.arange(ell.row_pad)))
        else:
            _verify_diagonal_block(part.local[p], ell.offsets,
                                   ell.local_vals[p], p)
        for blk, cols, vals, width, what, rows in blocks:
            live = vals != 0
            if np.any(cols[live] >= blk.ncols):
                r = int(np.argwhere(live & (cols >= blk.ncols))[0][0])
                _fail(f"ELL {what} entry references a column outside "
                      "the block", rank=p, row=int(rows[r]))
            if np.any(cols > width):
                _fail(f"ELL {what} column index beyond the sentinel",
                      rank=p, sentinel=width)
            r_idx, c_idx = np.nonzero(live)
            keys = np.stack([rows[r_idx].astype(np.int64),
                             cols[live].astype(np.int64)], 1)
            ck, cv = _csr_triples(blk)
            _multiset_equal(f"ELL {what} block", p, keys, vals[live],
                            ck, cv, "ell_nnz", "csr_nnz")


def _verify_ghost_rows(blk, rows, row_pad: int, p: int) -> None:
    """The rows a ghost block stores: strictly ascending (so unique), in
    ``[0, row_pad)``, and every row of ``blk`` with an entry among them."""
    rows = np.asarray(rows, dtype=np.int64)
    if len(rows) and (rows[0] < 0 or rows[-1] >= row_pad):
        _fail("ghost row outside the padded rows", rank=p,
              row=int(rows[0] if rows[0] < 0 else rows[-1]),
              row_pad=row_pad)
    if np.any(np.diff(rows) <= 0):
        i = int(np.argmax(np.diff(rows) <= 0))
        _fail("ghost rows not ascending and unique (a row would be written "
              "twice)", rank=p, row=int(rows[i + 1]))
    held = np.flatnonzero(np.diff(blk.indptr))
    omitted = np.setdiff1d(held, rows)
    if len(omitted):
        _fail("a row holding a ghost entry is omitted from the ghost rows "
              "(its ghost products would be dropped)", rank=p,
              row=int(omitted[0]))


def _verify_diagonal_block(blk, offsets, vals, p: int) -> None:
    """``vals [D, row_pad]`` holds exactly the block's nonzeros, each at
    ``[d, row]`` with ``col == row + offsets[d]`` inside the block."""
    if vals.shape[0] != len(offsets) or list(offsets) != sorted(offsets):
        _fail("diagonal block offsets are not ascending or disagree with "
              "its values", rank=p, offsets=len(offsets),
              diagonals=vals.shape[0])
    live = vals != 0
    d_idx, r_idx = np.nonzero(live)
    cols = r_idx + np.asarray(offsets, dtype=np.int64)[d_idx]
    if np.any((cols < 0) | (cols >= blk.ncols)) or \
            np.any(r_idx >= blk.nrows):
        _fail("diagonal entry outside the local block", rank=p)
    keys = np.stack([r_idx.astype(np.int64), cols], 1)
    ck, cv = _csr_triples(blk)
    _multiset_equal("diagonal local block", p, keys, vals[live], ck, cv,
                    "dia_nnz", "csr_nnz")


# ---------------------------------------------------------------------------
# MoE dispatch plans (token conservation)
# ---------------------------------------------------------------------------


def verify_moe_plan(plan) -> None:
    """Geometry arithmetic of an ``MoEPlan``: replication, capacity and the
    region factorization must be internally consistent."""
    if plan.e_log <= 0 or plan.e_phys <= 0 or plan.ep_size <= 0:
        _fail("non-positive MoE geometry", e_log=plan.e_log,
              e_phys=plan.e_phys, ep_size=plan.ep_size)
    if plan.e_phys % plan.e_log != 0:
        _fail("physical experts not a whole replication of logical ones",
              e_phys=plan.e_phys, e_log=plan.e_log)
    if plan.e_phys % plan.ep_size != 0:
        _fail("physical experts do not pack evenly onto the EP group",
              e_phys=plan.e_phys, ep_size=plan.ep_size)
    if plan.e_per_dev * plan.ep_size != plan.e_phys:
        _fail("e_per_dev inconsistent with e_phys / ep_size",
              e_per_dev=plan.e_per_dev, e_phys=plan.e_phys,
              ep_size=plan.ep_size)
    if plan.capacity <= 0:
        _fail("non-positive expert capacity", capacity=plan.capacity)
    if plan.mode != "dense":
        if plan.region_size * plan.devs_per_region != plan.ep_size:
            _fail("region factorization does not cover the EP group",
                  region_size=plan.region_size,
                  devs_per_region=plan.devs_per_region,
                  ep_size=plan.ep_size)
        pair_bound = plan.devs_per_region * plan.e_per_dev * plan.capacity
        if plan.uniq_capacity > pair_bound:
            _fail("uniq_capacity exceeds the exact per-region bound",
                  uniq_capacity=plan.uniq_capacity, bound=pair_bound)
    if plan.top_k > plan.e_log:
        _fail("top_k exceeds the number of logical experts",
              top_k=plan.top_k, e_log=plan.e_log)


def verify_moe_dispatch(plan, tokens_per_lane: int) -> None:
    """Token conservation of the capacity-packed dispatch pattern.

    Synthesizes the plan's routing pattern and checks: every lane owns
    exactly ``tokens_per_lane`` token values; no token is shipped more than
    ``top_k`` times; no (source lane, destination device) pair exceeds the
    hard ``e_per_dev * capacity`` bound; and the transport plan built for
    the plan's own mode conserves the pattern end to end.
    """
    from ..core.locality import build_plan
    from ..models.moe import (
        STRATEGY_OF_MODE,
        dispatch_pattern,
        dispatch_topology,
    )

    verify_moe_plan(plan)
    if plan.mode == "dense":
        return
    pattern, _stats, _fp = dispatch_pattern(plan, int(tokens_per_lane))
    verify_pattern(pattern)
    if pattern.n_procs != plan.ep_size:
        _fail("dispatch pattern lane count disagrees with the EP group",
              lanes=pattern.n_procs, ep_size=plan.ep_size)
    if np.any(pattern.n_local != tokens_per_lane):
        q = int(np.argmax(pattern.n_local != tokens_per_lane))
        _fail("lane owns the wrong token count", rank=q,
              n_local=int(pattern.n_local[q]), tokens=tokens_per_lane)
    # each kept (token, k) pair is one push: a token value may be requested
    # at most top_k times across the whole group
    counts = np.zeros(pattern.n_global, dtype=np.int64)
    for need in pattern.needs:
        np.add.at(counts, need, 1)
    if counts.max(initial=0) > plan.top_k:
        g = int(np.argmax(counts))
        _fail("token shipped more often than top_k routes allow",
              global_index=g, copies=int(counts[g]), top_k=plan.top_k)
    # per (src lane, dst device): at most capacity per hosted expert
    bound = plan.e_per_dev * plan.capacity
    for q, need in enumerate(pattern.needs):
        if not len(need):
            continue
        per_src = np.bincount(pattern.owner_proc[need],
                              minlength=plan.ep_size)
        if per_src.max() > bound:
            src = int(np.argmax(per_src))
            _fail("capacity overflow: lane ships more tokens to a device "
                  "than its experts can seat", src=src, dst=q,
                  shipped=int(per_src.max()), bound=bound)
    cplan = build_plan(pattern, dispatch_topology(plan),
                       STRATEGY_OF_MODE[plan.mode])
    verify_plan(cplan, pattern)


# ---------------------------------------------------------------------------
# dense collective plans (conflict-freedom + contribution conservation)
# ---------------------------------------------------------------------------


def verify_dense_plan(plan) -> None:
    """Full check of a ``core.dense.DensePlan``.

    Structural: one segment per device, non-negative counts, in-range and
    duplicate-free segment lists.  Conflict-freedom: every round reduces to
    a :class:`Round` and must pass :func:`verify_round_schedule` (partial
    permutation = one well-formed ppermute).  Conservation: the schedule is
    executed symbolically with *contribution vectors* as payload —
    ``contrib[p][s]`` is the 0/1 vector of source devices whose
    contribution to segment ``s`` device ``p`` currently holds — and the
    final state must be exactly the collective's definition: allreduce →
    every device holds every contribution of every segment; reduce_scatter
    → device ``p`` holds every contribution of segment ``p``; allgatherv →
    every device holds exactly the owner's copy of every segment.
    """
    P = plan.topo.n_procs
    n_seg = len(plan.counts)
    if n_seg != P:
        _fail("dense plan must carry one segment per device",
              segments=n_seg, n_procs=P)
    if np.any(plan.counts < 0):
        s = int(np.argmax(plan.counts < 0))
        _fail("negative segment count", segment=s,
              count=int(plan.counts[s]))
    if plan.collective not in ("allreduce", "allgatherv", "reduce_scatter"):
        _fail("unknown dense collective", collective=plan.collective)

    for r, rnd in enumerate(plan.rounds):
        if len(rnd.segs) != len(rnd.pairs):
            _fail("dense round segment lists disagree with pair count",
                  round=r, pairs=len(rnd.pairs), segs=len(rnd.segs))
        for (src, dst), segs in zip(rnd.pairs, rnd.segs):
            if len(segs) and (segs.min() < 0 or segs.max() >= n_seg):
                _fail("dense round moves a segment outside the plan",
                      round=r, src=src, dst=dst,
                      segment=int(segs.max()), segments=n_seg)
            if len(np.unique(segs)) != len(segs):
                _fail("dense round sends a segment twice in one message",
                      round=r, src=src, dst=dst)
    verify_round_schedule(
        [Round(list(r.pairs), list(r.segs), list(r.segs))
         for r in plan.rounds],
        step=f"dense/{plan.collective}/{plan.variant}",
    )

    # symbolic execution with contribution-set payloads
    eye = np.eye(P, dtype=np.int64)
    if plan.collective == "allgatherv":
        contrib = [np.zeros((n_seg, P), dtype=np.int64) for _ in range(P)]
        for p in range(P):
            contrib[p][p] = eye[p]
    else:
        contrib = [np.tile(eye[p], (n_seg, 1)) for p in range(P)]
    for r, rnd in enumerate(plan.rounds):
        payloads = [
            (dst, segs, contrib[src][segs].copy())
            for (src, dst), segs in zip(rnd.pairs, rnd.segs)
        ]
        for dst, segs, pay in payloads:
            if rnd.reduce:
                contrib[dst][segs] += pay
            else:
                contrib[dst][segs] = pay

    ones = np.ones(P, dtype=np.int64)
    for p in range(P):
        if plan.collective == "allreduce":
            bad = np.flatnonzero(~(contrib[p] == ones).all(axis=1))
            if len(bad):
                s = int(bad[0])
                _fail("allreduce segment not an exact sum of all "
                      "contributions", rank=p, segment=s,
                      contributions=contrib[p][s].tolist())
        elif plan.collective == "reduce_scatter":
            if not np.array_equal(contrib[p][p], ones):
                _fail("reduce_scatter own segment not an exact sum of all "
                      "contributions", rank=p,
                      contributions=contrib[p][p].tolist())
        else:  # allgatherv
            if not np.array_equal(contrib[p], eye):
                s = int(np.argmax((contrib[p] != eye).any(axis=1)))
                _fail("allgatherv segment is not exactly the owner's copy "
                      "(dropped, duplicated or summed values)", rank=p,
                      segment=s, contributions=contrib[p][s].tolist())


# ---------------------------------------------------------------------------
# cache-insertion dispatch (the REPRO_VERIFY hook)
# ---------------------------------------------------------------------------


def verify_cache_value(ns: str, value) -> None:
    """Verify a value entering a ``PlanCache`` namespace.

    Collectives get the full plan + device-plan check; MoE plan entries
    (stored as ``(plan, init_seconds)``) get the geometry check — the
    token-level :func:`verify_moe_dispatch` needs the token count, which
    the cache does not see, and runs in ``verify_zoo`` / engine verify.
    Executor namespaces hold opaque callables; their jaxpr audit happens
    where the collective is still in scope (``PlanCache.executor``).
    """
    if ns == "collective":
        verify_collective(value)
    elif ns == "moe_plan":
        plan = value[0] if isinstance(value, tuple) else value
        if hasattr(plan, "e_phys"):
            verify_moe_plan(plan)
    elif ns == "dense_plan":
        # stored as ((DensePlan, DenseSelection), init_seconds) — unwrap
        # tuples until the object with a round schedule surfaces
        plan = value
        while isinstance(plan, tuple) and not hasattr(plan, "rounds"):
            plan = plan[0]
        if hasattr(plan, "rounds"):
            verify_dense_plan(plan)
