"""Device-resident distributed SpMV: padded ELL blocks + plan executor.

This is the device half of the paper's workload: the persistent neighborhood
collective (``core.collectives``) delivers ghost values and the ``spmv_ell``
kernels multiply the per-device local and ghost blocks.  Everything is
static-shape SPMD: each process's blocks are padded to uniform sizes so one
``shard_map`` program serves all devices.

One device layout (:class:`DeviceEll`): ``cols``/``vals`` ``[P, row_pad,
K]`` with padding entries pointing at a sentinel slot (index ``in_pad``
resp. ``ghost_pad``) that the per-device program materializes as an
appended zero.  Two choices within it come from the operator's own
structure, both made by :func:`partitioned_to_device`:

* The local block is stored **by diagonals** where the partition is square
  and its local blocks, taken together, hold ``D`` distinct
  column-minus-row offsets with ``D * value_bytes <= K * (value_bytes +
  4)`` (no more bytes than the ELL it replaces): ``vals [P, D, row_pad]``
  over the static ``offsets``, applied as ``D`` shifted dense slices of a
  zero-padded x (``kernels.spmv_ell.spmv_dia``), no gather.  Stencil fine
  levels pass; Galerkin coarse levels, R and P fail by orders of magnitude
  and keep the padded-ELL gather.

* The ghost block is ELL over the rows that hold a ghost entry.  Where at
  most half of an operator's rows hold one on every process (``2 * Rg <=
  row_pad``, ``Rg`` the largest such count), it is stored **compact**:
  ``ghost_rows [P, Rg]`` lists each process's ghost rows in ascending
  order (a process with fewer lists distinct ghost-free rows too, whose
  slots are all padding), ``ghost_cols/ghost_vals [P, Rg, Kg]`` hold
  those rows' slots, and the ghost product over ``Rg`` rows is added back
  into ``y`` at ``ghost_rows``.  Otherwise nothing would be skipped and
  the block stays row-aligned ``[P, row_pad, Kg]`` (``ghost_rows``
  None).  Either way each row adds its ghost sum, taken in the same slot
  order, to its local sum, 0.0 where it holds no ghost.

Vectors are ``[P, pad]`` as produced by :func:`pack_vector` /
``core.collectives.pack_local_values`` — zero-padded per block.

Entry points:

* :func:`partitioned_to_device` — ``PartitionedCSR ->`` :class:`DeviceEll`,
  the local block's form chosen as above (:func:`partitioned_to_ell` and
  :func:`partitioned_to_dia` build either form directly);
* :func:`make_distributed_spmv` — build ``fn(x [P, in_pad]) -> y [P,
  row_pad]`` composing exchange + matvecs.  With ``overlap=True`` the
  schedule is split: the exchange is issued first, the local block (which
  does not depend on it) is applied while the ``NeighborAlltoallV`` rounds
  are in flight, and a second program adds the ghost block's product —
  structured so XLA's async collective latency hiding can actually
  overlap the two;
* :func:`select_spmv_overlap` — cost-model overlap on/off choice
  (:class:`OverlapSelection`);
* :func:`distributed_spmv` — one-shot convenience on a numpy vector.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import numpy as np

from ..obs import default_obs
from .csr import CSR
from .partition import PartitionedCSR

_IDX_BYTES = 4  # int32 column indices
_M_NNZ = default_obs().counter(
    "sparse/spmv_nnz",
    "stored SpMV nonzeros set up, by the layout that applies them")
_M_GHOST = default_obs().counter(
    "sparse/ghost_slots",
    "ghost-block slots set up: stored by the padded layout, and the entries "
    "(nonzeros) among them")
_M_GHOST_ROWS = default_obs().counter(
    "sparse/ghost_rows",
    "ghost-block rows set up: stored by the compact layouts, and all the "
    "padded rows of every operator with ghosts")


@dataclass
class DeviceEll:
    """Stacked per-process padded-ELL blocks of a partitioned operator.

    With ``offsets`` set the local block is stored by diagonals:
    ``local_vals [P, D, row_pad]``, entry ``[p, d, i]`` the coefficient of
    local column ``i + offsets[d]`` in row ``i`` (0.0 where that column is
    outside the block, and in padded rows), and ``local_cols`` is None.
    """

    n_procs: int
    row_pad: int     # uniform padded rows per process (== output vector pad)
    in_pad: int      # uniform padded input-vector block size
    ghost_pad: int   # uniform padded ghost count (0 => no exchange needed)
    local_cols: Optional[np.ndarray]  # [P, row_pad, Kl] int32; pad -> in_pad
    local_vals: np.ndarray   # [P, row_pad, Kl], or [P, D, row_pad] diagonal
    ghost_cols: np.ndarray   # [P, row_pad or Rg, Kg] int32; pad -> ghost_pad
    ghost_vals: np.ndarray   # [P, row_pad or Rg, Kg]
    offsets: Optional[Tuple[int, ...]] = None  # ascending; diagonal layout
    # [P, Rg] int32, ascending: the rows the compact ghost block holds;
    # None when it is row-aligned
    ghost_rows: Optional[np.ndarray] = None

    @property
    def ghost_layout(self) -> str:
        """``compact``, ``rows`` (row-aligned) or ``none`` (no ghosts)."""
        if not self.ghost_pad:
            return "none"
        return "rows" if self.ghost_rows is None else "compact"


def _ell_block(
    m: CSR, row_pad: int, K: int, pad_col: int, dtype,
    rows: Optional[np.ndarray] = None,
) -> tuple:
    """``[row_pad, K]`` cols/vals of ``m``'s rows, or of the ascending
    ``rows`` alone (every entry of ``m`` on one of them), each row's
    entries in CSR order."""
    cols = np.full((row_pad, K), pad_col, dtype=np.int32)
    vals = np.zeros((row_pad, K), dtype=dtype)
    if m.nnz:
        r = m.row_indices()
        pos = np.arange(m.nnz, dtype=np.int64) - m.indptr[r]
        if rows is not None:
            r = np.searchsorted(rows, r)
        cols[r, pos] = m.indices
        vals[r, pos] = m.data
    return cols, vals


def partitioned_to_ell(part: PartitionedCSR, dtype=np.float64) -> DeviceEll:
    """Convert each process's local/ghost CSR blocks to uniformly padded ELL.

    Row padding matches the owning vector layout (max block size), so the
    output of the matvec IS the next op's input vector — no repacking
    between levels of a solve.
    """
    row_pad = int(np.diff(part.offsets).max())
    in_pad = int(np.diff(part.col_offsets).max())
    ghost_pad = int(max((len(n) for n in part.needs), default=0))
    lc, lv = _stack_ell(part.local, row_pad, in_pad, dtype)
    gr, gc, gv = _stack_ghost(part, row_pad, ghost_pad, dtype)
    return DeviceEll(part.n_procs, row_pad, in_pad, ghost_pad, lc, lv, gc, gv,
                     ghost_rows=gr)


def _count_ghost_block(part: PartitionedCSR, stored: int,
                       row_pad: int, rows: int = 0) -> None:
    """``sparse/ghost_slots``: the ``stored`` slots of a partition's ghost
    layout and the ghost ``entries`` they hold; ``sparse/ghost_rows``: the
    ``rows`` a compact ghost block stores and ``all`` of the partition's
    padded rows.  Nothing for a partition with no ghosts."""
    entries = sum(m.nnz for m in part.ghost)
    if entries:
        _M_GHOST.inc(stored, kind="stored")
        _M_GHOST.inc(entries, kind="entries")
        _M_GHOST_ROWS.inc(part.n_procs * row_pad, kind="all")
        if rows:
            _M_GHOST_ROWS.inc(rows, kind="stored")


def _ell_width(blocks) -> int:
    """The ELL width of CSR blocks: their widest row, at least 1."""
    return max((int(np.diff(m.indptr).max()) for m in blocks if m.nnz),
               default=1)


def _stack_ell(blocks, row_pad: int, pad_col: int, dtype,
               rows: Optional[np.ndarray] = None) -> tuple:
    """``[P, row_pad, K]`` cols/vals of the blocks, K their widest row;
    with ``rows [P, row_pad]`` block ``p``'s rows ``rows[p]`` alone."""
    K = _ell_width(blocks)
    cols = np.empty((len(blocks), row_pad, K), dtype=np.int32)
    vals = np.empty((len(blocks), row_pad, K), dtype=dtype)
    for p, m in enumerate(blocks):
        cols[p], vals[p] = _ell_block(
            m, row_pad, K, pad_col, dtype,
            None if rows is None else rows[p])
    return cols, vals


def _stack_ghost(part: PartitionedCSR, row_pad: int, ghost_pad: int,
                 dtype) -> tuple:
    """``(ghost_rows, cols, vals)`` of a partition's ghost blocks: compact
    over ``Rg`` rows where ``2 * Rg <= row_pad``, else row-aligned with
    ``ghost_rows`` None (see the module docstring).  O(nnz + P row_pad)."""
    live = [np.flatnonzero(np.diff(m.indptr)) for m in part.ghost]
    Rg = max((len(r) for r in live), default=0)
    if not Rg or 2 * Rg > row_pad:
        gc, gv = _stack_ell(part.ghost, row_pad, ghost_pad, dtype)
        _count_ghost_block(part, gc.size, row_pad)
        return None, gc, gv
    rows = np.empty((part.n_procs, Rg), dtype=np.int32)
    for p, r in enumerate(live):
        # pad with the first ghost-free rows: distinct, so no row is
        # written twice
        free = np.ones(row_pad, dtype=bool)
        free[r] = False
        rows[p] = np.sort(np.concatenate(
            [r, np.flatnonzero(free)[: Rg - len(r)]]))
    gc, gv = _stack_ell(part.ghost, Rg, ghost_pad, dtype, rows)
    _count_ghost_block(part, gc.size, row_pad, rows.size)
    return rows, gc, gv


def diagonal_offsets(part: PartitionedCSR) -> Optional[Tuple[int, ...]]:
    """The ascending column-minus-row offsets of a square partition's local
    blocks, taken over all processes; None for a rectangular one.

    O(nnz): one ``bincount`` per block over ``offset + row_pad``.
    """
    if not np.array_equal(part.offsets, part.col_offsets):
        return None
    row_pad = int(np.diff(part.offsets).max())
    seen = np.zeros(2 * row_pad + 1, dtype=bool)
    for m in part.local:
        if m.nnz:
            off = m.indices - m.row_indices() + row_pad
            seen |= np.bincount(off, minlength=len(seen)) > 0
    return tuple(int(o) for o in np.flatnonzero(seen) - row_pad)


def partitioned_to_dia(
    part: PartitionedCSR, offsets: Tuple[int, ...], dtype=np.float64
) -> DeviceEll:
    """Device form whose local blocks are stored by the diagonals ``offsets``
    (:func:`diagonal_offsets`); the ghost blocks as :func:`partitioned_to_ell`.

    Each (row, offset) slot holds at most one entry: local blocks are
    canonical CSR (``partitioned_from_blocks`` builds them with
    ``CSR.from_coo``, which merges duplicates).
    """
    row_pad = int(np.diff(part.offsets).max())
    ghost_pad = int(max((len(n) for n in part.needs), default=0))
    off = np.asarray(offsets, dtype=np.int64)
    vals = np.zeros((part.n_procs, len(off), row_pad), dtype=dtype)
    for p, m in enumerate(part.local):
        if m.nnz:
            rows = m.row_indices()
            vals[p, np.searchsorted(off, m.indices - rows), rows] = m.data
    gr, gc, gv = _stack_ghost(part, row_pad, ghost_pad, dtype)
    return DeviceEll(part.n_procs, row_pad, row_pad, ghost_pad, None, vals,
                     gc, gv, offsets=tuple(offsets), ghost_rows=gr)


@dataclass(frozen=True)
class OverlapSelection:
    """The exchange/compute-overlap choice for one operator, recorded on
    ``DistOp`` next to the Section-5 transport selection.  Times are
    cost-model estimates unless the caller passed a measured exchange
    time."""

    mode: str              # "on" | "off"
    exchange_s: float      # exchange time tx (full collective)
    local_s: float         # local-block compute time tl
    exposed_s: float       # exchange time left exposed by this choice
    hidden_frac: float     # fraction of tx hidden behind local compute
    overhead_s: float      # split cost (carried-y traffic + extra launch)
    forced: bool = False   # True when the mode was pinned, not selected

    def __str__(self) -> str:
        how = "forced" if self.forced else "auto"
        return (
            f"overlap={self.mode} ({how}) "
            f"tx={self.exchange_s * 1e6:.1f}us "
            f"local={self.local_s * 1e6:.1f}us "
            f"exposed={self.exposed_s * 1e6:.1f}us "
            f"hidden={self.hidden_frac:.0%} "
            f"overhead={self.overhead_s * 1e6:.1f}us"
        )


def overlap_decision(
    exchange_s: float,
    local_s: float,
    *,
    rows: int,
    value_bytes: int = 8,
    mode: str = "auto",
    has_ghost: bool = True,
) -> OverlapSelection:
    """Decide overlap on/off from an exchange time and a local compute time.

    The split schedule hides ``min(tx, tl)`` of the exchange but pays
    ``overlap_split_overhead`` (the carried output makes one extra HBM
    round trip, plus a kernel launch).  ``auto`` turns overlap on iff the
    hidden time beats that overhead; a fully local operator (no ghosts)
    has nothing to hide and is always ``off``.
    """
    from ..core.costmodel import (
        exposed_exchange_seconds,
        hidden_fraction,
        overlap_split_overhead,
    )

    tx, tl = float(exchange_s), float(local_s)
    overhead = overlap_split_overhead(rows, value_bytes=value_bytes)
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"unknown overlap mode {mode!r}")
    if mode == "auto":
        on = has_ghost and (tx - exposed_exchange_seconds(tx, tl)) > overhead
    else:
        on = mode == "on" and has_ghost
    if on:
        return OverlapSelection(
            "on", tx, tl, exposed_exchange_seconds(tx, tl),
            hidden_fraction(tx, tl), overhead, forced=(mode != "auto"),
        )
    return OverlapSelection(
        "off", tx, tl, tx if has_ghost else 0.0, 0.0, overhead,
        forced=(mode != "auto"),
    )


def select_spmv_overlap(
    part: PartitionedCSR,
    exchange_seconds: float,
    *,
    mode: str = "auto",
    value_bytes: int = 8,
) -> OverlapSelection:
    """Choose the overlap schedule for one partitioned operator.

    ``exchange_seconds`` is the modeled (``core.costmodel.plan_time``) or
    measured full-exchange time; the local compute time comes from the
    roofline compute model over the worst per-process local block.
    """
    from ..core.costmodel import spmv_compute_time

    row_pad = int(np.diff(part.offsets).max())
    in_pad = int(np.diff(part.col_offsets).max())
    ghost_pad = int(max((len(n) for n in part.needs), default=0))
    nnz_local = max((m.nnz for m in part.local), default=0)
    local_s = spmv_compute_time(
        nnz_local, row_pad, in_pad, value_bytes=value_bytes
    )
    return overlap_decision(
        float(exchange_seconds), local_s, rows=row_pad,
        value_bytes=value_bytes, mode=mode, has_ghost=ghost_pad > 0,
    )


def partitioned_to_device(part: PartitionedCSR, dtype=np.float64) -> DeviceEll:
    """Convert a partition to its device form, the local block by diagonals
    or as ELL (see the module docstring), counting its stored nonzeros in
    ``sparse/spmv_nnz`` by the layout that applies them (a diagonal form's
    ghost block counts as ``ell``).

    Diagonals are chosen when the partition is square and its ``D``
    offsets (:func:`diagonal_offsets`) take no more bytes than the ELL
    width ``K`` they replace: ``D * value_bytes <= K * (value_bytes + 4)``,
    ``value_bytes`` the itemsize of ``dtype``.
    """
    local_nnz = sum(m.nnz for m in part.local)
    ghost_nnz = sum(m.nnz for m in part.ghost)
    vb = np.dtype(dtype).itemsize
    offsets = diagonal_offsets(part)
    if offsets and len(offsets) * vb <= \
            _ell_width(part.local) * (vb + _IDX_BYTES):
        _M_NNZ.inc(local_nnz, layout="diagonal")
        _M_NNZ.inc(ghost_nnz, layout="ell")
        return partitioned_to_dia(part, offsets, dtype)
    _M_NNZ.inc(local_nnz + ghost_nnz, layout="ell")
    return partitioned_to_ell(part, dtype)


def pack_vector(offsets: np.ndarray, pad: int, x: np.ndarray) -> np.ndarray:
    """Global vector -> [P, pad] block layout (zero padding)."""
    P_ = len(offsets) - 1
    out = np.zeros((P_, pad), dtype=x.dtype)
    for p in range(P_):
        lo, hi = int(offsets[p]), int(offsets[p + 1])
        out[p, : hi - lo] = x[lo:hi]
    return out


def unpack_vector(offsets: np.ndarray, y: np.ndarray) -> np.ndarray:
    """[P, pad] block layout -> global vector."""
    P_ = len(offsets) - 1
    return np.concatenate(
        [
            np.asarray(y[p, : int(offsets[p + 1]) - int(offsets[p])])
            for p in range(P_)
        ]
    )


def make_distributed_spmv(
    ell: DeviceEll,
    mesh,
    axis_name: str,
    exchange: Optional[Callable] = None,
    overlap: bool = False,
) -> Callable:
    """Build the device distributed SpMV ``fn(x [P, in_pad]) -> [P, row_pad]``.

    ``exchange`` is a bound plan executor (``NeighborAlltoallV.bind`` /
    ``PlanCache.executor``) mapping ``[P, in_pad, 1] -> [P, ghost_pad, 1]``;
    required unless ``ell.ghost_pad == 0`` (fully local operator).  The ELL
    matvecs go through ``kernels.spmv_ell.ops`` and so follow the platform's
    ``kernels.IMPLS`` entry; a diagonal local block runs the jnp
    ``spmv_dia`` body on every platform.  A compact ghost block's product
    covers its ``Rg`` rows and is added into ``y`` at ``ghost_rows``.

    ``overlap=True`` splits the schedule into (local matvec || exchange)
    followed by a carried-output ghost matvec: the exchange is issued
    first, the local phase takes no data from it, and only the final phase
    consumes the ghost values — the dependence structure XLA's async
    collective scheduling needs to hide the ``NeighborAlltoallV`` rounds
    behind the local compute.  Both phases add the same sums in the same
    order as the fused schedule.  No-ghost operators ignore the flag (there
    is nothing to overlap).

    The product's operations sit under the named scope ``spmv``, the halo
    exchange's under ``spmv/exchange``, so a profile attributes them.
    """
    import jax

    if exchange is not None:
        exchange = jax.named_scope("exchange")(exchange)

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map
    from ..kernels.spmv_ell.ops import spmv
    from ..kernels.spmv_ell.ref import spmv_dia

    if ell.ghost_pad and exchange is None:
        raise ValueError("operator has ghost columns: exchange required")

    spec = P(axis_name)

    def shard(a):
        return jax.device_put(a, NamedSharding(mesh, spec))

    # the local block's operands and product; blocks arrive with a leading
    # device dim of 1
    if ell.offsets is not None:
        local = [shard(ell.local_vals)]

        def local_mv(x, lv):
            return spmv_dia(ell.offsets, lv[0], x)
    else:
        local = [shard(ell.local_cols), shard(ell.local_vals)]

        def local_mv(x, lc, lv):
            # sentinel slot at index in_pad
            x = jnp.concatenate([x, jnp.zeros((1,), x.dtype)])
            return spmv(lc[0], lv[0], x)
    ghost = [shard(ell.ghost_cols), shard(ell.ghost_vals)]
    if ell.ghost_rows is not None:
        ghost.append(shard(ell.ghost_rows))
    nl = len(local)
    has_ghost = ell.ghost_pad > 0

    def add_ghost(y, gh, gc, gv, *gr):
        """``y`` plus the ghost block's product: on every row, or, for a
        compact block, on its rows ``gr`` (sorted, unique) alone."""
        gh = jnp.concatenate([gh, jnp.zeros((1,), gh.dtype)])
        yg = spmv(gc[0], gv[0], gh)
        if not gr:
            return y + yg
        # placed into a zero vector, so every row adds what it added from
        # the row-aligned block, +0.0 where it holds no ghost
        return y + jnp.zeros_like(y).at[gr[0][0]].set(
            yg, indices_are_sorted=True, unique_indices=True)

    if overlap and has_ghost:
        def per_device_local(x_blk, *lops):
            return local_mv(x_blk[0], *lops)[None]

        def per_device_ghost(y_blk, gh_blk, *gops):
            return add_ghost(y_blk[0], gh_blk[0], *gops)[None]

        mm_local = shard_map(
            per_device_local, mesh=mesh, in_specs=(spec,) * (1 + nl),
            out_specs=spec, check_vma=False,
        )
        mm_ghost = shard_map(
            per_device_ghost, mesh=mesh, in_specs=(spec,) * (2 + len(ghost)),
            out_specs=spec, check_vma=False,
        )

        def spmv_fn(x):
            gh = exchange(x[..., None])[..., 0]   # issued before local work
            y = mm_local(x, *local)               # no data dep on gh
            return mm_ghost(y, gh, *ghost)

        return jax.named_scope("spmv")(spmv_fn)

    def per_device(x_blk, gh_blk, *ops):
        y = local_mv(x_blk[0], *ops[:nl])
        if has_ghost:
            y = add_ghost(y, gh_blk[0], *ops[nl:])
        return y[None]

    consts = local + ghost
    mm = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(spec,) * (2 + len(consts)),
        out_specs=spec,
        check_vma=False,
    )

    def spmv_fn(x):
        if has_ghost:
            gh = exchange(x[..., None])[..., 0]
        else:
            gh = jnp.zeros((ell.n_procs, 0), x.dtype)
        return mm(x, gh, *consts)

    return jax.named_scope("spmv")(spmv_fn)


def distributed_spmv(
    part: PartitionedCSR,
    coll,
    mesh,
    axis_name: str,
    x: np.ndarray,
    dtype=np.float64,
    overlap: str = "off",
) -> np.ndarray:
    """One-shot device distributed SpMV of a numpy vector (convenience).

    ``overlap`` is ``"on"``, ``"off"``, or ``"auto"`` (cost-model
    split-schedule selection against the plan's modeled exchange time).
    For repeated products build the function once with
    :func:`make_distributed_spmv` and jit it.
    """
    import jax

    ell = partitioned_to_device(part, dtype)
    exchange = coll.bind(mesh, axis_name) if ell.ghost_pad else None
    if overlap == "auto":
        from ..core.costmodel import TPU_V5E, plan_time

        osel = select_spmv_overlap(part, plan_time(coll.plan, TPU_V5E))
        ov = osel.mode == "on"
    else:
        if overlap not in ("on", "off"):
            raise ValueError(f"unknown overlap mode {overlap!r}")
        ov = overlap == "on" and ell.ghost_pad > 0
    fn = jax.jit(
        make_distributed_spmv(ell, mesh, axis_name, exchange, overlap=ov)
    )
    xg = pack_vector(part.col_offsets, ell.in_pad, x.astype(dtype))
    y = fn(xg)
    return unpack_vector(part.offsets, np.asarray(y))
