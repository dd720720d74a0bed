"""Device-resident distributed SpMV: padded ELL blocks + plan executor.

This is the device half of the paper's workload: the persistent neighborhood
collective (``core.collectives``) delivers ghost values and the ``spmv_ell``
kernels multiply the per-device local and ghost blocks.  Everything is
static-shape SPMD: each process's blocks are padded to uniform sizes so one
``shard_map`` program serves all devices.

Two device layouts, selected per operator by VMEM footprint
(:func:`select_spmv_kernel`):

* **flat** (:class:`DeviceEll`): ``cols``/``vals`` ``[P, row_pad, K]`` with
  padding entries pointing at a sentinel slot (index ``in_pad`` resp.
  ``ghost_pad``) that the per-device program materializes as an appended
  zero.  The whole per-device x (local + ghost) is VMEM-resident in the
  kernel — right for coarse levels and small blocks.

  Its local block has two forms, chosen per operator from the block's own
  structure.  **ell**: the padded-ELL gather above.  **diagonal**: a square
  partition whose local blocks, taken together, hold ``D`` distinct
  column-minus-row offsets, with ``D * value_bytes <= K * (value_bytes +
  4)`` (no more bytes than the ELL it replaces), is stored as ``vals [P, D,
  row_pad]`` over the static ``offsets`` and applied as ``D`` shifted dense
  slices of a zero-padded x (``kernels.spmv_ell.spmv_dia``): no gather.
  Stencil fine levels pass; Galerkin coarse levels, R and P fail by orders
  of magnitude and keep the gather.

  The ghost block is ELL over the rows that hold a ghost entry.  Where at
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

* **column-blocked** (:class:`DeviceEllBlocked`): each row's nonzeros are
  reordered into column buckets of ``block_cols`` x entries; local columns
  fill the leading buckets, ghost columns the *trailing* buckets, so the
  halo-dependent partial products land in the last accumulation steps of
  the kernel's sequential column-bucket grid dim.  Per-bucket nonzero
  widths (``bucket_K``) are padded to one uniform K so a single BlockSpec
  serves every grid step; padding entries are (in-bucket col 0, val 0.0).
  VMEM residency is then independent of the x length — the production path
  for paper-scale fine levels.

Vectors are ``[P, pad]`` as produced by :func:`pack_vector` /
``core.collectives.pack_local_values`` — zero-padded per block.

Entry points:

* :func:`partitioned_to_ell` / :func:`partitioned_to_ell_blocked` —
  ``PartitionedCSR ->`` device form conversions;
* :func:`select_spmv_kernel` — modeled-VMEM flat-vs-blocked choice
  (threshold overridable via ``REPRO_SPMV_VMEM_LIMIT_BYTES`` or argument)
  and, under ``auto``, the flat layout's diagonal-or-ELL local block
  (:func:`diagonal_offsets`);
* :func:`make_distributed_spmv` — build ``fn(x [P, in_pad]) -> y [P,
  row_pad]`` composing exchange + ELL matvec(s) for either layout.  With
  ``overlap=True`` the schedule is split: the exchange is issued first,
  the local buckets (which do not depend on it) accumulate while the
  ``NeighborAlltoallV`` rounds are in flight, and a second carried-output
  kernel consumes the ghost buckets — structured so XLA's async collective
  latency hiding can actually overlap the two;
* :func:`select_spmv_overlap` — cost-model overlap on/off choice
  (:class:`OverlapSelection`), the Section-5-style companion of
  :func:`select_spmv_kernel`;
* :func:`row_block_bucket_map` — per-row-block live-bucket lists for the
  bucket-skipping kernel (shared by the fused and overlapped schedules);
* :func:`distributed_spmv` — one-shot convenience on a numpy vector.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Callable, Optional, Tuple, Union

import numpy as np

from ..kernels.spmv_ell import DEFAULT_BLOCK_COLS, DEFAULT_BLOCK_ROWS
from ..obs import default_obs
from .csr import CSR
from .partition import PartitionedCSR

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


def _stack_ell(blocks, row_pad: int, pad_col: int, dtype,
               rows: Optional[np.ndarray] = None) -> tuple:
    """``[P, row_pad, K]`` cols/vals of the blocks, K their widest row;
    with ``rows [P, row_pad]`` block ``p``'s rows ``rows[p]`` alone."""
    K = max(max((int(np.diff(m.indptr).max()) for m in blocks if m.nnz),
                default=0), 1)
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
    """Flat form whose local blocks are stored by the diagonals ``offsets``
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


@dataclass
class DeviceEllBlocked:
    """Column-bucketed padded-ELL blocks for the blocked SpMV kernel.

    One structure covers local *and* ghost columns: the per-device gather
    space is ``[local values | zero-fill to bucket edge | ghost values |
    zero-fill]`` of length ``n_buckets * block_cols``; bucket ``j`` of
    ``cols``/``vals`` (columns [j*K, (j+1)*K)) holds in-bucket indices into
    x slice ``j``.  Ghost columns occupy the trailing ``n_ghost_buckets``
    buckets, so halo-dependent work runs in the kernel's last accumulation
    steps.
    """

    n_procs: int
    row_pad: int     # uniform padded rows per process (== output vector pad)
    in_pad: int      # uniform padded input-vector block size
    ghost_pad: int   # uniform padded ghost count (0 => no exchange needed)
    block_cols: int
    n_local_buckets: int
    n_ghost_buckets: int
    K: int                   # uniform per-bucket padded width (max bucket_K)
    cols: np.ndarray         # [P, row_pad, n_buckets*K] int32 in-bucket idx
    vals: np.ndarray         # [P, row_pad, n_buckets*K]
    bucket_K: np.ndarray     # [n_buckets] max nnz of each bucket pre-padding

    @property
    def n_buckets(self) -> int:
        return self.n_local_buckets + self.n_ghost_buckets

    @property
    def x_len(self) -> int:
        return self.n_buckets * self.block_cols

    @property
    def ghost_layout(self) -> str:
        """``rows`` (its ghost buckets span every row) or ``none``."""
        return "rows" if self.ghost_pad else "none"


def _bucket_positions(rows: np.ndarray, buckets: np.ndarray, n_buckets: int):
    """Occurrence index of each entry within its (row, bucket) group."""
    key = rows.astype(np.int64) * n_buckets + buckets
    order = np.argsort(key, kind="stable")
    ks = key[order]
    new = np.concatenate([[True], ks[1:] != ks[:-1]])
    starts = np.flatnonzero(new)
    group = np.cumsum(new) - 1
    pos_sorted = np.arange(len(key)) - starts[group]
    pos = np.empty(len(key), dtype=np.int64)
    pos[order] = pos_sorted
    return pos


def _bucketed(m: CSR, bc: int, bucket0: int):
    """CSR block entries as (rows, buckets, in-bucket cols, vals)."""
    if not m.nnz:
        z = np.zeros(0, dtype=np.int64)
        return z, z, z, np.zeros(0)
    rows = m.row_indices().astype(np.int64)
    cols = m.indices.astype(np.int64)
    return rows, bucket0 + cols // bc, cols % bc, m.data


def partitioned_to_ell_blocked(
    part: PartitionedCSR,
    block_cols: int = DEFAULT_BLOCK_COLS,
    dtype=np.float64,
) -> DeviceEllBlocked:
    """Convert a partition to the column-bucketed blocked-ELL device form.

    Row padding matches :func:`partitioned_to_ell` so the two layouts are
    interchangeable level by level.  Each row's nonzeros are reordered into
    column buckets (local buckets first, ghost buckets trailing); per-bucket
    widths are recorded in ``bucket_K`` and padded to their max so one
    BlockSpec serves all grid steps of the blocked kernel.
    """
    P_ = part.n_procs
    bc = int(block_cols)
    assert bc > 0, bc
    row_pad = int(np.diff(part.offsets).max())
    in_pad = int(np.diff(part.col_offsets).max())
    ghost_pad = int(max((len(n) for n in part.needs), default=0))
    Cl = max(-(-in_pad // bc), 1)
    Cg = -(-ghost_pad // bc)
    C = Cl + Cg

    entries = []
    bucket_K = np.zeros(C, dtype=np.int64)
    for p in range(P_):
        rows_l, b_l, c_l, v_l = _bucketed(part.local[p], bc, 0)
        rows_g, b_g, c_g, v_g = _bucketed(part.ghost[p], bc, Cl)
        rows = np.concatenate([rows_l, rows_g])
        buckets = np.concatenate([b_l, b_g])
        incols = np.concatenate([c_l, c_g])
        vals = np.concatenate([v_l, v_g])
        entries.append((rows, buckets, incols, vals))
        if len(rows):
            cnt = np.bincount(rows * C + buckets, minlength=row_pad * C)
            bucket_K = np.maximum(bucket_K, cnt.reshape(row_pad, C).max(0))
    K = max(int(bucket_K.max()), 1)

    cols = np.zeros((P_, row_pad, C * K), dtype=np.int32)
    vals_out = np.zeros((P_, row_pad, C * K), dtype=dtype)
    for p, (rows, buckets, incols, vals) in enumerate(entries):
        if not len(rows):
            continue
        pos = _bucket_positions(rows, buckets, C)
        slot = buckets * K + pos
        cols[p, rows, slot] = incols
        vals_out[p, rows, slot] = vals
    _count_ghost_block(part, P_ * row_pad * Cg * K, row_pad)
    return DeviceEllBlocked(
        P_, row_pad, in_pad, ghost_pad, bc, Cl, Cg, K, cols, vals_out,
        bucket_K,
    )


# --------------------------------------------------------------- selection
#: VMEM per core that runs off the TPU (tests, CPU examples) model: a
#: 16 MiB core, small enough that test-sized operators exercise both
#: layouts.  On a TPU the figure comes from ``repro.chips``.
MODELED_VMEM_BYTES = 16 * 2 ** 20
_IDX_BYTES = 4  # int32 column indices


def vmem_bytes_per_core() -> int:
    """VMEM per core of the device this process runs on.

    A TPU's figure is looked up by ``device_kind`` in
    :data:`repro.chips.CHIPS`; a kind missing there raises.
    """
    import jax

    from ..chips import chip

    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return chip(dev.device_kind).vmem_bytes
    return MODELED_VMEM_BYTES


def default_spmv_vmem_limit() -> int:
    """Flat-vs-blocked threshold: half a core's VMEM (double buffering and
    headroom for the rest of the fused program);
    ``REPRO_SPMV_VMEM_LIMIT_BYTES`` overrides."""
    env = os.environ.get("REPRO_SPMV_VMEM_LIMIT_BYTES")
    return int(env) if env else vmem_bytes_per_core() // 2


def spmv_flat_vmem_bytes(
    *,
    in_pad: int,
    ghost_pad: int,
    k_local: int,
    k_ghost: int,
    value_bytes: int = 8,
    rows: Optional[int] = None,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    ghost_rows: Optional[int] = None,
) -> int:
    """Modeled per-device VMEM residency of the flat SpMV path.

    The flat path is two kernels (local + ghost matvec); this budget sums
    both deliberately — inside the fused jitted program XLA is free to
    schedule them concurrently (exchange/compute overlap is the point of
    the design), so near the threshold the conservative assumption is that
    both x vectors and both double-buffered cols/vals streams are resident
    at once.  ``rows`` clamps the row block exactly like the kernel does
    (``min(block_rows, R)``); ``ghost_rows`` clamps the ghost launch's
    (a compact ghost block's ``Rg``; ``rows`` when not given, which
    bounds it).
    """
    br = min(int(block_rows), int(rows)) if rows else int(block_rows)
    brg = min(br, int(ghost_rows)) if ghost_rows else br
    x_bytes = (in_pad + 1 + ghost_pad + (1 if ghost_pad else 0)) * value_bytes
    stream = 2 * (br * k_local + brg * k_ghost) * (_IDX_BYTES + value_bytes)
    out = br * value_bytes
    return int(x_bytes + stream + out)


def spmv_blocked_vmem_bytes(
    *,
    bucket_k: int,
    value_bytes: int = 8,
    rows: Optional[int] = None,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_cols: int = DEFAULT_BLOCK_COLS,
) -> int:
    """Modeled per-device VMEM residency of the column-blocked SpMV path:
    one x bucket + one cols/vals bucket block, double-buffered — independent
    of the x length."""
    br = min(int(block_rows), int(rows)) if rows else int(block_rows)
    bc = int(block_cols)
    x_bytes = 2 * bc * value_bytes
    stream = 2 * br * bucket_k * (_IDX_BYTES + value_bytes)
    out = br * value_bytes
    return int(x_bytes + stream + out)


@dataclass(frozen=True)
class KernelSelection:
    """The flat-vs-blocked choice for one operator, and the flat layout's
    diagonal-or-ELL local block, recorded alongside the plan's Section-5
    transport choice so the selections are inspectable."""

    variant: str            # "flat" | "blocked"
    flat_bytes: int         # modeled flat footprint
    blocked_bytes: int      # modeled blocked footprint (bucket-K upper bound)
    limit_bytes: int        # threshold the choice was made against
    forced: bool = False    # True when the variant was pinned, not selected
    offsets: Tuple[int, ...] = ()   # the local block's diagonals; () = ELL

    @property
    def local_layout(self) -> str:
        return "diagonal" if self.offsets else "ell"

    def __str__(self) -> str:
        how = "forced" if self.forced else "auto"
        local = (f"diagonal(D={len(self.offsets)})" if self.offsets
                 else "ell")
        return (
            f"kernel={self.variant} ({how}) local={local} "
            f"flat={self.flat_bytes / 2**10:.0f}KiB "
            f"blocked={self.blocked_bytes / 2**10:.0f}KiB "
            f"limit={self.limit_bytes / 2**10:.0f}KiB"
        )


def _ell_widths(part: PartitionedCSR) -> tuple:
    kl = max(
        max((int(np.diff(m.indptr).max()) for m in part.local if m.nnz),
            default=0), 1,
    )
    kg = max(
        max((int(np.diff(m.indptr).max()) for m in part.ghost if m.nnz),
            default=0), 1,
    )
    return kl, kg


def select_spmv_kernel(
    part: PartitionedCSR,
    *,
    variant: str = "auto",
    vmem_limit_bytes: Optional[int] = None,
    value_bytes: int = 8,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_cols: int = DEFAULT_BLOCK_COLS,
) -> KernelSelection:
    """Choose the SpMV device layout for one partitioned operator.

    ``variant="auto"`` compares the modeled flat footprint (whole x
    VMEM-resident) against the threshold and falls over to the blocked
    kernel when it does not fit; ``"flat"``/``"blocked"`` pin the choice
    (recorded as forced).  The blocked estimate uses the max row width as a
    bucket-K upper bound — packing can only shrink it.

    An ``auto`` flat choice stores the local block by diagonals when the
    partition is square and its ``D`` offsets (:func:`diagonal_offsets`)
    take no more bytes than the ELL width ``K`` they replace: ``D *
    value_bytes <= K * (value_bytes + 4)``.  A pinned variant keeps the
    ELL gather.
    """
    limit = (default_spmv_vmem_limit()
             if vmem_limit_bytes is None else int(vmem_limit_bytes))
    row_pad = int(np.diff(part.offsets).max())
    in_pad = int(np.diff(part.col_offsets).max())
    ghost_pad = int(max((len(n) for n in part.needs), default=0))
    kl, kg = _ell_widths(part)
    flat = spmv_flat_vmem_bytes(
        in_pad=in_pad, ghost_pad=ghost_pad, k_local=kl, k_ghost=kg,
        value_bytes=value_bytes, rows=row_pad, block_rows=block_rows,
    )
    blocked = spmv_blocked_vmem_bytes(
        bucket_k=max(kl, kg), value_bytes=value_bytes,
        rows=row_pad, block_rows=block_rows, block_cols=block_cols,
    )
    if variant == "auto":
        if flat > limit:
            return KernelSelection("blocked", flat, blocked, limit)
        offsets = diagonal_offsets(part)
        if offsets and len(offsets) * value_bytes <= \
                kl * (value_bytes + _IDX_BYTES):
            return KernelSelection("flat", flat, blocked, limit,
                                   offsets=offsets)
        return KernelSelection("flat", flat, blocked, limit)
    if variant not in ("flat", "blocked"):
        raise ValueError(f"unknown spmv variant {variant!r}")
    return KernelSelection(variant, flat, blocked, limit, forced=True)


def row_block_bucket_map(
    ell: DeviceEllBlocked,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    bucket_lo: int = 0,
    bucket_hi: Optional[int] = None,
) -> tuple:
    """Per-row-block live-bucket lists for the bucket-skipping kernel.

    Returns ``(lists [P, NRB, M] int32, counts [P, NRB] int32)`` where row
    block ``i`` of process ``p`` touches exactly the buckets
    ``lists[p, i, :counts[p, i]]`` (absolute bucket ids, ascending) within
    the window [bucket_lo, bucket_hi).  ``M`` is the global max count
    (min 1); padding entries hold ``bucket_lo`` and are masked by the
    kernel.  The row blocking mirrors the kernel's
    (``min(block_rows, row_pad)`` with a padded trailing block), so the
    lists line up with its grid.  The overlap schedule builds one map per
    phase from the same call with the phase's bucket window.
    """
    C, K = ell.n_buckets, ell.K
    lo = int(bucket_lo)
    hi = C if bucket_hi is None else int(bucket_hi)
    assert 0 <= lo < hi <= C, (lo, hi, C)
    R = ell.row_pad
    br = min(int(block_rows), R)
    pad = (-R) % br
    nrb = (R + pad) // br
    W = hi - lo
    live = (ell.vals.reshape(ell.n_procs, R, C, K) != 0).any(-1)[:, :, lo:hi]
    if pad:
        live = np.concatenate(
            [live, np.zeros((ell.n_procs, pad, W), bool)], axis=1
        )
    live_rb = live.reshape(ell.n_procs, nrb, br, W).any(2)   # [P, NRB, W]
    counts = live_rb.sum(-1).astype(np.int32)
    M = max(int(counts.max()), 1)
    lists = np.full((ell.n_procs, nrb, M), lo, dtype=np.int32)
    for p in range(ell.n_procs):
        for rb in range(nrb):
            idx = np.flatnonzero(live_rb[p, rb])
            lists[p, rb, : len(idx)] = idx + lo
    return lists, counts


@dataclass(frozen=True)
class OverlapSelection:
    """The exchange/compute-overlap choice for one operator, recorded on
    ``DistOp`` next to the Section-5 transport and flat-vs-blocked kernel
    selections.  Times are cost-model estimates unless the caller passed a
    measured exchange time."""

    mode: str              # "on" | "off"
    exchange_s: float      # exchange time tx (full collective)
    local_s: float         # local-bucket compute time tl
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


def partitioned_to_device(
    part: PartitionedCSR,
    selection: KernelSelection,
    dtype=np.float64,
    block_cols: int = DEFAULT_BLOCK_COLS,
) -> Union[DeviceEll, "DeviceEllBlocked"]:
    """Convert a partition to the device form the selection calls for,
    counting its stored nonzeros in ``sparse/spmv_nnz`` by the layout that
    applies them (a diagonal form's ghost block counts as ``ell``)."""
    local_nnz = sum(m.nnz for m in part.local)
    ghost_nnz = sum(m.nnz for m in part.ghost)
    if selection.offsets:
        _M_NNZ.inc(local_nnz, layout="diagonal")
        _M_NNZ.inc(ghost_nnz, layout="ell")
        return partitioned_to_dia(part, selection.offsets, dtype)
    _M_NNZ.inc(local_nnz + ghost_nnz, layout="ell")
    if selection.variant == "blocked":
        return partitioned_to_ell_blocked(part, block_cols, dtype)
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
    ell: Union[DeviceEll, DeviceEllBlocked],
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
    covers its ``Rg`` rows and is added into ``y`` at ``ghost_rows``.  A
    :class:`DeviceEllBlocked` selects the column-blocked kernel: local and
    ghost values are concatenated into the bucketed gather space and one
    accumulating kernel covers both (ghost buckets trail, so halo-dependent
    work lands in the last accumulation steps).

    ``overlap=True`` splits the schedule into (local matvec || exchange)
    followed by a carried-output ghost matvec: the exchange is issued
    first, the local phase takes no data from it, and only the final phase
    consumes the ghost values — the dependence structure XLA's async
    collective scheduling needs to hide the ``NeighborAlltoallV`` rounds
    behind the local compute.  Both phases accumulate buckets in the same
    ascending order as the fused schedule.  No-ghost operators ignore the
    flag (there is nothing to overlap).

    The product's operations sit under the named scope ``spmv``, the halo
    exchange's under ``spmv/exchange``, so a profile attributes them.
    """
    import jax

    if exchange is not None:
        exchange = jax.named_scope("exchange")(exchange)
    if isinstance(ell, DeviceEllBlocked):
        return jax.named_scope("spmv")(_make_distributed_spmv_blocked(
            ell, mesh, axis_name, exchange, overlap
        ))

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


def _make_distributed_spmv_blocked(
    ell: DeviceEllBlocked,
    mesh,
    axis_name: str,
    exchange: Optional[Callable] = None,
    overlap: bool = False,
) -> Callable:
    """Blocked-layout counterpart of :func:`make_distributed_spmv`.

    Both the fused and the overlapped schedule go through the
    bucket-skipping kernel whenever :func:`row_block_bucket_map` shows at
    least one row block skipping at least one bucket of its window (banded
    operators touch few buckets per row block); otherwise the dense
    blocked/partial kernels stream every bucket.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map
    from ..kernels.spmv_ell.ops import (
        spmv_blocked,
        spmv_blocked_partial,
        spmv_blocked_skip,
    )

    if ell.ghost_pad and exchange is None:
        raise ValueError("operator has ghost columns: exchange required")

    spec = P(axis_name)

    def shard(a):
        return jax.device_put(a, NamedSharding(mesh, spec))

    consts = [shard(ell.cols), shard(ell.vals)]
    has_ghost = ell.ghost_pad > 0
    bc = ell.block_cols
    C, Cl = ell.n_buckets, ell.n_local_buckets
    local_fill = Cl * bc - ell.in_pad
    ghost_fill = ell.n_ghost_buckets * bc - ell.ghost_pad

    if overlap and has_ghost:
        llists, lcounts = row_block_bucket_map(ell, bucket_hi=Cl)
        glists, gcounts = row_block_bucket_map(ell, bucket_lo=Cl)
        local_skip = llists.shape[2] < Cl
        ghost_skip = glists.shape[2] < C - Cl
        consts_l = consts + (
            [shard(llists), shard(lcounts)] if local_skip else []
        )
        consts_g = consts + (
            [shard(glists), shard(gcounts)] if ghost_skip else []
        )

        def per_device_local(x_blk, cols, vals, *sk):
            xl = jnp.concatenate(
                [x_blk[0], jnp.zeros((local_fill,), x_blk.dtype)]
            )
            if local_skip:
                bl, cnt = sk
                y = spmv_blocked_skip(
                    cols[0], vals[0], xl, bl[0], cnt[0],
                    n_buckets=C, block_cols=bc,
                )
            else:
                y0 = jnp.zeros((ell.row_pad,), x_blk.dtype)
                y = spmv_blocked_partial(
                    cols[0], vals[0], xl, y0,
                    bucket_lo=0, bucket_hi=Cl, n_buckets=C, block_cols=bc,
                )
            return y[None]

        def per_device_ghost(y_blk, gh_blk, cols, vals, *sk):
            xg = jnp.concatenate(
                [gh_blk[0], jnp.zeros((ghost_fill,), gh_blk.dtype)]
            )
            if ghost_skip:
                bl, cnt = sk
                y = spmv_blocked_skip(
                    cols[0], vals[0], xg, bl[0], cnt[0],
                    n_buckets=C, block_cols=bc, bucket_base=Cl, y0=y_blk[0],
                )
            else:
                y = spmv_blocked_partial(
                    cols[0], vals[0], xg, y_blk[0],
                    bucket_lo=Cl, bucket_hi=C, n_buckets=C, block_cols=bc,
                )
            return y[None]

        mm_local = shard_map(
            per_device_local, mesh=mesh,
            in_specs=(spec,) * (3 + 2 * local_skip),
            out_specs=spec, check_vma=False,
        )
        mm_ghost = shard_map(
            per_device_ghost, mesh=mesh,
            in_specs=(spec,) * (4 + 2 * ghost_skip),
            out_specs=spec, check_vma=False,
        )

        def spmv_fn(x):
            gh = exchange(x[..., None])[..., 0]   # issued before local work
            y = mm_local(x, *consts_l)            # no data dep on gh
            return mm_ghost(y, gh, *consts_g)

        return spmv_fn

    lists, counts = row_block_bucket_map(ell)
    use_skip = lists.shape[2] < C
    if use_skip:
        consts += [shard(lists), shard(counts)]

    def per_device(x_blk, gh_blk, cols, vals, *sk):
        x = x_blk[0]
        parts = [x, jnp.zeros((local_fill,), x.dtype)]
        if has_ghost:
            parts += [gh_blk[0], jnp.zeros((ghost_fill,), x.dtype)]
        xcat = jnp.concatenate(parts)     # [n_buckets * block_cols]
        if use_skip:
            bl, cnt = sk
            y = spmv_blocked_skip(
                cols[0], vals[0], xcat, bl[0], cnt[0],
                n_buckets=C, block_cols=bc,
            )
        else:
            y = spmv_blocked(cols[0], vals[0], xcat, bc)
        return y[None]

    mm = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(spec,) * (4 + 2 * use_skip),
        out_specs=spec,
        check_vma=False,
    )

    def spmv_fn(x):
        if has_ghost:
            gh = exchange(x[..., None])[..., 0]
        else:
            gh = jnp.zeros((ell.n_procs, 0), x.dtype)
        return mm(x, gh, *consts)

    return spmv_fn


def distributed_spmv(
    part: PartitionedCSR,
    coll,
    mesh,
    axis_name: str,
    x: np.ndarray,
    dtype=np.float64,
    variant: str = "flat",
    block_cols: int = DEFAULT_BLOCK_COLS,
    overlap: str = "off",
) -> np.ndarray:
    """One-shot device distributed SpMV of a numpy vector (convenience).

    ``variant`` is ``"flat"``, ``"blocked"``, or ``"auto"`` (modeled-VMEM
    selection); ``overlap`` is ``"on"``, ``"off"``, or ``"auto"``
    (cost-model split-schedule selection against the plan's modeled
    exchange time).  For repeated products build the function once with
    :func:`make_distributed_spmv` and jit it.
    """
    import jax

    sel = select_spmv_kernel(part, variant=variant, block_cols=block_cols)
    ell = partitioned_to_device(part, sel, dtype, block_cols)
    exchange = coll.bind(mesh, axis_name) if ell.ghost_pad else None
    if overlap == "auto":
        from ..core.costmodel import TPU_V5E, plan_time

        osel = select_spmv_overlap(part, plan_time(coll.plan, TPU_V5E))
        ov = osel.mode == "on"
    else:
        osel = None
        if overlap not in ("on", "off"):
            raise ValueError(f"unknown overlap mode {overlap!r}")
        ov = overlap == "on" and ell.ghost_pad > 0
    fn = jax.jit(
        make_distributed_spmv(ell, mesh, axis_name, exchange, overlap=ov)
    )
    xg = pack_vector(part.col_offsets, ell.in_pad, x.astype(dtype))
    y = fn(xg)
    return unpack_vector(part.offsets, np.asarray(y))
