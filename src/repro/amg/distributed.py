"""Device-resident distributed AMG solve on persistent neighborhood collectives.

This closes the loop the paper measures: a BoomerAMG-style V-cycle whose
every SpMV-shaped halo exchange (operator, restriction, prolongation, at
every level) runs through a locality-aware persistent neighborhood
collective — on device, under ``shard_map``, inside one jitted program.

Setup (:meth:`DistributedHierarchy.setup`) is the persistent init: each
hierarchy level is block-partitioned, its communication pattern extracted,
and a ``NeighborAlltoallV`` initialized *once* with the Section-5 dynamic
selector (``strategy="auto"``): communication-light fine levels come out
``standard``, communication-heavy coarse levels aggregated — the paper's
observed optimum.  All plans and bound executors go through a
:class:`~repro.core.cache.PlanCache`, so repeated setups on the same grid
(or operators sharing a pattern) skip re-planning entirely.

Solve: a jitted V-cycle (Chebyshev smoother, degrees matching the host
solver exactly) over ``[P, pad]`` block vectors; matvecs compose the plan
executor with the padded-ELL SpMV kernel, or with shifted slices where
a level's local block is banded (``sparse.device``).  With the
same rho estimates the device residual history tracks the host
:func:`~repro.amg.hierarchy.solve` to rounding error.

Elasticity: :meth:`DistributedHierarchy.repartition` rebuilds the whole
hierarchy onto a different mesh / process count / row balance *through the
same PlanCache*, so only patterns the new geometry has never seen are
re-planned — a grow-back to a previously used geometry re-plans nothing
(observable via the attached ``last_resize`` event).  ``row_weights``
(per-host EWMA step seconds from ``runtime.straggler``) skews every
level's row blocks inversely to measured speed — the straggler mitigation.

Entry points: ``DistributedHierarchy.setup(...)``, ``.solve(b, x0=...)``,
``.repartition(...)``, ``.selection_table()``,
``.measure_exchange_seconds()``.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, List, Optional, Tuple

import numpy as np

from ..core.cache import PlanCache, default_plan_cache
from ..core.costmodel import MachineParams, TPU_V5E, plan_time
from ..core.neighborhood import NeighborAlltoallV
from ..core.plan import Topology
from ..core.selection import SelectionReport
from ..obs import default_obs, now as _now
from ..sparse.device import (
    DeviceEll,
    OverlapSelection,
    make_distributed_spmv,
    pack_vector,
    partitioned_to_device,
    select_spmv_overlap,
    unpack_vector,
)
from ..sparse.partition import (
    PartitionedCSR,
    block_offsets,
    partition_rect_csr,
    partitioned_from_blocks,
)
from .distributed_setup import (
    DistributedSetup,
    _block_inv_diag,
    distributed_build_hierarchy,
)
from .hierarchy import Hierarchy, inv_diag

_OBS = default_obs()
_M_HALO = _OBS.counter(
    "amg/halo_values",
    "values an operator's exchange plan moves in one application, summed "
    "over its steps and the chips, by level and operator")


@dataclass
class DistOp:
    """One partitioned operator + its persistent collective + device form.

    ``overlap`` records the exchange/compute-overlap schedule choice next
    to the plan's Section-5 transport choice, and the device form its
    local and ghost layouts, so all the selections travel with the
    operator.
    """

    part: PartitionedCSR
    coll: NeighborAlltoallV
    ell: DeviceEll
    overlap: Optional[OverlapSelection] = None

    @property
    def strategy(self) -> str:
        return self.coll.strategy

    @property
    def selection(self) -> Optional[SelectionReport]:
        return self.coll.selection

    @property
    def local_layout(self) -> str:
        """``diagonal`` or ``ell``: how the local block is stored."""
        return "diagonal" if self.ell.offsets else "ell"

    @property
    def ghost_layout(self) -> str:
        return self.ell.ghost_layout

    @property
    def overlap_mode(self) -> str:
        return self.overlap.mode if self.overlap else "off"

    @property
    def halo_values(self) -> int:
        """Values the plan's messages carry in one application, summed over
        its steps and the chips (a value relayed twice counts twice)."""
        stats = self.coll.plan.stats
        t = stats.totals()
        return (t["intra_bytes"] + t["inter_bytes"]) // stats.value_bytes


@dataclass
class DistributedLevel:
    index: int
    n: int                       # global unknowns at this level
    pad: int                     # per-process vector padding
    A: DistOp
    dinv: np.ndarray             # [P, pad] Jacobi scaling (0 in padding)
    rho: float                   # spectral-radius estimate (from host setup)
    R: Optional[DistOp] = None   # fine -> coarse (None on coarsest)
    P: Optional[DistOp] = None   # coarse -> fine


def _default_procs_per_region(n_procs: int) -> int:
    for ppr in (4, 2):
        if n_procs % ppr == 0 and n_procs > ppr:
            return ppr
    return 1


def _make_op(part: PartitionedCSR, *, cache: PlanCache, topo: Topology,
             strategy: str, value_bytes: int, params: MachineParams,
             dtype, spmv_overlap: str) -> DistOp:
    """A partitioned operator's :class:`DistOp`: its collective from the
    plan cache, its device form, and the overlap choice against the
    plan's modeled exchange time."""
    coll = cache.collective(part.pattern, topo, strategy, value_bytes, params)
    ell = partitioned_to_device(part, dtype)
    osel = select_spmv_overlap(
        part, plan_time(coll.plan, params),
        mode=spmv_overlap, value_bytes=value_bytes,
    )
    return DistOp(part, coll, ell, osel)


def _count_halo_values(dl: DistributedLevel) -> None:
    """``amg/halo_values`` of the level's A, R and P (obs on only)."""
    if not _OBS.enabled:
        return
    for name, op in (("A", dl.A), ("R", dl.R), ("P", dl.P)):
        if op is not None:
            _M_HALO.inc(op.halo_values, level=dl.index, op=name)


class DistributedHierarchy:
    """A host AMG hierarchy lowered to a device-resident distributed solve."""

    def __init__(
        self,
        levels: List[DistributedLevel],
        mesh,
        axis_name: str,
        topo: Topology,
        cache: PlanCache,
        dtype,
        strategy: str,
        params: MachineParams,
        value_bytes: int,
        spmv_overlap: str = "auto",
        coarse_gather: str = "off",
    ):
        self.levels = levels
        self.mesh = mesh
        self.axis_name = axis_name
        self.topo = topo
        self.cache = cache
        self.dtype = dtype
        # the cache key under which every collective was initialized —
        # executor lookups must reuse it verbatim to hit the same entries
        self.strategy = strategy
        self.params = params
        self.value_bytes = value_bytes
        # the exchange/compute-overlap policy (auto | on | off)
        self.spmv_overlap = spmv_overlap
        # coarsest-level dense allgatherv policy: "off" keeps the
        # distributed Chebyshev; "auto"/"hier"/"ring" gather the coarse
        # rhs with a plan-based dense collective and smooth replicated
        # (selection recorded in coarse_selection)
        self.coarse_gather = coarse_gather
        self.coarse_selection = None
        # populated by setup_partitioned: the distributed-setup record
        # (per-level blocks + exchange accounting), None for host lowering
        self.setup_info: Optional[DistributedSetup] = None
        # elastic bookkeeping: the host hierarchy this was lowered from
        # (repartition source of truth; reconstructed on demand for
        # setup_partitioned-built hierarchies) and the ResizeEvent of the
        # rebuild that produced this instance (None for a first setup)
        self._host: Optional[Hierarchy] = None
        self.last_resize = None
        # the last solve's final iterate as the devices hold it: [P, pad],
        # one row block per device of the mesh
        self.x_device = None
        self._build_device_fns()

    # ------------------------------------------------------------- setup
    @classmethod
    def setup(
        cls,
        h: Hierarchy,
        mesh,
        axis_name: str = "proc",
        procs_per_region: Optional[int] = None,
        strategy: str = "auto",
        params: MachineParams = TPU_V5E,
        value_bytes: int = 8,
        cache: Optional[PlanCache] = None,
        dtype=np.float64,
        spmv_overlap: str = "auto",
        coarse_gather: str = "off",
        row_weights: Optional[np.ndarray] = None,
    ) -> "DistributedHierarchy":
        """Partition every level and init its collectives once (persistent).

        ``strategy="auto"`` runs the paper's Section-5 selector per level
        and per transfer operator; pass a concrete strategy to pin it.
        Each operator's local block is stored by diagonals where its offsets
        show it banded (a stencil fine level), else as ELL
        (:func:`~repro.sparse.device.partitioned_to_device`).
        ``spmv_overlap="auto"`` selects the split
        exchange/compute-overlap schedule per operator whenever the modeled
        hidden exchange time beats the split overhead; ``"on"``/``"off"``
        pin it.  All choices are recorded on each :class:`DistOp`.

        ``row_weights`` (per-host step *seconds*, e.g. the EWMA from
        ``runtime.straggler.StragglerDetector``) skews every level's row
        blocks inversely to the weights via
        ``runtime.straggler.rebalance_shards`` — a 2x-slower host owns half
        the rows.  ``None`` keeps the balanced contiguous blocking.
        """
        n_procs = int(dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name])
        topo = Topology(
            n_procs, procs_per_region or _default_procs_per_region(n_procs)
        )
        cache = cache if cache is not None else default_plan_cache()
        make_op = partial(_make_op, cache=cache, topo=topo,
                          strategy=strategy, value_bytes=value_bytes,
                          params=params, dtype=dtype,
                          spmv_overlap=spmv_overlap)

        if row_weights is None:
            offs = [block_offsets(lvl.A.nrows, n_procs) for lvl in h.levels]
        else:
            from ..runtime.straggler import rebalance_shards

            w = np.asarray(row_weights, dtype=float).reshape(-1)
            assert len(w) == n_procs, (len(w), n_procs)
            offs = [
                np.concatenate(
                    [[0], np.cumsum(rebalance_shards(w, lvl.A.nrows))]
                ).astype(np.int64)
                for lvl in h.levels
            ]
        levels: List[DistributedLevel] = []
        with _OBS.span("amg/setup", n_procs=n_procs, strategy=strategy,
                       levels=len(h.levels)):
            for k, lvl in enumerate(h.levels):
                with _OBS.span("amg/build_level", level=k,
                               n=lvl.A.nrows) as lsp:
                    A_op = make_op(
                        partition_rect_csr(lvl.A, offs[k], offs[k]))
                    pad = int(np.diff(offs[k]).max())
                    dinv = inv_diag(lvl.A)
                    dl = DistributedLevel(
                        index=k,
                        n=lvl.A.nrows,
                        pad=pad,
                        A=A_op,
                        dinv=pack_vector(offs[k], pad, dinv.astype(dtype)),
                        rho=lvl.rho or 1.0,
                    )
                    if lvl.P is not None and k + 1 < len(h.levels):
                        dl.R = make_op(partition_rect_csr(
                            lvl.R, offs[k + 1], offs[k]))
                        dl.P = make_op(partition_rect_csr(
                            lvl.P, offs[k], offs[k + 1]))
                    levels.append(dl)
                    _count_halo_values(dl)
                    lsp.set(strategy=A_op.strategy,
                            layout=A_op.local_layout,
                            ghost=A_op.ghost_layout,
                            overlap=A_op.overlap_mode)
            dh = cls(levels, mesh, axis_name, topo, cache, dtype,
                     strategy, params, value_bytes,
                     spmv_overlap=spmv_overlap,
                     coarse_gather=coarse_gather)
        dh._host = h
        return dh

    @classmethod
    def setup_partitioned(
        cls,
        A_blocks,
        row_offsets: np.ndarray,
        mesh,
        axis_name: str = "proc",
        procs_per_region: Optional[int] = None,
        strategy: str = "auto",
        params: MachineParams = TPU_V5E,
        value_bytes: int = 8,
        cache: Optional[PlanCache] = None,
        dtype=np.float64,
        max_levels: int = 25,
        min_coarse: int = 64,
        strength_theta: float = 0.25,
        seed: int = 0,
        spmv_overlap: str = "auto",
        coarse_gather: str = "off",
    ) -> "DistributedHierarchy":
        """End-to-end distributed build: partitioned fine matrix -> solve.

        Runs the distributed *setup* (``amg.distributed_setup``: PMIS /
        interpolation / Galerkin SpGEMM over sparse dynamic data exchanges)
        and lowers the resulting per-rank blocks straight to the device
        solve — the global operators are never materialized on one rank.
        Setup and solve share one :class:`PlanCache`; for structurally
        symmetric operators the setup halo pattern IS the solve halo
        pattern, so the solve collectives come out of the cache pre-built.
        """
        n_procs = int(dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name])
        assert n_procs == len(A_blocks), (n_procs, len(A_blocks))
        topo = Topology(
            n_procs, procs_per_region or _default_procs_per_region(n_procs)
        )
        cache = cache if cache is not None else default_plan_cache()
        setup = distributed_build_hierarchy(
            A_blocks, row_offsets, topo, cache=cache,
            max_levels=max_levels, min_coarse=min_coarse,
            strength_theta=strength_theta, seed=seed,
            strategy=strategy, value_bytes=value_bytes, params=params,
        )
        make_op = partial(_make_op, cache=cache, topo=topo,
                          strategy=strategy, value_bytes=value_bytes,
                          params=params, dtype=dtype,
                          spmv_overlap=spmv_overlap)

        levels: List[DistributedLevel] = []
        with _OBS.span("amg/setup_partitioned", n_procs=n_procs,
                       strategy=strategy, levels=len(setup.levels)):
            for k, sl in enumerate(setup.levels):
                with _OBS.span("amg/build_level", level=k,
                               n=sl.nrows) as lsp:
                    A_op = make_op(partitioned_from_blocks(
                        sl.A_blocks, sl.row_offsets, sl.row_offsets))
                    pad = int(np.diff(sl.row_offsets).max())
                    dinv = np.zeros((n_procs, pad), dtype=dtype)
                    for p, Ab in enumerate(sl.A_blocks):
                        dinv[p, : Ab.nrows] = _block_inv_diag(
                            Ab, int(sl.row_offsets[p])
                        ).astype(dtype)
                    dl = DistributedLevel(
                        index=k, n=sl.nrows, pad=pad, A=A_op,
                        dinv=dinv, rho=sl.rho or 1.0,
                    )
                    if sl.P_blocks is not None and k + 1 < len(setup.levels):
                        dl.R = make_op(partitioned_from_blocks(
                            sl.R_blocks, sl.coarse_offsets, sl.row_offsets))
                        dl.P = make_op(partitioned_from_blocks(
                            sl.P_blocks, sl.row_offsets, sl.coarse_offsets))
                    levels.append(dl)
                    _count_halo_values(dl)
                    lsp.set(strategy=A_op.strategy,
                            layout=A_op.local_layout,
                            ghost=A_op.ghost_layout,
                            overlap=A_op.overlap_mode)
            dh = cls(levels, mesh, axis_name, topo, cache, dtype,
                     strategy, params, value_bytes,
                     spmv_overlap=spmv_overlap,
                     coarse_gather=coarse_gather)
        dh.setup_info = setup
        return dh

    # ------------------------------------------------- device programs
    def _bind(self, op: DistOp) -> Callable:
        exchange = None
        if op.ell.ghost_pad:
            exchange = self._bind_exchange_only(op)
        return make_distributed_spmv(
            op.ell, self.mesh, self.axis_name, exchange,
            overlap=(op.overlap_mode == "on"),
        )

    def _bind_coarse(self) -> Callable:
        """Coarsest-level solve by dense allgatherv + replicated Chebyshev.

        The coarsest packed rhs ``[P, pad]`` is exactly the allgatherv
        input layout (``counts`` = real block sizes, ``cmax`` = pad):
        each device contributes its block, the plan-based gather
        replicates the full coarse vector, and a dense padded coarse
        operator (zeros at padding rows/cols, so no unpadding is needed)
        runs the same degree-24 Chebyshev arithmetic as :meth:`_cheby` —
        every device then keeps its own block of the result.  The
        :class:`~repro.core.dense.DenseSelection` lands in
        :attr:`coarse_selection`.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as PSpec

        from jax import shard_map
        from ..core import dense_round_runner
        from ..sparse.partition import partitioned_to_global

        lv = self.levels[-1]
        offs = np.asarray(lv.A.part.col_offsets, dtype=np.int64)
        counts = np.diff(offs)
        variant = "auto" if self.coarse_gather == "auto" else \
            self.coarse_gather
        plan, sel = self.cache.dense_collective(
            "allgatherv", counts, self.topo, variant=variant,
            value_bytes=self.value_bytes, params=self.params,
        )
        self.coarse_selection = sel
        run = dense_round_runner(plan, self.axis_name)

        P_, pad = self.topo.n_procs, lv.pad
        Ag = partitioned_to_global(lv.A.part)
        # global index -> padded position p*pad + local slot
        pos = np.concatenate([
            p * pad + np.arange(int(counts[p]), dtype=np.int64)
            for p in range(P_)
        ])
        Ad = np.zeros((P_ * pad, P_ * pad), dtype=self.dtype)
        rows = Ag.row_indices().astype(np.int64)
        cols = Ag.indices.astype(np.int64)
        np.add.at(Ad, (pos[rows], pos[cols]), Ag.data.astype(self.dtype))
        Ad_dev = jnp.asarray(Ad)
        dinv = jnp.asarray(np.asarray(lv.dinv).reshape(-1))

        rho = lv.rho
        upper = 1.1 * rho
        lower = 0.30 * rho
        theta = 0.5 * (upper + lower)
        delta = 0.5 * (upper - lower)
        sigma = theta / delta

        def coarse_cheby(b, degree=24):
            x = jnp.zeros_like(b)
            rho_k = 1.0 / sigma
            r = dinv * (b - Ad_dev @ x)
            p = r / theta
            x = x + p
            for _ in range(degree - 1):
                rho_next = 1.0 / (2.0 * sigma - rho_k)
                r = dinv * (b - Ad_dev @ x)
                p = rho_next * rho_k * p + 2.0 * rho_next / delta * r
                x = x + p
                rho_k = rho_next
            return x

        def per_device(b_blk):              # [1, pad] own packed block
            rank = jax.lax.axis_index(self.axis_name)
            zero = jnp.zeros((), rank.dtype)
            buf = jnp.zeros((P_, pad), b_blk.dtype)
            buf = jax.lax.dynamic_update_slice(buf, b_blk, (rank, zero))
            with jax.named_scope("exchange"):
                full = run(buf).reshape(-1)  # replicated coarse rhs
            x = coarse_cheby(full).reshape(P_, pad)
            return jax.lax.dynamic_slice(x, (rank, zero), (1, pad))

        spec = PSpec(self.axis_name)
        return shard_map(per_device, mesh=self.mesh, in_specs=(spec,),
                         out_specs=spec, check_vma=False)

    def _build_device_fns(self) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as PSpec

        blocks = NamedSharding(self.mesh, PSpec(self.axis_name))
        self._dinv = [jax.device_put(lv.dinv, blocks) for lv in self.levels]
        self._Amv = [self._bind(lv.A) for lv in self.levels]
        self._Rmv = [
            self._bind(lv.R) if lv.R is not None else None
            for lv in self.levels
        ]
        self._Pmv = [
            self._bind(lv.P) if lv.P is not None else None
            for lv in self.levels
        ]
        self._coarse_fn = (
            self._bind_coarse() if self.coarse_gather != "off" else None
        )
        self._step: Optional[Callable] = None
        self._consts: list = []

    def step_program(self) -> Tuple[Callable, list]:
        """The V-cycle step as one jitted program, and its operands.

        Returns ``(step, consts)`` with ``step(consts, x, b) -> (x + V(b -
        Ax), ||b - Ax||)`` over ``[P, pad]`` block vectors.  Operators,
        halo index maps and smoother scalings enter the program as the
        arguments ``consts``, not as literals: a fine level of 2^20 rows
        would otherwise write about 10^8 bytes of constants into the
        program, which slows its compilation and keys the compile cache on
        the matrix values.
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as PSpec

        vec = jax.ShapeDtypeStruct(
            (self.topo.n_procs, self.levels[0].pad), self.dtype,
            sharding=NamedSharding(self.mesh, PSpec(self.axis_name)),
        )
        closed = jax.make_jaxpr(self._make_step())(vec, vec)
        jaxpr = closed.jaxpr

        def amg_vcycle_step(consts, x, b):
            return tuple(jax.core.eval_jaxpr(jaxpr, consts, x, b))

        return jax.jit(amg_vcycle_step), list(closed.consts)

    def _device_step(self) -> Callable:
        """:meth:`step_program`, built on first use, with its operands
        placed in ``self._consts``."""
        if self._step is not None:
            return self._step
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as PSpec

        with _OBS.span("amg/step_program", levels=len(self.levels)):
            self._step, consts = self.step_program()
            replicated = NamedSharding(self.mesh, PSpec())
            # block-sharded operands are already placed; host constants
            # traced into the step (scalars, the dense coarse operator) are
            # replicated
            self._consts = [
                c if isinstance(c, jax.Array) and c.committed
                else jax.device_put(c, replicated)
                for c in consts
            ]
        return self._step

    def _cheby(self, k: int, x, b, degree: int):
        """Chebyshev smoother — same arithmetic as the host ``chebyshev``."""
        lv = self.levels[k]
        Amv = self._Amv[k]
        dinv = self._dinv[k]
        rho = lv.rho
        upper = 1.1 * rho
        lower = 0.30 * rho
        theta = 0.5 * (upper + lower)
        delta = 0.5 * (upper - lower)
        sigma = theta / delta
        rho_k = 1.0 / sigma
        r = dinv * (b - Amv(x))
        p = r / theta
        x = x + p
        for _ in range(degree - 1):
            rho_next = 1.0 / (2.0 * sigma - rho_k)
            r = dinv * (b - Amv(x))
            p = rho_next * rho_k * p + 2.0 * rho_next / delta * r
            x = x + p
            rho_k = rho_next
        return x

    def _vcycle(self, k: int, b):
        """One V-cycle from level ``k`` down.  Each level's work sits under
        the named scope ``L<k>`` and one of its phases (``pre``,
        ``residual``, ``restrict``, ``prolong``, ``post``, ``coarse``); the
        recursion into level k+1 stays outside it, so every operation of
        the compiled step names exactly one level."""
        import jax
        import jax.numpy as jnp

        lv = self.levels[k]
        level = f"L{k}"
        if lv.R is None or k == len(self.levels) - 1:
            with jax.named_scope(level), jax.named_scope("coarse"):
                if self._coarse_fn is not None:
                    return self._coarse_fn(b)
                return self._cheby(k, jnp.zeros_like(b), b, degree=24)
        with jax.named_scope(level):
            with jax.named_scope("pre"):
                x = self._cheby(k, jnp.zeros_like(b), b, degree=3)
            with jax.named_scope("residual"):
                r = b - self._Amv[k](x)
            with jax.named_scope("restrict"):
                rc = self._Rmv[k](r)
        ec = self._vcycle(k + 1, rc)
        with jax.named_scope(level):
            with jax.named_scope("prolong"):
                x = x + self._Pmv[k](ec)
            with jax.named_scope("post"):
                return self._cheby(k, x, b, degree=3)

    def _make_step(self):
        import jax
        import jax.numpy as jnp

        def step(x, b):
            # ``outer``: the step's own residual, its norm and the update
            with jax.named_scope("outer"):
                r = b - self._Amv[0](x)
                rn = jnp.linalg.norm(r)
            v = self._vcycle(0, r)
            with jax.named_scope("outer"):
                return x + v, rn

        return step

    # -------------------------------------------------------------- solve
    def solve(
        self,
        b: np.ndarray,
        tol: float = 1e-8,
        max_iters: int = 100,
        x0: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, List[float]]:
        """AMG-preconditioned stationary iteration, fully on device.

        Mirrors the host :func:`repro.amg.hierarchy.solve` loop (residual
        check before update) so histories are comparable.  ``x0`` (a global
        host vector) warm-starts the iteration — how a solve resumes on a
        repartitioned hierarchy after an elastic resize: the iterate from
        the old geometry is re-packed under the new blocking and the
        contraction continues where it left off.
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as PSpec

        lv0 = self.levels[0]
        blocks = NamedSharding(self.mesh, PSpec(self.axis_name))

        def place(v):
            # one row block per device, so no call moves a whole vector
            return jax.device_put(
                pack_vector(lv0.A.part.col_offsets, lv0.pad,
                            np.asarray(v).astype(self.dtype)),
                blocks,
            )

        hist: List[float] = []
        with _OBS.span("amg/solve", n=lv0.n, tol=tol,
                       max_iters=max_iters) as sp:
            with _OBS.span("amg/place"):
                bg = place(b)
                x = place(np.zeros(len(b)) if x0 is None else x0)
            nb = max(float(np.linalg.norm(b)), 1e-300)
            step = self._device_step()
            for it in range(max_iters):
                # the iteration span covers the whole V-cycle: its
                # dispatch, then the float() that waits for the device
                with _OBS.span("amg/vcycle_iter", iter=it):
                    with _OBS.span("amg/dispatch"):
                        x_new, rn = step(self._consts, x, bg)
                    with _OBS.span("amg/sync"):
                        rel = float(rn) / nb
                hist.append(rel)
                if rel < tol:
                    break
                x = x_new
            sp.set(iters=len(hist), final_rel=hist[-1] if hist else 0.0)
            self.x_device = x
            with _OBS.span("amg/unpack"):
                x_host = unpack_vector(lv0.A.part.offsets, np.asarray(x))
        return x_host, hist

    # ------------------------------------------------------------ elastic
    def _global_hierarchy(self) -> Hierarchy:
        """The host hierarchy this solve represents — stored by
        :meth:`setup`, reconstructed (values bit-exact, via
        ``sparse.partition.partitioned_to_global``) for hierarchies built
        distributed by :meth:`setup_partitioned`.  ``rho`` estimates carry
        over unchanged so the repartitioned Chebyshev arithmetic is
        identical."""
        if self._host is not None:
            return self._host
        from ..sparse.partition import partitioned_to_global
        from .hierarchy import Level

        levels: List[Level] = []
        for lv in self.levels:
            levels.append(Level(
                A=partitioned_to_global(lv.A.part),
                P=partitioned_to_global(lv.P.part) if lv.P else None,
                R=partitioned_to_global(lv.R.part) if lv.R else None,
                rho=lv.rho,
            ))
        self._host = Hierarchy(levels)
        return self._host

    def repartition(
        self,
        mesh=None,
        axis_name: Optional[str] = None,
        procs_per_region: Optional[int] = None,
        row_weights: Optional[np.ndarray] = None,
        params: Optional[MachineParams] = None,
        reason: str = "requested",
    ) -> "DistributedHierarchy":
        """Rebuild the hierarchy onto a new geometry through the SAME cache.

        The elastic entry point: pass a smaller/larger ``mesh`` after a
        device-set change, ``row_weights`` (per-host step seconds) after a
        straggler flag, and/or re-fitted ``params`` so the Section-5
        selector re-runs under measured rates.  Every pattern is re-planned
        through ``self.cache`` — patterns the target geometry has produced
        before (e.g. growing back to a previously used device count) hit
        the surviving entries and re-plan nothing.  The returned hierarchy
        carries a ``runtime.controller.ResizeEvent`` in ``last_resize``
        with the rebuild's wall time and the plan-cache miss/hit delta.
        """
        from ..runtime.controller import cache_delta_event

        mesh = mesh if mesh is not None else self.mesh
        axis_name = axis_name if axis_name is not None else self.axis_name
        h = self._global_hierarchy()
        before = self.cache.counters()
        t0 = _now()
        with _OBS.span("amg/repartition", reason=reason,
                       old_n=self.topo.n_procs) as sp:
            new = DistributedHierarchy.setup(
                h, mesh, axis_name,
                procs_per_region=procs_per_region,
                strategy=self.strategy,
                params=params if params is not None else self.params,
                value_bytes=self.value_bytes,
                cache=self.cache,
                dtype=self.dtype,
                spmv_overlap=self.spmv_overlap,
                coarse_gather=self.coarse_gather,
                row_weights=row_weights,
            )
            sp.set(new_n=new.topo.n_procs)
        secs = _now() - t0
        new.last_resize = cache_delta_event(
            self.cache, before, reason,
            self.topo.n_procs, new.topo.n_procs, secs,
        )
        return new

    # ------------------------------------------------------- introspection
    def selection_table(self) -> List[Tuple[int, str, str, Optional[str]]]:
        """[(level, op, chosen strategy, selector report)] for every
        collective of the hierarchy."""
        rows = []
        for lv in self.levels:
            for name, op in (("A", lv.A), ("R", lv.R), ("P", lv.P)):
                if op is None:
                    continue
                rep = str(op.selection) if op.selection else None
                rows.append((lv.index, name, op.strategy, rep))
        return rows

    def kernel_table(
        self,
    ) -> List[Tuple[int, str, str, str, str, Optional[str]]]:
        """[(level, op, local layout, ghost layout, overlap mode, overlap
        report)] — the local block's form (``diagonal`` or ``ell``), the
        ghost block's (``compact``, ``rows`` or ``none``) and the
        exchange/compute-overlap choice per operator, mirroring
        :meth:`selection_table` for the transport choice."""
        rows = []
        for lv in self.levels:
            for name, op in (("A", lv.A), ("R", lv.R), ("P", lv.P)):
                if op is None:
                    continue
                rep = str(op.overlap) if op.overlap else None
                rows.append((lv.index, name, op.local_layout,
                             op.ghost_layout, op.overlap_mode, rep))
        return rows

    def describe(self) -> str:
        lines = [
            f"Distributed AMG: {len(self.levels)} levels on "
            f"{self.topo.n_procs} procs ({self.topo.n_regions} regions), "
            f"plan cache: {self.cache.stats()}"
        ]
        for lv in self.levels:
            t = lv.A.coll.plan.stats.totals()
            lines.append(
                f"  L{lv.index}: n={lv.n:>8,d} pad={lv.pad:>6d} "
                f"A={lv.A.strategy:8s} "
                f"local={lv.A.local_layout:8s} "
                f"ghost={lv.A.ghost_layout:7s} "
                f"ov={lv.A.overlap_mode:4s} "
                f"inter_msgs={t['inter_msgs']:5d} "
                f"inter_bytes={t['inter_bytes']:8d}"
                + (f" R={lv.R.strategy} P={lv.P.strategy}" if lv.R else "")
            )
        if self.coarse_selection is not None:
            lines.append(f"  coarse_gather={self.coarse_gather}: "
                         f"{self.coarse_selection}")
        return "\n".join(lines)

    def measure_exchange_seconds(
        self, iters: int = 20, warmup: int = 3, tracer=None
    ) -> List[Tuple[int, str, float]]:
        """Measured (not modeled) per-level device exchange wall time.

        Times the jitted bound executor of each level's operator halo on
        the real mesh (shared protocol: ``core.collectives.time_executor``);
        returns [(level, strategy, seconds_per_exchange)].  Levels without
        ghost columns have no exchange and report 0.0.  When ``tracer`` (a
        ``repro.profile.TraceRecorder``) is given, each level's timing is
        recorded against its plan — the measured feed of the
        measured-vs-modeled calibration loop.  With no explicit tracer,
        any ``TraceRecorder`` attached to the enabled obs layer receives
        the same samples through the span bridge (``pure_exchange``
        span attributes) — how a production solve keeps feeding
        calibration without threading a tracer through every call.
        """
        from jax.sharding import NamedSharding, PartitionSpec as PSpec

        from ..core.collectives import time_executor

        out = []
        for lv in self.levels:
            if not lv.A.ell.ghost_pad:
                out.append((lv.index, lv.A.strategy, 0.0))
                continue
            with _OBS.span("amg/measure_exchange", level=lv.index,
                           strategy=lv.A.strategy) as sp:
                secs = time_executor(
                    self._bind_exchange_only(lv.A),
                    self.topo.n_procs,
                    lv.A.ell.in_pad,
                    dtype=self.dtype,
                    iters=iters,
                    warmup=warmup,
                    sharding=NamedSharding(self.mesh, PSpec(self.axis_name)),
                )
                if tracer is not None:
                    tracer.record_plan(lv.A.coll.plan, secs,
                                       label=f"amg/L{lv.index}",
                                       pure_exchange=True)
                else:
                    # no explicit tracer: let the obs bridge record it
                    # (guarded so a tracer passed here is never doubled)
                    sp.set(plan=lv.A.coll.plan, pure_exchange=True,
                           seconds=secs)
            out.append((lv.index, lv.A.strategy, secs))
        return out

    def _bind_exchange_only(self, op: DistOp) -> Callable:
        return self.cache.executor(
            op.part.pattern, self.topo, self.mesh, self.axis_name,
            strategy=self.strategy,
            value_bytes=self.value_bytes,
            params=self.params,
        )
