"""Device-side execution of CommPlans: edge-colored ppermute rounds in shard_map.

XLA programs are SPMD with static shapes, so the MPI world of independent
ragged sends becomes a *round schedule*: the planner edge-colors the message
multigraph (``plan.color_rounds``) so that within a round every device sends
to at most one peer and receives from at most one peer — exactly one
``jax.lax.ppermute`` per round, padded to the round's widest message.

Padding bookkeeping uses a sentinel slot: every staging buffer carries one
extra row; gather indices pointing at it read zeros, scatter indices pointing
at it are harmless writes that get dropped when the buffer is consumed.

The executor is built once per plan ("init") and the returned function is
jitted by the caller — persistent-collective semantics for free.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .plan import CommPlan, CommStep, Message, Round, color_rounds


@dataclass
class DeviceRound:
    perm: List[Tuple[int, int]]
    width: int
    gather: np.ndarray   # [P, width] indices into step input buffer (pad = in_pad)
    scatter: np.ndarray  # [P, width] indices into step output buffer (pad = out_pad)


@dataclass
class DeviceStep:
    name: str
    reads_local: bool
    writes_ghost: bool
    in_pad: int    # padded per-device input size (excl. sentinel row)
    out_pad: int
    local_gather: np.ndarray   # [P, Lw] local-copy gathers (pad = in_pad)
    local_scatter: np.ndarray  # [P, Lw]
    rounds: List[DeviceRound]


@dataclass
class DevicePlan:
    strategy: str
    n_procs: int
    n_local_pad: int
    ghost_pad: int
    steps: List[DeviceStep]

    @property
    def n_rounds(self) -> int:
        return sum(len(s.rounds) for s in self.steps)

    @property
    def padded_wire_values(self) -> int:
        return sum(
            r.width * len(r.perm) for s in self.steps for r in s.rounds
        )


def _pack(idx_lists: Sequence[Tuple[int, np.ndarray]], P: int, width: int,
          pad: int) -> np.ndarray:
    out = np.full((P, width), pad, dtype=np.int32)
    for proc, idx in idx_lists:
        out[proc, : len(idx)] = idx
    return out


def build_device_plan(plan: CommPlan) -> DevicePlan:
    """Freeze a CommPlan into padded per-device index arrays + round schedule."""
    P_ = plan.topo.n_procs
    n_local_pad = int(plan.pattern.n_local.max())
    ghost_pad = int(max((len(n) for n in plan.pattern.needs), default=0))

    dsteps: List[DeviceStep] = []
    for step in plan.steps:
        in_pad = n_local_pad if step.reads_local else int(step.in_sizes.max())
        out_pad = ghost_pad if step.writes_ghost else int(step.out_sizes.max())
        local = [m for m in step.messages if m.src == m.dst and m.size > 0]
        lw = max((m.size for m in local), default=0)
        lg = _pack([(m.src, m.src_idx) for m in local], P_, lw, in_pad)
        ls = _pack([(m.dst, m.dst_idx) for m in local], P_, lw, out_pad)
        rounds = []
        for rnd in color_rounds(step.messages):
            w = rnd.width
            g = _pack(
                [(sd[0], si) for sd, si in zip(rnd.pairs, rnd.src_idx)],
                P_, w, in_pad,
            )
            s = _pack(
                [(sd[1], di) for sd, di in zip(rnd.pairs, rnd.dst_idx)],
                P_, w, out_pad,
            )
            rounds.append(DeviceRound(list(rnd.pairs), w, g, s))
        dsteps.append(
            DeviceStep(
                name=step.name,
                reads_local=step.reads_local,
                writes_ghost=step.writes_ghost,
                in_pad=in_pad,
                out_pad=out_pad,
                local_gather=lg,
                local_scatter=ls,
                rounds=rounds,
            )
        )
    return DevicePlan(plan.strategy, P_, n_local_pad, ghost_pad, dsteps)


# ---------------------------------------------------------------------------
# shard_map executor
# ---------------------------------------------------------------------------


def _with_sentinel(buf: jnp.ndarray) -> jnp.ndarray:
    """Append one zero row (the pad sentinel)."""
    pad = jnp.zeros((1,) + buf.shape[1:], buf.dtype)
    return jnp.concatenate([buf, pad], axis=0)


def make_executor(
    dplan: DevicePlan,
    mesh: Mesh,
    axis_name: str,
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Build ``exec(x) -> ghosts``.

    ``x``: [n_procs, n_local_pad, d] sharded over ``axis_name`` on dim 0;
    returns [n_procs, ghost_pad, d] with the delivered values.  The function
    body runs under shard_map; jit it (optionally fusing surrounding compute
    — that is how the paper's start/wait overlap materializes: XLA schedules
    the ``l`` rounds concurrently with the ``s``/``g`` chain).  Each plan
    step's operations sit under the named scope ``step_<name>``, so a
    profile shows the locality-aware phases apart.
    """
    # Device-plan index arrays become sharded constants.
    steps = dplan.steps

    def per_device(x_blk, *idx_blks):
        # x_blk: [1, n_local_pad, d]
        x = _with_sentinel(x_blk[0])
        ghost = jnp.zeros((dplan.ghost_pad + 1,) + x.shape[1:], x.dtype)
        it = iter(idx_blks)
        buf = None
        for st in steps:
            src = x if st.reads_local else buf
            with jax.named_scope(f"step_{st.name}"):
                out = ghost if st.writes_ghost else jnp.zeros(
                    (st.out_pad + 1,) + x.shape[1:], x.dtype
                )
                lg = next(it)[0]
                ls = next(it)[0]
                if lg.shape[0] > 0:
                    out = out.at[ls].set(src[lg])
                for rnd in st.rounds:
                    g = next(it)[0]
                    s = next(it)[0]
                    sendbuf = src[g]
                    recvbuf = jax.lax.ppermute(sendbuf, axis_name, rnd.perm)
                    out = out.at[s].set(recvbuf)
            if st.writes_ghost:
                ghost = out
            else:
                buf = out
        return ghost[None, :-1]

    # flatten index arrays in traversal order
    idx_arrays: List[np.ndarray] = []
    for st in steps:
        idx_arrays.append(st.local_gather)
        idx_arrays.append(st.local_scatter)
        for rnd in st.rounds:
            idx_arrays.append(rnd.gather)
            idx_arrays.append(rnd.scatter)

    spec = P(axis_name)
    from jax import shard_map

    fn = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(spec,) * (1 + len(idx_arrays)),
        out_specs=spec,
        check_vma=False,
    )

    idx_device = [
        jax.device_put(a, NamedSharding(mesh, spec)) for a in idx_arrays
    ]

    def exec_fn(x: jnp.ndarray) -> jnp.ndarray:
        return fn(x, *idx_device)

    return exec_fn


def time_executor(
    exchange: Callable,
    n_procs: int,
    n_pad: int,
    dtype=np.float64,
    iters: int = 20,
    warmup: int = 3,
    seed: int = 0,
    sharding=None,
) -> float:
    """Measured wall seconds per exchange of a bound executor.

    The one timing protocol shared by ``benchmarks.amg_comm`` and
    ``amg.distributed`` (jit + compile call + warmup + timed loop), so the
    two measured paths cannot drift.  ``dtype`` defaults to float64 to match
    the plans' ``value_bytes=8`` modeling assumption.  ``sharding`` places
    the input block-per-device first, so the timed calls move no input
    between devices.
    """
    import jax

    from ..obs import now as _now

    fn = jax.jit(exchange)
    x = jnp.asarray(
        np.random.default_rng(seed)
        .normal(size=(n_procs, max(n_pad, 1), 1))
        .astype(dtype)
    )
    if x.dtype != np.dtype(dtype):
        # jnp.asarray silently downcasts f64 -> f32 when jax_enable_x64 is
        # off, which would halve the wire volume being timed vs the claim
        raise RuntimeError(
            f"requested {np.dtype(dtype)} but device materialized {x.dtype};"
            " enable jax_enable_x64 (or pass the narrower dtype explicitly)"
        )
    if sharding is not None:
        x = jax.device_put(x, sharding)
    fn(x).block_until_ready()  # compile
    for _ in range(warmup):
        fn(x).block_until_ready()
    t0 = _now()
    for _ in range(iters):
        fn(x).block_until_ready()
    return (_now() - t0) / iters


def pack_local_values(
    plan: CommPlan, local_vals: Sequence[np.ndarray], d: Optional[int] = None
) -> np.ndarray:
    """[P, n_local_pad(, d)] global array from ragged per-proc values."""
    P_ = plan.topo.n_procs
    n_pad = int(plan.pattern.n_local.max())
    trailing = local_vals[0].shape[1:]
    out = np.zeros((P_, n_pad) + trailing, dtype=local_vals[0].dtype)
    for p, v in enumerate(local_vals):
        out[p, : len(v)] = v
    return out


def unpack_ghosts(plan: CommPlan, ghosts: np.ndarray) -> List[np.ndarray]:
    return [
        np.asarray(ghosts[p, : len(plan.pattern.needs[p])])
        for p in range(plan.topo.n_procs)
    ]
