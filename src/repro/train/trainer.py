"""Trainer: jitted train_step with TP/DP/EP sharding, microbatching,
remat, ZeRO-1 moments, optional error-feedback gradient compression.

``make_train_step(model, opt_cfg)`` returns (state_specs, train_step) where
train_step(state, batch) -> (state, metrics) is ready for jax.jit with
in_shardings/out_shardings derived from the specs — the same artifact the
multi-pod dry-run lowers and the real launcher executes.

Gradient sync (``TrainerConfig.grad_sync``): the default ``"jit"`` leaves
the data-parallel allreduce to GSPMD.  ``"auto"`` / ``"hier"`` / ``"ring"``
route it through an *explicit* plan-based dense allreduce
(``core.dense``) selected by the Section-5 cost model —
:func:`make_dp_train_step` builds the shard_map step, returns the
:class:`~repro.core.dense.DenseSelection` it recorded, and is numerically
equal to the implicit path (same mean-of-shard-means arithmetic).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from ..core import (
    TPU_V5E,
    DenseSelection,
    MachineParams,
    Topology,
    default_plan_cache,
    dense_round_runner,
    even_counts,
)
from ..models.lm import Model
from ..obs import default_obs
from .compression import ef_compress_tree, init_residual
from .optimizer import (
    AdamWConfig,
    OptState,
    adamw_update,
    init_opt_state,
    opt_state_specs,
)

_OBS = default_obs()

GRAD_SYNC_METHODS = ("jit", "auto", "hier", "ring")


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    residual: Optional[Any]      # error-feedback state (None if off)


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    microbatches: int = 1        # gradient accumulation
    compress_grads: bool = False
    # "jit" (implicit GSPMD allreduce) | "auto" | "hier" | "ring"
    # (explicit plan-based dense allreduce, see make_dp_train_step)
    grad_sync: str = "jit"


def batch_specs(model: Model) -> Dict[str, P]:
    ba = model.batch_axes
    b = ba if len(ba) > 1 else (ba[0] if ba else None)
    fam = model.cfg.family
    d = {"labels": P(b, None)}
    if fam == "audio":
        d["enc_embeds"] = P(b, None, None)
        d["tokens"] = P(b, None)
    elif fam == "vlm":
        d["embeds"] = P(b, None, None)
        d["positions"] = P(b, None, None)
    else:
        d["tokens"] = P(b, None)
    return d


def make_train_state(model: Model, tcfg: TrainerConfig, seed: int = 0,
                     abstract: bool = False) -> TrainState:
    params = model.init_params(seed=seed, abstract=abstract)
    if abstract:
        opt = jax.eval_shape(init_opt_state, params)
        res = (jax.eval_shape(init_residual, params)
               if tcfg.compress_grads else None)
    else:
        opt = init_opt_state(params)
        res = init_residual(params) if tcfg.compress_grads else None
    return TrainState(params, opt, res)


def state_specs(model: Model, tcfg: TrainerConfig) -> TrainState:
    pspecs = model.param_specs()
    axes = dict(zip(model.mesh.axis_names, model.mesh.devices.shape))
    params_abs = model.init_params(abstract=True)
    ospecs = opt_state_specs(params_abs, pspecs, axes)
    rspecs = (jax.tree.map(lambda s: s, ospecs.mu)
              if tcfg.compress_grads else None)
    return TrainState(pspecs, ospecs, rspecs)


def make_train_step(model: Model, tcfg: TrainerConfig):
    """Returns train_step(state, batch) -> (new_state, metrics)."""

    def loss_fn(params, batch):
        return model.loss(params, batch)

    def train_step(state: TrainState, batch: Dict) -> Tuple[TrainState, Dict]:
        mb = tcfg.microbatches
        if mb > 1:
            B = batch["tokens"].shape[0] if "tokens" in batch else \
                batch["embeds"].shape[0]
            assert B % mb == 0

            def micro(i, acc):
                grads_acc, loss_acc = acc
                sl = {
                    k: jax.lax.dynamic_slice_in_dim(v, i * (B // mb),
                                                    B // mb, axis=0)
                    for k, v in batch.items()
                }
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state.params, sl
                )
                grads_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), grads_acc, g
                )
                return grads_acc, loss_acc + l

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            grads, loss = jax.lax.fori_loop(
                0, mb, lambda i, acc: micro(i, acc),
                (zero, jnp.zeros((), jnp.float32)),
            )
            grads = jax.tree.map(lambda g: g / mb, grads)
            loss = loss / mb
            metrics_extra = {}
        else:
            (loss, metrics_extra), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params, batch)

        residual = state.residual
        if tcfg.compress_grads:
            grads, residual = ef_compress_tree(grads, residual)

        new_params, new_opt, om = adamw_update(
            tcfg.opt, state.params, grads, state.opt
        )
        metrics = {"loss": loss, **om}
        if isinstance(metrics_extra, dict):
            metrics.update({k: v for k, v in metrics_extra.items()})
        return TrainState(new_params, new_opt, residual), metrics

    return train_step


def _default_procs_per_region(n: int) -> int:
    for r in (4, 2, 1):
        if n % r == 0:
            return r
    return 1


def make_grad_sync(
    mesh,
    axis_name: str,
    n: int,
    method: str = "auto",
    procs_per_region: Optional[int] = None,
    cache=None,
    value_bytes: int = 8,
    params: MachineParams = TPU_V5E,
) -> Tuple[Callable, Any, DenseSelection]:
    """Explicit gradient-sync primitive: ``(sync, plan, selection)``.

    ``sync(flat)`` sums a per-device flat ``[m]`` vector (``m <= padded
    capacity``) across ``axis_name`` via a plan-based dense allreduce —
    for use *inside* a ``shard_map`` over that axis.  ``method`` pins the
    variant (``"hier"`` / ``"ring"``) or lets the cost model choose
    (``"auto"``); the plan comes through the shared :class:`PlanCache`
    ``dense_plan`` namespace, so repeated trainer builds re-plan nothing.
    """
    if method not in ("auto", "hier", "ring"):
        raise ValueError(
            f"grad_sync method {method!r} not in ('auto', 'hier', 'ring')"
        )
    n_dev = mesh.shape[axis_name]
    ppr = (procs_per_region if procs_per_region is not None
           else _default_procs_per_region(n_dev))
    topo = Topology(n_dev, ppr)
    cache = cache if cache is not None else default_plan_cache()
    with _OBS.span("train/grad_sync_plan", method=method, n=n,
                   n_dev=n_dev) as sp:
        plan, sel = cache.dense_collective(
            "allreduce", even_counts(n, n_dev), topo, variant=method,
            value_bytes=value_bytes, params=params,
        )
        sp.set(chosen=sel.chosen)
    run = dense_round_runner(plan, axis_name)
    n_seg, cmax = len(plan.counts), plan.cmax

    def sync(flat):
        m = flat.shape[0]
        if m > n_seg * cmax:
            raise ValueError(
                f"grad_sync built for {n_seg * cmax} values, got {m}"
            )
        buf = jnp.pad(flat, (0, n_seg * cmax - m)).reshape(n_seg, cmax)
        return run(buf).reshape(-1)[:m]

    return sync, plan, sel


def make_dp_train_step(
    loss_fn: Callable,
    template_params: Any,
    tcfg: TrainerConfig,
    mesh,
    axis_name: str = "dp",
    procs_per_region: Optional[int] = None,
    cache=None,
    machine: MachineParams = TPU_V5E,
):
    """Pure data-parallel train step with selectable gradient sync.

    ``loss_fn(params, batch) -> scalar`` must be a *mean over the leading
    batch axis* (equal shard sizes), so the global loss is the mean of
    per-shard losses and the global gradient the mean of per-shard
    gradients — which makes the explicit path (per-shard ``value_and_grad``
    under ``shard_map``, one plan-based dense allreduce of grads+loss,
    divide by the device count) numerically equal to the implicit GSPMD
    path (``grad_sync="jit"``: jit of the global loss with the batch
    sharded and params replicated).

    Returns ``(train_step, selection)`` where ``train_step(state, batch)
    -> (state, metrics)`` is jitted with the batch sharded over
    ``axis_name`` and ``selection`` is the recorded
    :class:`DenseSelection` (``None`` for the implicit path) — the
    trainer's analogue of ``DistOp`` recording ``kern=``/``ov=``.
    """
    method = tcfg.grad_sync
    if method not in GRAD_SYNC_METHODS:
        raise ValueError(
            f"grad_sync {method!r} not in {GRAD_SYNC_METHODS}"
        )
    n_dev = mesh.shape[axis_name]
    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P(axis_name))

    def finish(state, loss, grads):
        new_params, new_opt, om = adamw_update(
            tcfg.opt, state.params, grads, state.opt
        )
        return (TrainState(new_params, new_opt, state.residual),
                {"loss": loss, **om})

    if method == "jit":

        def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
            return finish(state, loss, grads)

        return jax.jit(train_step, in_shardings=(repl, shard),
                       donate_argnums=(0,)), None

    flat0, unravel = ravel_pytree(template_params)
    n_flat = int(flat0.size)
    # one allreduce covers the gradient vector plus the loss scalar
    sync, _plan, sel = make_grad_sync(
        mesh, axis_name, n_flat + 1, method=method,
        procs_per_region=procs_per_region, cache=cache, params=machine,
    )

    def per_shard(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        flat, _ = ravel_pytree(grads)
        vec = jnp.concatenate([flat, loss[None].astype(flat.dtype)])
        return sync(vec) / n_dev

    mapped = shard_map(
        per_shard, mesh=mesh, in_specs=(P(), P(axis_name)),
        out_specs=P(), check_vma=False,
    )

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        avg = mapped(state.params, batch)
        grads = unravel(avg[:n_flat])
        loss = avg[n_flat]
        return finish(state, loss, grads)

    return jax.jit(train_step, in_shardings=(repl, shard),
                   donate_argnums=(0,)), sel


def jit_train_step(model: Model, tcfg: TrainerConfig):
    """jit with explicit in/out shardings (what dryrun.py lowers)."""
    if tcfg.grad_sync != "jit":
        raise ValueError(
            "jit_train_step is the implicit-GSPMD path; explicit "
            f"grad_sync={tcfg.grad_sync!r} is served by make_dp_train_step"
        )
    specs = state_specs(model, tcfg)
    bspecs = batch_specs(model)
    mesh = model.mesh

    def shardify(tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), tree,
            is_leaf=lambda s: isinstance(s, P),
        )

    step = make_train_step(model, tcfg)
    return jax.jit(
        step,
        in_shardings=(shardify(specs), shardify(bspecs)),
        out_shardings=(shardify(specs), None),
        donate_argnums=(0,),
    ), specs
