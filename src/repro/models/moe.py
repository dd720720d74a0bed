"""Expert-parallel MoE dispatch with locality-aware (paper) strategies.

Token -> expert all-to-all is the canonical irregular communication in the
assigned LM pool, and the place where the paper's three collectives map
one-to-one onto MoE serving/training:

``a2a``        (paper: *standard*)  one flat all-to-all over the whole EP
               group.  When EP spans pods, every device exchanges a message
               with every remote device: (Pp-1)*Pm inter-pod messages/device.
``hier``       (paper: *partially optimized*, 3-step aggregation)  tokens
               first cross the fast intra-pod 'model' axis so that lane m
               holds everything bound for remote lane m (lane m is the
               load-balanced "leader" for lane-m traffic — the paper's
               balanced leader assignment), then one inter-pod message per
               pod pair crosses the slow 'pod' axis: Pp-1 inter-pod
               messages/device, Pm x fewer than ``a2a``.
``hier_dedup`` (paper: *fully optimized*, index extension)  with top-k > 1
               a token is often routed to several experts hosted in the same
               remote region; the aggregated path still ships its hidden
               state once per (token, expert).  Dedup ships each distinct
               token once per destination region plus int32 fan-out
               metadata, replicating only *inside* the region (cheap axis).
               Region = pod when EP spans pods, else destination device.
``dense``      no dispatch at all: every device computes its local expert
               shard for all (replicated) tokens, masked by router weights —
               the naive pjit-auto baseline for benchmarks.
``auto``       (paper: *Section-5 dynamic selection*)  not a transport but a
               selector: the batch's routing pattern is expressed as a
               ``core.plan.CommPattern`` (push-side sparse dynamic data
               exchange, arXiv 2308.13869), the three candidate strategies
               are scored with the locality-aware max-rate cost model
               (``core.costmodel``), and the cheapest of a2a / hier /
               hier_dedup is chosen — the same per-pattern choice the AMG
               levels make.  ``dense`` is never auto-selected (it is a
               baseline, not a transport).

Plan-cache lifecycle
--------------------
:func:`moe_plan_for` is the cached entry point (``lm``, ``serving`` and
``serve.engine`` all plan through it): dispatch geometry plus a
routing-pattern fingerprint key an entry in ``core.cache.PlanCache``, so
the expensive init — representative-routing construction, candidate
planning, Section-5 selection — runs once per (mesh, tokens_per_lane,
top_k, mode, cap_factor) shape.  Repeated batches and decode steps on an
unchanged mesh and token count re-plan *nothing* (observable as zero new
``PlanCache`` misses).  :func:`moe_layer` additionally memoizes its jitted
shard_map dispatch executor in the same cache (``moe_executor``), so the
per-layer transport program is built once and reused across layers, calls
and solves — the MoE analogue of ``MPI_Neighbor_alltoallv_init``.

Implementation notes
--------------------
* Sequence-sharded dispatch: x is replicated over 'model'; each lane routes
  its 1/Pm slice of tokens, so token sets are disjoint per lane and dedup is
  lane-local (no cross-lane duplicates exist by construction).
* All buffers are static-capacity; overflow tokens are dropped (standard MoE
  capacity semantics) and their combine weights zeroed.
* Experts with E < |EP| are replicated (r = |EP|/E); the router spreads
  tokens over replicas by token index — doubling as load balancing.
* Pallas ``moe_pack`` kernels do the pack/fan-out gathers on TPU.
* Expert outputs differ per expert, so the *return* trip cannot dedup; it
  uses the aggregated transport (the paper's partial path) in all modes.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core import (
    CommPattern,
    SparseDynamicExchange,
    Topology,
    default_plan_cache,
    pattern_fingerprint,
    select_plan,
)
from ..core.costmodel import MachineParams, TPU_V5E
from ..core.dynexchange import DiscoveryStats
from ..core.selection import SelectionReport
from ..kernels.moe_pack import combine as pack_combine
from ..kernels.moe_pack import pack as pack_gather
from ..obs import default_obs
from .common import ArchConfig, Initializer, activation

_OBS = default_obs()

MODES = ("dense", "a2a", "hier", "hier_dedup")

# paper strategy <-> MoE transport (the Section-5 selector speaks strategy)
STRATEGY_OF_MODE = {"a2a": "standard", "hier": "partial",
                    "hier_dedup": "full"}
MODE_OF_STRATEGY = {v: k for k, v in STRATEGY_OF_MODE.items()}


@dataclasses.dataclass(frozen=True)
class MoEPlan:
    """Static dispatch geometry (the persistent 'init' of the collective)."""

    mode: str
    ep_axes: Tuple[str, ...]     # mesh axes the experts are sharded over
    ep_size: int
    e_log: int                   # logical experts
    e_phys: int                  # after replication
    e_per_dev: int
    top_k: int
    capacity: int                # C: per (src device, physical expert)
    region_axis: str             # slow axis for dedup ('pod' or 'model')
    region_size: int
    devs_per_region: int
    uniq_capacity: int           # Cu: unique tokens per (src lane, region)
    cap_factor: float
    fingerprint: str = ""        # routing-pattern fingerprint (cache identity)

    @property
    def replicas(self) -> int:
        return self.e_phys // self.e_log

    @property
    def ec(self) -> int:         # rows per (src, dst-device) block
        return self.e_per_dev * self.capacity


def make_moe_plan(
    cfg: ArchConfig,
    mesh: Mesh,
    tokens_per_lane: int,
    mode: str = "hier_dedup",
    ep_over_pods: bool = True,
    cap_factor: float = 1.25,
    dedup_factor: Optional[float] = None,
) -> MoEPlan:
    assert mode in MODES, mode
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    has_pod = "pod" in axes and axes["pod"] > 1 and ep_over_pods \
        and mode != "dense"
    ep_axes = ("pod", "model") if has_pod else ("model",)
    ep_size = int(np.prod([axes[a] for a in ep_axes]))
    e_log = cfg.n_experts
    # least replication r >= ceil(ep_size/e_log) with e_log*r divisible by
    # ep_size, so every device hosts the same number of physical experts
    # even when n_experts does not pack evenly onto the EP group (e.g. 3
    # logical experts on 4 devices -> r=4, e_phys=12, 3 per device)
    r0 = max(1, math.ceil(ep_size / e_log))
    step = ep_size // math.gcd(e_log, ep_size)
    r = ((r0 + step - 1) // step) * step
    e_phys = e_log * r
    assert e_phys % ep_size == 0, (e_phys, ep_size)
    e_per_dev = e_phys // ep_size
    k = cfg.top_k
    N = tokens_per_lane
    cap = max(8, int(math.ceil(k * N / e_phys * cap_factor / 8.0)) * 8)

    region_axis = "pod" if has_pod else "model"
    region_size = axes[region_axis]
    devs_per_region = ep_size // region_size
    pair_bound = devs_per_region * e_per_dev * cap   # exact per-region bound
    if dedup_factor is None:
        # expected distinct tokens hitting a region:
        # P(hit) = 1 - (1 - e_region/E_phys)^k, with 30% slack
        e_region = devs_per_region * e_per_dev
        frac = 1.0 - (1.0 - e_region / e_phys) ** k
        est = int(math.ceil(N * frac * 1.3))
        uniq = min(pair_bound, min(N, max(8, ((est + 7) // 8) * 8)))
    else:
        uniq = min(pair_bound, max(8, int(pair_bound * dedup_factor)
                                   // 8 * 8))
    return MoEPlan(
        mode=mode, ep_axes=ep_axes, ep_size=ep_size, e_log=e_log,
        e_phys=e_phys, e_per_dev=e_per_dev, top_k=k, capacity=cap,
        region_axis=region_axis, region_size=region_size,
        devs_per_region=devs_per_region, uniq_capacity=uniq,
        cap_factor=cap_factor,
    )


# ---------------------------------------------------------------------------
# planned dispatch: routing pattern -> CommPattern -> Section-5 selection ->
# PlanCache (the persistent 'init' shared with the AMG levels)
# ---------------------------------------------------------------------------


def _pack_routing(
    eids: list,
    replicas: int,
    e_per_dev: int,
    capacity: int,
    tokens_per_lane: int,
) -> Tuple[CommPattern, DiscoveryStats, str]:
    """Per-lane [N, k] logical-expert assignments -> dispatch CommPattern.

    Shared tail of the uniform and the measured-histogram synthesizers:
    replicate over physical experts, capacity-pack with exactly the
    semantics of :func:`route` / :func:`capacity_pack` (token-major rank),
    then discover the pattern via the push-side sparse dynamic data
    exchange: lane ``p`` owns its ``tokens_per_lane`` token values, each
    kept (token, k) pair pushes that token to the destination device.
    """
    N = tokens_per_lane
    dest: list = []
    local_ids: list = []
    for p, eid in enumerate(eids):
        k = eid.shape[1]
        rep = (np.arange(N) % replicas)[:, None]
        phys = (eid * replicas + rep).reshape(-1)
        # capacity packing: rank within each physical expert, token-major
        order = np.argsort(phys, kind="stable")
        sorted_e = phys[order]
        starts = np.r_[0, np.flatnonzero(np.diff(sorted_e)) + 1]
        run_len = np.diff(np.r_[starts, len(phys)])
        rank = np.empty(len(phys), np.int64)
        rank[order] = np.arange(len(phys)) - np.repeat(starts, run_len)
        keep = rank < capacity
        dest.append((phys[keep] // e_per_dev).astype(np.int64))
        local_ids.append((np.repeat(np.arange(N), k)[keep]).astype(np.int64))
    pattern, stats = SparseDynamicExchange.push_pattern(
        dest, local_ids, n_local=[N] * len(eids)
    )
    return pattern, stats, pattern_fingerprint(pattern)


@functools.lru_cache(maxsize=256)
def _routing_pattern(
    ep_size: int,
    e_log: int,
    replicas: int,
    e_per_dev: int,
    capacity: int,
    top_k: int,
    tokens_per_lane: int,
) -> Tuple[CommPattern, DiscoveryStats, str]:
    """Representative dispatch routing of one batch as a ``CommPattern``.

    Routing is synthesized from a fixed-seed uniform router (the
    load-balanced steady state the aux loss drives toward).  A token routed
    to several experts of one region appears as duplicate global indices —
    what the ``full`` planner dedups.  Deterministic, so the fingerprint is
    stable across calls and processes: repeated batches and decode steps
    key the same cache entry.
    """
    N, k = tokens_per_lane, top_k
    eids = []
    for p in range(ep_size):
        rng = np.random.default_rng(p)
        eids.append(np.argsort(rng.random((N, e_log)), axis=1)[:, :k])
    return _pack_routing(eids, replicas, e_per_dev, capacity, N)


def quantize_histogram(
    hist, e_log: int, quantum: int = 64
) -> Tuple[int, ...]:
    """Normalize an expert histogram to integer counts summing ``quantum``.

    Largest-remainder apportionment, deterministic tie-break on expert
    index.  Two measured histograms that differ by less than ~1/quantum in
    every fraction quantize identically — so their synthesized routing
    patterns share a fingerprint and the adaptive re-planner's cache lookup
    hits instead of re-planning (the "unchanged histogram re-plans
    nothing" property asserted in tests).
    """
    h = np.asarray(hist, dtype=np.float64).reshape(-1)
    if len(h) != e_log:
        raise ValueError(f"histogram has {len(h)} bins, expected {e_log}")
    total = float(h.sum())
    frac = (h / total) if total > 0 else np.full(e_log, 1.0 / e_log)
    raw = frac * quantum
    base = np.floor(raw).astype(np.int64)
    short = quantum - int(base.sum())
    if short > 0:
        order = np.lexsort((np.arange(e_log), -(raw - base)))
        base[order[:short]] += 1
    return tuple(int(x) for x in base)


@functools.lru_cache(maxsize=256)
def _histogram_routing_pattern(
    ep_size: int,
    e_log: int,
    replicas: int,
    e_per_dev: int,
    capacity: int,
    top_k: int,
    tokens_per_lane: int,
    qhist: Tuple[int, ...],
) -> Tuple[CommPattern, DiscoveryStats, str]:
    """Dispatch CommPattern whose expert marginals match a *measured*
    histogram (``qhist``: quantized counts from :func:`quantize_histogram`)
    instead of the synthesized uniform routing — the pattern the adaptive
    re-planner fingerprints when a serve workload drifts.

    Each token draws ``top_k`` *distinct* experts weighted by the
    histogram (Gumbel top-k, lane-seeded rng: deterministic across calls
    and processes) — matching :func:`route`'s semantics, where one token
    never hits the same logical expert twice, so the dedup planner scores
    duplicate counts the real workload would actually produce.
    """
    N, k = tokens_per_lane, top_k
    q = np.asarray(qhist, dtype=np.float64)
    frac = q / max(float(q.sum()), 1.0)
    # zero-probability experts stay drawable at ~1e-12 so k distinct
    # experts always exist even for a fully collapsed histogram
    logp = np.log(np.maximum(frac, 1e-12))
    eids = []
    for p in range(ep_size):
        rng = np.random.default_rng(100_003 + p)
        g = rng.gumbel(size=(N, e_log))
        eids.append(np.argsort(-(logp[None, :] + g), axis=1)[:, :k])
    return _pack_routing(eids, replicas, e_per_dev, capacity, N)


def dispatch_pattern(
    plan: MoEPlan, tokens_per_lane: int
) -> Tuple[CommPattern, DiscoveryStats, str]:
    """(pattern, discovery stats, fingerprint) of ``plan``'s dispatch.

    Region topology is deliberately absent: the pattern records only who
    needs which values; locality enters at planning time via
    :func:`dispatch_topology`."""
    return _routing_pattern(
        plan.ep_size, plan.e_log, plan.replicas,
        plan.e_per_dev, plan.capacity, plan.top_k, tokens_per_lane,
    )


def dispatch_topology(plan: MoEPlan) -> Topology:
    """EP group as a locality topology: regions are pods (or single
    devices when EP does not span pods), pod-major device order — the
    same layout :func:`ep_exchange` moves data in."""
    return Topology(plan.ep_size, max(1, plan.devs_per_region))


def _select_mode_over_pattern(
    plan: MoEPlan,
    pattern: CommPattern,
    value_bytes: int,
    params: MachineParams = TPU_V5E,
) -> Tuple[str, SelectionReport]:
    """Section-5 selection of a transport mode for one routing pattern."""
    _plan, report = select_plan(
        pattern, dispatch_topology(plan), params=params,
        value_bytes=value_bytes,
        candidates=tuple(MODE_OF_STRATEGY),
    )
    return MODE_OF_STRATEGY[report.chosen], report


def select_moe_mode(
    plan: MoEPlan,
    tokens_per_lane: int,
    value_bytes: int,
    params: MachineParams = TPU_V5E,
) -> Tuple[str, SelectionReport]:
    """Section-5 dynamic selection over a2a / hier / hier_dedup.

    Scores the three candidate strategies on the batch's routing pattern
    with the locality-aware max-rate model (message counts and bytes are
    exact plan quantities; ``value_bytes`` is the full hidden-state row) and
    returns the winning transport mode — mirroring the per-level AMG
    strategy choice.
    """
    pattern, _stats, _fp = dispatch_pattern(plan, tokens_per_lane)
    return _select_mode_over_pattern(plan, pattern, value_bytes, params)


def moe_plan_for(
    cfg: ArchConfig,
    mesh: Mesh,
    tokens_per_lane: int,
    mode: str = "auto",
    ep_over_pods: bool = True,
    cap_factor: float = 1.25,
    dedup_factor: Optional[float] = None,
    params: MachineParams = TPU_V5E,
    cache=None,
) -> MoEPlan:
    """Cached dispatch planning — the entry point ``lm`` / ``serving`` /
    ``serve.engine`` use instead of calling :func:`make_moe_plan` per call.

    Keyed on (mesh, tokens_per_lane, top_k, mode, cap_factor, ...) plus the
    routing-pattern fingerprint in ``core.cache.PlanCache`` (process-wide
    default unless ``cache`` is passed): the first call for a shape builds
    the geometry, synthesizes the routing pattern and — for
    ``mode="auto"`` — runs the Section-5 selector; every later call with an
    unchanged mesh and token count is a cache hit that re-plans nothing.

    The pattern synthesis behind the fingerprint is itself memoized
    (:func:`dispatch_pattern` lru), so its O(ep_size * tokens * experts)
    numpy cost is paid once per dispatch geometry per process — the same
    amortization class as the planning it keys.
    """
    cache = default_plan_cache() if cache is None else cache
    geom = make_moe_plan(
        cfg, mesh, tokens_per_lane,
        mode=("a2a" if mode == "auto" else mode),
        ep_over_pods=ep_over_pods, cap_factor=cap_factor,
        dedup_factor=dedup_factor,
    )
    if geom.mode == "dense":
        # no dispatch exchange to plan: geometry is the whole plan
        return geom
    _pattern, _stats, fp = dispatch_pattern(geom, tokens_per_lane)
    value_bytes = cfg.d_model * np.dtype(cfg.dtype).itemsize
    # mesh enters the key by content (axes x shape): a rebuilt-but-equal
    # mesh still hits, mirroring the content-hashed pattern fingerprints
    mesh_key = (tuple(mesh.axis_names), tuple(np.shape(mesh.devices)))
    key = (
        "moe_plan", mesh_key, tokens_per_lane, cfg.n_experts, cfg.top_k,
        mode, ep_over_pods, cap_factor, dedup_factor, value_bytes, params,
        fp,
    )

    def build() -> MoEPlan:
        chosen = mode
        if mode == "auto":
            chosen, _report = select_moe_mode(
                geom, tokens_per_lane, value_bytes, params
            )
        return dataclasses.replace(geom, mode=chosen, fingerprint=fp)

    return cache.moe_plan(key, build)


def moe_plan_from_histogram(
    cfg: ArchConfig,
    mesh: Mesh,
    tokens_per_lane: int,
    hist,
    mode: str = "auto",
    quantum: int = 64,
    ep_over_pods: bool = True,
    cap_factor: float = 1.25,
    dedup_factor: Optional[float] = None,
    params: MachineParams = TPU_V5E,
    cache=None,
) -> MoEPlan:
    """Cached dispatch planning over a *measured* expert histogram — the
    re-planning entry point of ``repro.profile.adapt.AdaptivePlanner``.

    Mirrors :func:`moe_plan_for` but the routing pattern (and therefore the
    fingerprint keying the plan cache) is synthesized from ``hist`` — the
    observed per-expert (token, k)-pair counts of a batch, fed from
    :func:`moe_dispatch_lane`'s ``expert_counts`` output — instead of the
    uniform steady-state router.  The histogram is quantized
    (:func:`quantize_histogram`) before fingerprinting, so re-planning
    under an effectively unchanged routing distribution is a cache hit
    that re-plans nothing; a drifted histogram keys (and, for
    ``mode="auto"``, re-selects) a genuinely new plan.
    """
    cache = default_plan_cache() if cache is None else cache
    geom = make_moe_plan(
        cfg, mesh, tokens_per_lane,
        mode=("a2a" if mode == "auto" else mode),
        ep_over_pods=ep_over_pods, cap_factor=cap_factor,
        dedup_factor=dedup_factor,
    )
    if geom.mode == "dense":
        return geom
    qhist = quantize_histogram(hist, geom.e_log, quantum)
    pattern, _stats, fp = _histogram_routing_pattern(
        geom.ep_size, geom.e_log, geom.replicas, geom.e_per_dev,
        geom.capacity, geom.top_k, tokens_per_lane, qhist,
    )
    value_bytes = cfg.d_model * np.dtype(cfg.dtype).itemsize
    mesh_key = (tuple(mesh.axis_names), tuple(np.shape(mesh.devices)))
    key = (
        "moe_plan_hist", mesh_key, tokens_per_lane, cfg.n_experts,
        cfg.top_k, mode, ep_over_pods, cap_factor, dedup_factor,
        value_bytes, params, fp,
    )

    def build() -> MoEPlan:
        chosen = mode
        if mode == "auto":
            chosen, _report = _select_mode_over_pattern(
                geom, pattern, value_bytes, params
            )
        return dataclasses.replace(geom, mode=chosen, fingerprint=fp)

    return cache.moe_plan(key, build)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_moe(init: Initializer, cfg: ArchConfig, L: int, e_phys: int) -> Dict:
    d, f = cfg.d_model, cfg.d_ff_expert
    p = {
        "router": init.tensor((L, d, cfg.n_experts), fan_in=d,
                              dtype=jnp.float32),
        "w_gate": init.tensor((L, e_phys, d, f), fan_in=d),
        "w_up": init.tensor((L, e_phys, d, f), fan_in=d),
        "w_down": init.tensor((L, e_phys, f, d), fan_in=f),
    }
    if cfg.n_shared_experts:
        fs = cfg.d_ff_expert * cfg.n_shared_experts
        p["ws_gate"] = init.tensor((L, d, fs), fan_in=d)
        p["ws_up"] = init.tensor((L, d, fs), fan_in=d)
        p["ws_down"] = init.tensor((L, fs, d), fan_in=fs)
    return p


def remap_expert_params(moe_params: Dict, e_log: int,
                        r_old: int, r_new: int) -> Dict:
    """Re-replicate expert weights for a changed EP group size.

    The physical expert layout is ``phys = logical * replicas + rep``
    (see ``_pack_routing``), so replica 0 of every logical expert lives at
    stride ``replicas`` — slicing ``[:, ::r_old]`` recovers the logical
    weights and ``np.repeat(..., r_new, axis=1)`` re-expands them for the
    new group.  Operates host-side on the expert tensors (``w_gate`` /
    ``w_up`` / ``w_down``, shape [L, e_log*r, ...]); router and shared
    weights are replication-independent and pass through untouched.
    Dtypes are preserved (``np.repeat`` never casts).
    """
    import jax

    out = dict(moe_params)
    for key in ("w_gate", "w_up", "w_down"):
        v = np.asarray(jax.device_get(moe_params[key]))
        assert v.shape[1] == e_log * r_old, (v.shape, e_log, r_old)
        base = v[:, ::r_old]                   # replica 0 per logical expert
        out[key] = np.repeat(base, r_new, axis=1)
    return out


def moe_param_specs(cfg: ArchConfig, plan: MoEPlan) -> Dict:
    """PartitionSpecs for init_moe params (leading L axis unsharded)."""
    e_spec = plan.ep_axes if len(plan.ep_axes) > 1 else plan.ep_axes[0]
    p = {
        "router": P(),
        "w_gate": P(None, e_spec, None, None),
        "w_up": P(None, e_spec, None, None),
        "w_down": P(None, e_spec, None, None),
    }
    if cfg.n_shared_experts:
        p["ws_gate"] = P(None, None, "model")
        p["ws_up"] = P(None, None, "model")
        p["ws_down"] = P(None, "model", None)
    return p


EXPERT_WEIGHT_KEYS = ("w_gate", "w_up", "w_down")


def gather_expert_weights(
    moe_params: Dict,
    plan: MoEPlan,
    mesh: Mesh,
    method: str = "auto",
    cache=None,
    params: MachineParams = TPU_V5E,
):
    """Replicate the EP-sharded expert weights with a plan-based dense
    allgatherv — ``(gathered_params, DenseSelection)``.

    The expert tensors (``w_gate``/``w_up``/``w_down``, sharded over the
    EP axis) are flattened per device into one segment and gathered in a
    single dense collective over :func:`dispatch_topology` (so region
    structure matches the dispatch transport), selected by the Section-5
    cost model (``method="auto"``) or pinned (``"hier"``/``"ring"``) — the
    weight-replication step of a dense fallback forward, an elastic
    EP-group rebuild, or a checkpoint re-shard.  Router and shared-expert
    weights are already replicated and pass through untouched.  The
    returned :class:`~repro.core.dense.DenseSelection` is the recorded
    choice, the way ``DistOp`` records ``kern=``/``ov=``.
    """
    from jax import shard_map
    from ..core import dense_round_runner

    if len(plan.ep_axes) != 1:
        raise ValueError(
            f"gather_expert_weights needs a single EP mesh axis, got "
            f"{plan.ep_axes!r}"
        )
    axis = plan.ep_axes[0]
    ep, e_per_dev = plan.ep_size, plan.e_per_dev
    gshapes = {k: tuple(moe_params[k].shape) for k in EXPERT_WEIGHT_KEYS}
    lshapes = {k: (s[0], e_per_dev) + s[2:] for k, s in gshapes.items()}
    sizes = {k: int(np.prod(s)) for k, s in lshapes.items()}
    chunk = sum(sizes.values())

    cache = cache if cache is not None else default_plan_cache()
    topo = dispatch_topology(plan)
    variant = "auto" if method == "auto" else method
    with _OBS.span("moe/expert_gather_plan", method=method, ep=ep,
                   chunk=chunk) as sp:
        dplan, sel = cache.dense_collective(
            "allgatherv", np.full(ep, chunk, dtype=np.int64), topo,
            variant=variant, params=params,
        )
        sp.set(chosen=sel.chosen)
    run = dense_round_runner(dplan, axis)

    def per_device(*leaves):
        rank = jax.lax.axis_index(axis)
        zero = jnp.zeros((), rank.dtype)
        flat = jnp.concatenate([x.reshape(-1) for x in leaves])
        buf = jnp.zeros((ep, chunk), flat.dtype)
        buf = jax.lax.dynamic_update_slice(buf, flat[None], (rank, zero))
        full = run(buf)                      # [ep, chunk] replicated
        outs, off = [], 0
        for k in EXPERT_WEIGHT_KEYS:
            part = full[:, off:off + sizes[k]].reshape((ep,) + lshapes[k])
            # [ep, L, e_per_dev, ...] -> [L, ep*e_per_dev, ...]: devices
            # hold contiguous expert blocks in rank order, so the outer
            # ep axis folds straight back into e_phys order
            part = jnp.moveaxis(part, 0, 1).reshape(gshapes[k])
            outs.append(part)
            off += sizes[k]
        return tuple(outs)

    fn = shard_map(
        per_device, mesh=mesh,
        in_specs=(P(None, axis, None, None),) * len(EXPERT_WEIGHT_KEYS),
        out_specs=(P(),) * len(EXPERT_WEIGHT_KEYS),
        check_vma=False,
    )
    gathered = jax.jit(fn)(*(moe_params[k] for k in EXPERT_WEIGHT_KEYS))
    out = dict(moe_params)
    out.update(dict(zip(EXPERT_WEIGHT_KEYS, gathered)))
    return out, sel


# ---------------------------------------------------------------------------
# routing + capacity packing (all shapes static)
# ---------------------------------------------------------------------------


def _segment_ranks(sorted_ids: jnp.ndarray) -> jnp.ndarray:
    """Rank of each element within its run of equal ids (ids pre-sorted)."""
    n = sorted_ids.shape[0]
    idx = jnp.arange(n)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]]
    )
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, idx, 0)
    )
    return idx - seg_start


def _rank_within(ids: jnp.ndarray) -> jnp.ndarray:
    """Stable rank of each element among equal values of ``ids``."""
    order = jnp.argsort(ids, stable=True)
    ranks_sorted = _segment_ranks(ids[order])
    return jnp.zeros_like(ranks_sorted).at[order].set(ranks_sorted)


def route(
    x: jnp.ndarray,              # [N, D] this lane's tokens
    router_w: jnp.ndarray,       # [D, E_log] (f32)
    plan: MoEPlan,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing -> (phys expert ids [N,k], weights [N,k], aux loss)."""
    N = x.shape[0]
    # f32 floor, but f64 activations keep their width (bit-match checks)
    cdt = jnp.promote_types(x.dtype, jnp.float32)
    logits = x.astype(cdt) @ router_w.astype(cdt)          # [N, E_log]
    probs = jax.nn.softmax(logits, axis=-1)
    w, eid = jax.lax.top_k(probs, plan.top_k)              # [N, k]
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # load-balance aux (Switch-style): E * sum_e f_e * P_e
    f = jnp.zeros((plan.e_log,), jnp.float32).at[eid.reshape(-1)].add(
        1.0 / (N * plan.top_k)
    )
    aux = plan.e_log * jnp.sum(f * jnp.mean(probs, axis=0))
    if plan.replicas > 1:  # spread over replicas by token index
        rep = (jnp.arange(N) % plan.replicas)[:, None]
        phys = eid * plan.replicas + rep
    else:
        phys = eid
    return phys.astype(jnp.int32), w, aux


def capacity_pack(
    phys: jnp.ndarray,           # [N, k]
    plan: MoEPlan,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Assign each (token, k) a slot in the [E_phys * C] send layout.

    Drop order: pairs claim expert slots in token-major order (flat index
    ``token * k + j``), so when an expert overflows its capacity ``C`` the
    *late-sequence* tokens are the ones dropped — first-come-first-served
    by sequence position, NOT random or load-aware.  This bias is invisible
    in the outputs (dropped pairs just get zero combine weight), which is
    why :func:`moe_dispatch_lane` surfaces a ``dropped_fraction`` scalar:
    benchmarks and tests assert capacity health instead of silently
    under-serving the end of every sequence.

    Returns (slot [N,k] (sentinel E_phys*C when dropped), keep [N,k],
    slot_token [E_phys*C]: source token per slot, sentinel N when empty)."""
    N, k = phys.shape
    C = plan.capacity
    flat_e = phys.reshape(-1)
    rank = _rank_within(flat_e)
    keep = rank < C
    slot = jnp.where(keep, flat_e * C + rank, plan.e_phys * C)
    token_of_pair = jnp.repeat(jnp.arange(N), k).astype(jnp.int32)
    slot_token = jnp.full((plan.e_phys * C + 1,), N, jnp.int32)
    slot_token = slot_token.at[slot].set(token_of_pair)[: plan.e_phys * C]
    return slot.reshape(N, k), keep.reshape(N, k), slot_token


# ---------------------------------------------------------------------------
# transport (the paper's strategies)
# ---------------------------------------------------------------------------


def _a2a(x, axis, split, concat):
    return jax.lax.all_to_all(x, axis, split_axis=split, concat_axis=concat,
                              tiled=True)


def ep_exchange(send: jnp.ndarray, plan: MoEPlan) -> jnp.ndarray:
    """send: [G*eC, D] ordered by destination device (pod-major);
    returns [G*eC, D] ordered by source device."""
    G, D = plan.ep_size, send.shape[-1]
    eC = send.shape[0] // G
    if len(plan.ep_axes) == 1:
        return _a2a(send, plan.ep_axes[0], 0, 0)
    if plan.mode == "a2a":
        return _a2a(send, plan.ep_axes, 0, 0)
    # hierarchical: fast-axis hop to the leader lane, then one slow-axis
    # message per pod pair (paper's 3-step aggregation, s then g)
    Pp, Pm = plan.region_size, plan.devs_per_region
    b = send.reshape(Pp, Pm, eC, D)          # [dst pod, dst lane, eC]
    b = _a2a(b, "model", 1, 1)               # -> [dst pod, src lane, eC]
    b = _a2a(b, "pod", 0, 0)                 # -> [src pod, src lane, eC]
    return b.reshape(G * eC, D)


def ep_exchange_back(recv: jnp.ndarray, plan: MoEPlan) -> jnp.ndarray:
    """Inverse transport: rows ordered by source device -> back to sources,
    arriving ordered by destination (computing) device = send layout."""
    G, D = plan.ep_size, recv.shape[-1]
    eC = recv.shape[0] // G
    if len(plan.ep_axes) == 1:
        return _a2a(recv, plan.ep_axes[0], 0, 0)
    if plan.mode == "a2a":
        return _a2a(recv, plan.ep_axes, 0, 0)
    Pp, Pm = plan.region_size, plan.devs_per_region
    b = recv.reshape(Pp, Pm, eC, D)          # [src pod, src lane, eC]
    b = _a2a(b, "pod", 0, 0)                 # -> [cmp pod, src lane, eC]
    b = _a2a(b, "model", 1, 1)               # -> [cmp pod, cmp lane, eC]
    return b.reshape(G * eC, D)


# ---------------------------------------------------------------------------
# the layer body (runs under shard_map)
# ---------------------------------------------------------------------------


def _expert_ffn(wg, wu, wd, act_fn, xb):
    """xb: [e_per_dev, T, D]; w*: [e_per_dev, D, f] / [e_per_dev, f, D]."""
    xf = xb.astype(wg.dtype)
    h = act_fn(jnp.einsum("etd,edf->etf", xf, wg)) * jnp.einsum(
        "etd,edf->etf", xf, wu
    )
    return jnp.einsum("etf,efd->etd", h, wd)


def moe_dispatch_lane(
    x_lane: jnp.ndarray,         # [N, D] this lane's tokens
    params: Dict,                # per-layer slices; expert weights LOCAL shard
    plan: MoEPlan,
    cfg: ArchConfig,
    valid: Optional[jnp.ndarray] = None,   # [N] bool; False rows are pads
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (y_lane [N, D], aux scalar, dropped_fraction scalar,
    expert_counts [e_log] f32).

    ``dropped_fraction`` is the fraction of this lane's *valid* (token, k)
    pairs that lost their expert slot to capacity overflow (see
    :func:`capacity_pack` for the token-major drop order) — 0 in ``dense``
    mode, which routes nothing.  ``valid`` masks sequence-padding rows out
    of the metric (pads are still routed and can consume capacity, but
    they are not real tokens: counting them would distort the fraction
    whenever tokens don't divide the lane count).  An all-pad lane reports
    1.0 — weight lane fractions by their valid-pair count when averaging
    (as :func:`moe_layer` does).

    ``expert_counts`` is this lane's measured routing histogram: valid
    (token, k) pairs per *logical* expert, pre-capacity (drops are a
    capacity symptom, not a routing signal).  It is the observation the
    adaptive re-planner consumes (``repro.profile.adapt``) in place of the
    synthesized uniform routing behind :func:`dispatch_pattern`."""
    N, D = x_lane.shape
    C = plan.capacity
    act_fn = activation(cfg.act)
    if valid is None:
        valid = jnp.ones((N,), bool)
    phys, w, aux = route(x_lane, params["router"], plan)
    pair_valid = jnp.broadcast_to(valid[:, None], phys.shape)
    counts = jnp.zeros((plan.e_log,), jnp.float32).at[
        (phys // plan.replicas).reshape(-1)
    ].add(pair_valid.reshape(-1).astype(jnp.float32))

    if plan.mode == "dense":
        wg, wu, wd = params["w_gate"], params["w_up"], params["w_down"]
        e_per = wg.shape[0]
        ep_idx = jax.lax.axis_index("model")
        xb = jnp.broadcast_to(x_lane[None], (e_per, N, D))
        y_all = _expert_ffn(wg, wu, wd, act_fn, xb)      # [e_per, N, D]
        e_ids = ep_idx * e_per + jnp.arange(e_per)
        cdt = jnp.promote_types(x_lane.dtype, jnp.float32)
        match = phys[None, :, :] == e_ids[:, None, None]  # [e_per, N, k]
        wk = jnp.sum(match * w[None].astype(cdt), axis=-1)
        y = jnp.einsum("en,end->nd", wk, y_all.astype(cdt))
        y = jax.lax.psum(y, "model")
        return (y.astype(x_lane.dtype), aux, jnp.zeros((), jnp.float32),
                counts)

    slot, keep, slot_token = capacity_pack(phys, plan)
    w = w * keep.astype(w.dtype)

    x_pad = jnp.concatenate([x_lane, jnp.zeros((1, D), x_lane.dtype)], 0)
    send = pack_gather(x_pad, jnp.minimum(slot_token, N))  # [E_phys*C, D]

    # delivered = pairs whose expert output actually comes back; the dedup
    # path can additionally lose pairs to uniq_capacity overflow (their
    # fan-out reads the zero pad row), which must be just as observable as
    # expert-capacity drops
    delivered = keep
    if plan.mode == "hier_dedup" and plan.top_k > 1:
        yb, pair_ok = _dedup_outbound(x_lane, slot, keep, phys, params,
                                      plan, act_fn)
        delivered = keep & pair_ok.reshape(N, plan.top_k)
    else:
        recv = ep_exchange(send, plan)                   # by source device
        xb = recv.reshape(plan.ep_size, plan.e_per_dev, C, D)
        xb = jnp.swapaxes(xb, 0, 1).reshape(
            plan.e_per_dev, plan.ep_size * C, D
        )
        yo = _expert_ffn(params["w_gate"], params["w_up"], params["w_down"],
                         act_fn, xb)
        yb = jnp.swapaxes(
            yo.reshape(plan.e_per_dev, plan.ep_size, C, D), 0, 1
        ).reshape(plan.ep_size * plan.e_per_dev * C, D)
    y_recv = ep_exchange_back(yb.astype(x_lane.dtype), plan)

    kept_real = jnp.sum((delivered & valid[:, None]).astype(jnp.float32))
    n_real = jnp.sum(valid.astype(jnp.float32)) * plan.top_k
    dropped = 1.0 - kept_real / jnp.maximum(n_real, 1.0)

    buf = jnp.concatenate([y_recv, jnp.zeros((1, D), y_recv.dtype)], 0)
    y = pack_combine(buf, jnp.minimum(slot, plan.e_phys * C), w)
    return y.astype(x_lane.dtype), aux, dropped, counts


def moe_layer(
    x: jnp.ndarray,              # [B, S, D] batch sharded over batch_axes
    params: Dict,                # per-layer slices (no leading L dim)
    plan: MoEPlan,
    cfg: ArchConfig,
    mesh: Mesh,
    batch_axes: Tuple[str, ...],
    cache=None,
    return_expert_counts: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    """shard_map wrapper: sequence-shard tokens over 'model' lanes, dispatch,
    all_gather the lane outputs back.  Returns (y [B,S,D], aux scalar,
    dropped_fraction scalar — mean over lanes, see :func:`capacity_pack`);
    with ``return_expert_counts=True`` a fourth output is appended: the
    batch's measured routing histogram ([e_log] f32, valid (token, k)
    pairs per logical expert, psum'd over every mesh axis — so replicated
    lanes multiply the scale uniformly; normalize before comparing).

    When ``cache`` (a ``core.cache.PlanCache``) is given, the jitted
    shard_map dispatch executor is memoized in it keyed on (plan geometry,
    mesh, specs, param-tree structure): every MoE layer of every forward
    reuses one compiled transport program per dispatch geometry instead of
    rebuilding it each call.  The routing *fingerprint* is deliberately
    excluded from that key — the compiled transport depends only on
    geometry + mode, so an adaptively re-selected plan that lands back on
    a previously compiled mode recompiles nothing.
    """
    from jax import shard_map

    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    Pm = axes["model"]
    all_axes = tuple(mesh.axis_names)

    pspecs = moe_param_specs(cfg, plan)
    # strip the leading L axis from the specs (params are per-layer slices)
    def strip(spec):
        return P(*spec[1:]) if len(spec) else spec
    pspecs = {k: strip(v) for k, v in pspecs.items()
              if k in params and not k.startswith("ws_")}
    pflat, ptree = jax.tree.flatten(
        {k: params[k] for k in pspecs}
    )
    spec_flat = jax.tree.flatten({k: pspecs[k] for k in pspecs})[0]
    # batch sharding only when the batch divides the data axes (long-context
    # decode has global_batch=1: tokens replicate, dispatch stays correct
    # because every replica performs the identical exchange)
    n_batch_dev = int(np.prod([axes[a] for a in batch_axes])) \
        if batch_axes else 1
    if batch_axes and x.shape[0] % n_batch_dev == 0:
        x_spec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0],
                   None, None)
    else:
        x_spec = P(None, None, None)

    def build():
        def body(xb, *pvals):
            pb = jax.tree.unflatten(ptree, pvals)
            b_loc, S, D = xb.shape
            n_all = b_loc * S
            xf = xb.reshape(n_all, D)
            if plan.mode == "dense":
                y, aux, drop, counts = moe_dispatch_lane(xf, pb, plan, cfg)
                out = (y.reshape(b_loc, S, D),
                       jax.lax.pmean(aux, all_axes),
                       jax.lax.pmean(drop, all_axes))
                if return_expert_counts:
                    out += (jax.lax.psum(counts, all_axes),)
                return out
            n_pad = n_all + ((-n_all) % Pm)
            if n_pad != n_all:
                xf = jnp.pad(xf, ((0, n_pad - n_all), (0, 0)))
            n_lane = n_pad // Pm
            m = jax.lax.axis_index("model")
            x_lane = jax.lax.dynamic_slice_in_dim(xf, m * n_lane, n_lane, 0)
            # pad rows (appended past n_all) are routed but masked out of
            # the capacity-health metric; lane fractions are averaged
            # weighted by their real-pair counts
            valid = m * n_lane + jnp.arange(n_lane) < n_all
            y_lane, aux, drop, counts = moe_dispatch_lane(
                x_lane, pb, plan, cfg, valid=valid
            )
            y = jax.lax.all_gather(y_lane, "model", axis=0, tiled=True)
            y = y[:n_all].reshape(b_loc, S, D)
            nv = jnp.sum(valid.astype(jnp.float32))
            drop = jax.lax.psum(drop * nv, all_axes) / jnp.maximum(
                jax.lax.psum(nv, all_axes), 1.0
            )
            out = (y, jax.lax.pmean(aux, all_axes), drop)
            if return_expert_counts:
                out += (jax.lax.psum(counts, all_axes),)
            return out

        n_out = 4 if return_expert_counts else 3
        return jax.jit(shard_map(
            body,
            mesh=mesh,
            in_specs=(x_spec,) + tuple(spec_flat),
            out_specs=(x_spec,) + (P(),) * (n_out - 1),
            check_vma=False,
        ))

    if cache is not None:
        # fingerprint-stripped: the compiled transport depends on geometry
        # + mode only, so adaptive re-plans reuse compiled executors
        geom_key = dataclasses.replace(plan, fingerprint="")
        key = ("moe_exec", geom_key, mesh, x_spec, ptree, cfg.act,
               return_expert_counts)
        fn = cache.moe_executor(key, build)
    else:
        fn = build()
    return fn(x, *pflat)


def _dedup_outbound(x_lane, slot, keep, phys, params, plan, act_fn):
    """Paper's fully-optimized outbound: one copy per (token, dst region) +
    int32 metadata; fan out to expert slots inside the region.

    Returns (expert outputs laid out [G(src device, pod-major) * eC, D],
    pair_ok [N*k] bool: pairs whose token won a uniq slot and will come
    back — pairs beyond ``uniq_capacity`` fan out from the zero pad row,
    i.e. they are dropped and the caller must count them as such)."""
    N, D = x_lane.shape
    C = plan.capacity
    Rg = plan.region_size
    Dg = plan.devs_per_region
    eC = plan.ec
    Cu = plan.uniq_capacity
    Cp = Dg * eC                              # exact pair bound per region

    keep_f = keep.reshape(-1)
    dev = (phys // plan.e_per_dev).reshape(-1)           # dst device
    region = jnp.where(keep_f, dev // Dg, Rg)            # pod-major order
    pair_token = jnp.repeat(jnp.arange(N), plan.top_k)

    # ---- lane-local dedup: first pair of each (region, token) key --------
    key = region * (N + 1) + pair_token
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    is_first = jnp.concatenate(
        [jnp.ones((1,), bool), key_s[1:] != key_s[:-1]]
    )
    region_s = region[order]
    # unique rank within region: count of firsts so far in this region
    reg_start = jnp.concatenate(
        [jnp.ones((1,), bool), region_s[1:] != region_s[:-1]]
    )
    firsts = is_first.astype(jnp.int32)
    cum = jnp.cumsum(firsts)
    reg_base = jax.lax.associative_scan(
        jnp.maximum, jnp.where(reg_start, cum - firsts, 0)
    )
    ur = cum - firsts - reg_base                          # 0-based, sorted
    uniq_ok_s = is_first & (ur < Cu) & (region_s < Rg)
    uslot_s = jnp.where(uniq_ok_s, region_s * Cu + ur, Rg * Cu)

    # forward-fill each key's uslot to its non-first pairs via segment ids
    n_pairs = key.shape[0]
    seg_id = cum - 1                                      # key index, sorted
    seg_uslot = jnp.full((n_pairs + 1,), Rg * Cu, jnp.int32)
    seg_uslot = seg_uslot.at[
        jnp.where(is_first, seg_id, n_pairs)
    ].set(uslot_s.astype(jnp.int32))
    pair_uslot_s = seg_uslot[seg_id]
    pair_uslot = jnp.zeros((n_pairs,), jnp.int32).at[order].set(pair_uslot_s)

    # uniq value buffer [Rg*Cu] -> source token
    uniq_token = jnp.full((Rg * Cu + 1,), N, jnp.int32)
    uniq_token = uniq_token.at[uslot_s].set(
        pair_token[order].astype(jnp.int32)
    )[: Rg * Cu]

    # ---- metadata: meta[region, dst_in_region] = uslot-within-region ------
    slot_f = slot.reshape(-1)
    dst_in_region = jnp.where(
        keep_f, (dev % Dg) * eC + slot_f % eC, Cp
    )
    pair_ok = keep_f & (pair_uslot < Rg * Cu)
    mpos = jnp.where(pair_ok, region * Cp + dst_in_region, Rg * Cp)
    meta = jnp.full((Rg * Cp + 1,), -1, jnp.int32)
    meta = meta.at[mpos].set((pair_uslot % Cu).astype(jnp.int32))[: Rg * Cp]

    # ---- ship uniques + metadata across the slow axis ---------------------
    x_pad = jnp.concatenate([x_lane, jnp.zeros((1, D), x_lane.dtype)], 0)
    uniq_vals = pack_gather(x_pad, jnp.minimum(uniq_token, N))  # [Rg*Cu, D]
    uniq_rcv = _a2a(uniq_vals.reshape(Rg, Cu, D), plan.region_axis, 0, 0)
    meta_rcv = _a2a(meta.reshape(Rg, Cp), plan.region_axis, 0, 0)

    # ---- fan out inside the region (paper step r) --------------------------
    u_flat = uniq_rcv.reshape(Rg * Cu, D)
    u_pad = jnp.concatenate([u_flat, jnp.zeros((1, D), u_flat.dtype)], 0)
    m_flat = meta_rcv.reshape(Rg * Cp)                   # uslot or -1
    src_reg = jnp.repeat(jnp.arange(Rg), Cp)
    valid = m_flat >= 0
    gidx = jnp.where(valid, src_reg * Cu + m_flat, Rg * Cu)
    vals = pack_gather(u_pad, gidx)                      # [Rg*Cp, D]
    # rearrange [src_reg, dst_dev_in_region, eC] -> [dst_dev, src_reg, eC]
    fan = vals.reshape(Rg, Dg, eC, D)
    fan = jnp.swapaxes(fan, 0, 1).reshape(Dg, Rg * eC, D)
    if Dg > 1:
        fan = _a2a(fan, "model", 0, 0)                   # dim0 -> src lane
    xb = fan.reshape(Dg, Rg, plan.e_per_dev, C, D)
    # expert batches with source device pod-major: g0 = src_reg * Dg + lane
    xb = xb.transpose(2, 1, 0, 3, 4).reshape(
        plan.e_per_dev, Rg * Dg * C, D
    )
    yo = _expert_ffn(params["w_gate"], params["w_up"], params["w_down"],
                     act_fn, xb)
    yb = yo.reshape(plan.e_per_dev, Rg, Dg, C, D).transpose(1, 2, 0, 3, 4)
    return yb.reshape(plan.ep_size * eC, D), pair_ok
