"""MoE dispatch through the neighborhood-collective planning stack.

Covers the planned-dispatch tentpole (``moe_plan_for`` / PlanCache keys /
Section-5 ``auto`` selection) and the dispatch-geometry bugfixes: expert
replication round-up for non-divisible (n_experts, ep_size), push-side
empty-exchange dtype inference, and the capacity-drop observability
(``dropped_fraction``, token-major drop order).
"""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced
from repro.core import (
    PlanCache,
    SparseDynamicExchange,
    Topology,
    build_plan,
    default_plan_cache,
)
from repro.models.common import Initializer
from repro.models.moe import (
    capacity_pack,
    dispatch_pattern,
    init_moe,
    make_moe_plan,
    moe_layer,
    moe_plan_for,
    select_moe_mode,
)


def mesh_stub(*shape, pods=False):
    """make_moe_plan only reads axis_names/devices.shape — a stub covers
    every (e_log, ep_size) combination without real devices."""
    names = ("pod", "data", "model")[-len(shape):] if pods or len(shape) > 2 \
        else ("data", "model")[-len(shape):]
    return SimpleNamespace(axis_names=names, devices=np.empty(shape))


def moe_cfg(**over):
    cfg = reduced("mixtral-8x7b")
    return cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32, **over})


# ---------------------------------------------------------------------------
# geometry bugfix: non-divisible (n_experts, ep_size)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("e_log,ep", [(3, 4), (5, 8), (6, 4), (3, 8),
                                      (7, 4), (5, 2), (9, 6)])
def test_replication_rounds_up_to_even_packing(e_log, ep):
    """3 logical experts on 4 devices used to hit e_phys=6, 6 % 4 != 0."""
    cfg = moe_cfg(n_experts=e_log)
    plan = make_moe_plan(cfg, mesh_stub(1, ep), 32, mode="a2a")
    assert plan.ep_size == ep
    assert plan.e_phys % ep == 0
    assert plan.e_phys % e_log == 0           # whole replicas only
    assert plan.e_per_dev * ep == plan.e_phys
    assert plan.replicas >= 1
    # minimality: one fewer replication step would break even packing
    # (replicas is the least multiple of ep/gcd(e_log, ep) >= ceil(ep/e_log))
    import math
    step = ep // math.gcd(e_log, ep)
    assert plan.replicas % step == 0
    assert plan.replicas - step < max(1, math.ceil(ep / e_log)) \
        or plan.replicas == step


@pytest.mark.parametrize("e_log,ep", [(8, 4), (4, 4), (2, 8)])
def test_replication_unchanged_when_divisible(e_log, ep):
    cfg = moe_cfg(n_experts=e_log)
    plan = make_moe_plan(cfg, mesh_stub(1, ep), 32, mode="a2a")
    assert plan.e_phys == max(e_log, ep)


# ---------------------------------------------------------------------------
# push-side exchange: empty-receiver dtype, pattern equivalence
# ---------------------------------------------------------------------------


def test_push_all_empty_keeps_declared_dtype():
    """An all-empty exchange must still honor the senders' dtype (it used
    to fall back to float64 because only non-empty payloads were probed)."""
    n = 4
    dest = [np.zeros(0, np.int64)] * n
    payload = [np.zeros((0, 3), np.float32)] * n
    received, sources, _stats = SparseDynamicExchange.push(dest, payload)
    for r, s in zip(received, sources):
        assert r.dtype == np.float32
        assert r.shape == (0, 3)
        assert len(s) == 0


def test_push_mixed_empty_prefers_nonempty_dtype():
    dest = [np.array([1]), np.zeros(0, np.int64)]
    payload = [np.array([[1, 2]], np.int32), np.zeros((0, 2), np.float64)]
    received, _src, _stats = SparseDynamicExchange.push(dest, payload)
    assert received[1].dtype == np.int32
    np.testing.assert_array_equal(received[1], [[1, 2]])


def test_push_pattern_matches_push_delivery():
    """The CommPattern from push_pattern, executed as a standard plan,
    delivers exactly what push() delivers (same values, same order)."""
    rng = np.random.default_rng(7)
    n = 4
    dest = [rng.integers(0, n, size=rng.integers(0, 9)).astype(np.int64)
            for _ in range(n)]
    offsets = np.cumsum([0] + [len(d) for d in dest])
    # payload rows = their global ids, so delivered values identify rows
    payload = [np.arange(offsets[p], offsets[p] + len(dest[p]), dtype=np.int64)
               for p in range(n)]
    received, sources, _ = SparseDynamicExchange.push(dest, payload)

    pattern, stats = SparseDynamicExchange.push_pattern(dest)
    topo = Topology(n, 2)
    plan = build_plan(pattern, topo, "standard")
    local_vals = [p.astype(np.float64) for p in payload]
    ghosts = plan.execute_numpy(local_vals)
    for q in range(n):
        np.testing.assert_array_equal(ghosts[q].astype(np.int64), received[q])
        np.testing.assert_array_equal(
            pattern.owner_proc[pattern.needs[q]], sources[q]
        )
    assert stats.allreduce_ints == n * n


def test_push_pattern_duplicates_enable_dedup():
    """Pushing one value to several ranks of a region (top-k fan-out) must
    survive as duplicate global indices — which the full planner removes."""
    n = 4
    # rank 0 pushes its value 0 to ranks 2 and 3 (one region)
    dest = [np.array([2, 3]), np.zeros(0, np.int64),
            np.zeros(0, np.int64), np.zeros(0, np.int64)]
    local_ids = [np.array([0, 0]), np.zeros(0, np.int64),
                 np.zeros(0, np.int64), np.zeros(0, np.int64)]
    pattern, _ = SparseDynamicExchange.push_pattern(
        dest, local_ids, n_local=[1, 1, 1, 1]
    )
    topo = Topology(n, 2)
    partial = build_plan(pattern, topo, "partial")
    full = build_plan(pattern, topo, "full")
    assert int(partial.stats.inter_bytes.sum()) == 2 * 8
    assert int(full.stats.inter_bytes.sum()) == 1 * 8   # deduped crossing
    ghosts = full.execute_numpy([np.array([5.0]), np.zeros(0),
                                 np.zeros(0), np.zeros(0)])
    assert ghosts[2][0] == 5.0 and ghosts[3][0] == 5.0


# ---------------------------------------------------------------------------
# capacity drops: observable fraction, token-major order
# ---------------------------------------------------------------------------


def test_capacity_pack_drops_late_tokens_first():
    """Single hot expert: the first C pairs in token-major order keep their
    slots, every later-sequence token is dropped (documented bias)."""
    cfg = moe_cfg(n_experts=1, top_k=1)
    plan = make_moe_plan(cfg, mesh_stub(1, 1), 16, mode="a2a",
                         cap_factor=0.5)
    assert plan.capacity == 8
    phys = jnp.zeros((16, 1), jnp.int32)       # everyone routes to expert 0
    slot, keep, slot_token = capacity_pack(phys, plan)
    keep = np.asarray(keep).reshape(-1)
    assert keep[:8].all() and not keep[8:].any()
    np.testing.assert_array_equal(np.asarray(slot_token)[:8], np.arange(8))


def test_dropped_fraction_excludes_padding_rows():
    """Pads are routed (and may consume capacity) but must not enter the
    capacity-health metric: with 12 real of 16 rows and capacity 8 on one
    hot expert, dropped is 1 - 8/12, not 1 - 8/16."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.models.moe import moe_dispatch_lane

    cfg = moe_cfg(n_experts=1, top_k=1)
    plan = make_moe_plan(cfg, mesh_stub(1, 1), 16, mode="a2a",
                         cap_factor=0.5)
    assert plan.capacity == 8
    init = Initializer(0, jnp.float32)
    params = {k: v[0] for k, v in init_moe(init, cfg, 1, plan.e_phys).items()
              if not k.startswith("ws_")}
    mesh = jax.make_mesh((1,), ("model",))
    x = jnp.asarray(np.random.default_rng(0)
                    .normal(size=(16, cfg.d_model)).astype(np.float32))

    def body(xl):
        valid = jnp.arange(16) < 12
        _y, _aux, drop, _counts = moe_dispatch_lane(xl, params, plan, cfg,
                                                    valid=valid)
        return drop

    drop = shard_map(body, mesh=mesh, in_specs=(P(None, None),),
                     out_specs=P(), check_vma=False)(x)
    np.testing.assert_allclose(float(drop), 1.0 - 8.0 / 12.0, atol=1e-6)


def test_moe_layer_surfaces_dropped_fraction():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = moe_cfg(n_experts=4, top_k=1)
    cache = PlanCache()
    # biased router -> all tokens pick expert 0; capacity 8 of 16 pairs
    plan = moe_plan_for(cfg, mesh, 16, mode="a2a", cap_factor=0.5,
                        cache=cache)
    assert plan.capacity * plan.e_phys >= 8
    init = Initializer(0, jnp.float32)
    params = {k: v[0] for k, v in init_moe(init, cfg, 1, plan.e_phys).items()}
    params["router"] = params["router"] * 0.0
    params["router"] = params["router"].at[:, 0].set(50.0)
    # strictly positive features so the +50 column dominates every token's
    # logits and routing really is all-to-expert-0
    x = jnp.asarray(np.random.default_rng(0)
                    .uniform(0.1, 1.0, size=(1, 16, cfg.d_model))
                    .astype(np.float32))
    y, aux, dropped = moe_layer(x, params, plan, cfg, mesh, ("data",),
                                cache=cache)
    assert y.shape == x.shape
    # all 16 pairs target expert 0 (replicas=1): capacity keeps 8
    np.testing.assert_allclose(float(dropped), 0.5, atol=1e-6)
    # ample capacity drops nothing
    plan2 = moe_plan_for(cfg, mesh, 16, mode="a2a", cap_factor=8.0,
                         cache=cache)
    _y, _aux, dropped2 = moe_layer(x, params, plan2, cfg, mesh, ("data",),
                                   cache=cache)
    assert float(dropped2) == 0.0


def test_dropped_fraction_counts_dedup_uniq_overflow():
    """hier_dedup can also drop pairs when a region's distinct-token count
    exceeds uniq_capacity; those silent zero-contributions must show up in
    dropped_fraction exactly like expert-capacity drops."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = moe_cfg(n_experts=4, top_k=2)
    cache = PlanCache()
    # ample expert capacity, but dedup_factor squeezes uniq slots to 8 for
    # 16 distinct tokens hitting the (single-device) region
    plan = moe_plan_for(cfg, mesh, 16, mode="hier_dedup", cap_factor=8.0,
                        dedup_factor=0.05, cache=cache)
    assert plan.uniq_capacity == 8
    init = Initializer(0, jnp.float32)
    params = {k: v[0] for k, v in init_moe(init, cfg, 1, plan.e_phys).items()}
    x = jnp.asarray(np.random.default_rng(0)
                    .normal(size=(1, 16, cfg.d_model)).astype(np.float32))
    _y, _aux, dropped = moe_layer(x, params, plan, cfg, mesh, ("data",),
                                  cache=cache)
    # 8 of 16 tokens win a uniq slot; both pairs of each loser are dropped
    np.testing.assert_allclose(float(dropped), 0.5, atol=1e-6)


# ---------------------------------------------------------------------------
# planned dispatch: cache behavior + auto selection
# ---------------------------------------------------------------------------


def test_moe_plan_for_caches_by_shape_and_fingerprint():
    cfg = moe_cfg()
    mesh = mesh_stub(2, 1, 8, pods=True)      # EP spans 2 pods x 8 lanes
    cache = PlanCache()
    p1 = moe_plan_for(cfg, mesh, 128, mode="auto", cache=cache)
    assert (cache.misses, cache.hits) == (1, 0)
    assert p1.mode in ("a2a", "hier", "hier_dedup")
    assert p1.fingerprint
    p2 = moe_plan_for(cfg, mesh, 128, mode="auto", cache=cache)
    assert p2 is p1
    assert (cache.misses, cache.hits) == (1, 1)
    # a different token count is a different dispatch geometry
    p3 = moe_plan_for(cfg, mesh, 256, mode="auto", cache=cache)
    assert cache.misses == 2 and p3.capacity >= p1.capacity
    # explicit mode entry is distinct but equal geometry when auto agrees
    p4 = moe_plan_for(cfg, mesh, 128, mode=p1.mode, cache=cache)
    assert cache.misses == 3
    assert p4 == p1


def test_auto_selection_follows_cost_model_crossover():
    """Section-5 selection on a 4-pod EP group: aggregation wins the
    message-count-dominated regime (small wire rows), the flat a2a wins
    once bandwidth dominates — the paper's crossover, and the selected
    mode is always the model's argmin."""
    from repro.models.moe import STRATEGY_OF_MODE

    cfg = moe_cfg(n_experts=8, top_k=2)
    plan = make_moe_plan(cfg, mesh_stub(4, 1, 16, pods=True), 512,
                         mode="a2a")
    for vb, expect in ((512, ("hier", "hier_dedup")), (32768, ("a2a",))):
        mode, report = select_moe_mode(plan, 512, value_bytes=vb)
        best = min(report.modeled_times, key=report.modeled_times.get)
        assert STRATEGY_OF_MODE[mode] == best
        assert mode in expect, (vb, mode, report.modeled_times)
    # with top_k > 1, dedup never crosses more bytes than plain aggregation
    mode, report = select_moe_mode(plan, 512, value_bytes=512)
    assert report.modeled_times["full"] <= report.modeled_times["partial"]


def test_dispatch_pattern_fingerprint_is_stable():
    cfg = moe_cfg()
    plan = make_moe_plan(cfg, mesh_stub(2, 1, 4, pods=True), 64, mode="a2a")
    _pat1, _st1, fp1 = dispatch_pattern(plan, 64)
    _pat2, _st2, fp2 = dispatch_pattern(plan, 64)
    assert fp1 == fp2
    _pat3, _st3, fp3 = dispatch_pattern(plan, 128)
    assert fp3 != fp1


def test_repeated_forward_and_decode_plan_nothing():
    """Second identical forward and second identical decode step must
    report zero additional PlanCache misses (plans AND executors)."""
    from repro.models import Model, serving

    cfg = moe_cfg()
    model = Model(cfg, moe_mode="auto", remat=False, moe_cap_factor=8.0)
    params = model.init_params(seed=0)
    rng = np.random.default_rng(0)
    inputs = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, size=(2, 16)).astype(np.int32))}
    cache = default_plan_cache()

    model.forward(params, inputs)
    m0, e0 = cache.misses, cache.exec_misses
    model.forward(params, inputs)
    assert (cache.misses, cache.exec_misses) == (m0, e0)

    _last, caches = serving.prefill(model, params, inputs, max_len=32)
    tok = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, size=(2, 1))
                                 .astype(np.int32))}
    _l1, caches = serving.decode_step(model, params, tok, caches, cur_len=16)
    m0, e0 = cache.misses, cache.exec_misses
    _l2, caches = serving.decode_step(model, params, tok, caches, cur_len=17)
    assert (cache.misses, cache.exec_misses) == (m0, e0)
