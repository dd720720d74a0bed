"""Serving: prefill + single-token decode for every family.

The serving forward uses a python loop over layers (not scan) so per-layer
cache shapes may differ: sliding-window layers allocate exactly ``window``
KV slots (rolling cache, left-aligned, roll-when-full) while global layers
allocate ``max_len``.  That asymmetry is what makes gemma3 / mixtral
long_500k decodable: only the global/full layers pay O(max_len) memory.

Cache invariants (attention layers):
  * slots [0, filled) hold the most recent ``filled`` tokens in order;
  * filled = min(cur_len, Lc); K entries are stored *post-RoPE* at their
    true positions, so relative attention survives eviction;
  * the flash kernel masks with kv_len=filled, q_offset=filled-1+T_new.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.flash_attention import attention as flash
from .attention import (
    gqa_project_out,
    gqa_project_qkv,
    mla_attention,
    project_cross_kv,
    gqa_cross_from_cache,
)
from ..core import default_plan_cache
from .blocks import mlp
from .common import rms_norm
from .lm import Model, _stack_slice
from .moe import moe_layer, moe_plan_for
from .ssm import init_mamba_state, mamba_block


# ---------------------------------------------------------------------------
# attention-layer cache ops
# ---------------------------------------------------------------------------


def _prefill_attn(p_l, x, pos, cfg, window, max_len):
    """Full-sequence attention; returns (out, (ck, cv, filled))."""
    B, T, _ = x.shape
    q, k, v = gqa_project_qkv(p_l, x, pos, cfg)
    o = flash(q, k, v, causal=True, window=window)
    out = gqa_project_out(p_l, o, cfg)
    Lc = window if window > 0 else max_len
    Hkv, dh = k.shape[1], k.shape[3]
    if T >= Lc:
        ck, cv = k[:, :, T - Lc:], v[:, :, T - Lc:]
        filled = Lc
    else:
        # a pad keeps k's sharding, where a scatter into fresh zeros has
        # no output sharding on a mesh with Explicit axes
        tail = [(0, 0), (0, 0), (0, Lc - T), (0, 0)]
        ck, cv = jnp.pad(k, tail), jnp.pad(v, tail)
        filled = T
    return out, {"k": ck, "v": cv}


def _decode_attn(p_l, x, cur, cfg, window, cache):
    """One-token attention against a rolling cache."""
    B = x.shape[0]
    pos = jnp.broadcast_to(cur[None, None], (B, 1)).astype(jnp.int32)
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[:, None, :], (B, 3, 1))
    q, k, v = gqa_project_qkv(p_l, x, pos, cfg)   # k roped at true pos
    ck, cv = cache["k"], cache["v"]
    Lc = ck.shape[2]

    def append(args):
        ck, cv = args
        # literal 0s promote to int64 under jax_enable_x64 while `cur`
        # stays the caller's int32 — dynamic_update_slice requires one type
        zero = jnp.zeros((), cur.dtype)
        idx = (zero, zero, cur, zero)
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), idx)
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), idx)
        return ck, cv

    def roll(args):
        ck, cv = args
        ck = jnp.concatenate([ck[:, :, 1:], k.astype(ck.dtype)], axis=2)
        cv = jnp.concatenate([cv[:, :, 1:], v.astype(cv.dtype)], axis=2)
        return ck, cv

    ck, cv = jax.lax.cond(cur >= Lc, roll, append, (ck, cv))
    filled = jnp.minimum(cur + 1, Lc)
    o = flash(q, ck, cv, causal=True, kv_len=filled, q_offset=filled - 1)
    return gqa_project_out(p_l, o, cfg), {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# family dispatch: one layer (prefill or decode)
# ---------------------------------------------------------------------------


def moe_tokens_per_lane(model: Model, n_tokens: int) -> int:
    """Per-lane token count a forward of ``n_tokens`` global tokens
    dispatches — the single shape-derivation site shared by `_moe_ffn`,
    ``serve.engine``'s pre-warm and the adaptive re-planner, so the three
    can never key different plan-cache entries for one workload."""
    axes = dict(zip(model.mesh.axis_names, model.mesh.devices.shape))
    lanes = axes["model"]
    n_dev = max(1, int(np.prod([axes[a] for a in model.batch_axes])))
    return max(1, n_tokens // n_dev // lanes)


def moe_plan_for_model(model: Model, n_tokens: int, cache=None):
    """The dispatch plan a ``model`` forward uses for ``n_tokens`` global
    tokens — see :func:`moe_tokens_per_lane` for the shared shape key.

    Cached planning: every decode step (n_tokens=B) and every prefill of
    an equal prompt length key the same plan-cache entry — steady-state
    serving re-plans nothing."""
    return moe_plan_for(
        model.cfg, model.mesh, moe_tokens_per_lane(model, n_tokens),
        mode=model.moe_mode, ep_over_pods=model.ep_over_pods,
        cap_factor=model.moe_cap_factor, cache=cache,
    )


def moe_exchange_probe(
    model: Model,
    plan,
    n_tokens: int,
    cache=None,
    iters: int = 5,
    warmup: int = 1,
):
    """Time ``plan``'s dispatch pattern as a PURE exchange: (CommPlan,
    seconds_per_exchange), or None when there is nothing to probe (dense
    mode / non-MoE family).

    The online-calibration feed of ``ServeEngine(observe=True)``: decode
    dispatch wall time includes expert compute (recorded
    ``pure_exchange=False``, excluded from rate fits), so the engine
    periodically runs the *same routing pattern* as a bare neighborhood
    exchange on the EP devices — those samples are fit-grade.  The
    collective and its bound executor go through ``cache``, so repeated
    probes re-plan and re-bind nothing.  Synthetic f32 payload with
    ``d_model * itemsize`` bytes per value matches the plan's modeled
    wire volume.
    """
    from ..obs import now as _now
    from .moe import STRATEGY_OF_MODE, dispatch_pattern, dispatch_topology

    if plan is None or plan.mode not in STRATEGY_OF_MODE:
        return None
    cache = cache if cache is not None else default_plan_cache()
    pattern, _stats, _fp = dispatch_pattern(
        plan, moe_tokens_per_lane(model, n_tokens)
    )
    topo = dispatch_topology(plan)
    value_bytes = model.cfg.d_model * np.dtype(model.cfg.dtype).itemsize
    strategy = STRATEGY_OF_MODE[plan.mode]
    devs = np.asarray(model.mesh.devices).reshape(-1)[: topo.n_procs]
    mesh = jax.sharding.Mesh(devs, ("probe",))
    coll = cache.collective(pattern, topo, strategy, value_bytes)
    fn = jax.jit(cache.executor(pattern, topo, mesh, "probe",
                                strategy=strategy, value_bytes=value_bytes))
    # f32 payload, one value = d columns -> value_bytes on the wire
    d = max(1, value_bytes // 4)
    n_pad = max(1, int(pattern.n_local.max()))
    x = jnp.asarray(
        np.random.default_rng(0)
        .normal(size=(topo.n_procs, n_pad, d))
        .astype(np.float32)
    )
    fn(x).block_until_ready()          # compile
    for _ in range(warmup):
        fn(x).block_until_ready()
    t0 = _now()
    for _ in range(iters):
        fn(x).block_until_ready()
    return coll.plan, (_now() - t0) / iters


def _moe_ffn(model: Model, p_l, h, n_tokens, moe_plan=None, collect=False):
    """One MoE FFN sublayer.  ``moe_plan`` overrides the cached per-shape
    plan (the adaptive serving path pins a re-selected plan); with
    ``collect=True`` returns (y, expert_counts, dropped) so the decode
    loop can feed measured routing histograms to the re-planner."""
    cfg = model.cfg
    plan = moe_plan if moe_plan is not None \
        else moe_plan_for_model(model, n_tokens)
    out = moe_layer(h, p_l["moe"], plan, cfg, model.mesh,
                    model.batch_axes, cache=default_plan_cache(),
                    return_expert_counts=collect)
    y = out[0]
    if cfg.n_shared_experts:
        y = y + mlp({"w_" + k[3:]: v for k, v in p_l["moe"].items()
                     if k.startswith("ws_")}, h, cfg.act)
    if collect:
        return y, out[3], out[2]
    return y


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def prefill(model: Model, params: Dict, inputs: Dict, max_len: int,
            moe_plan=None):
    """Fill caches from a prompt. Returns (last_logits [B,V], cache).

    ``moe_plan`` pins the MoE dispatch plan instead of the per-(B*T)
    cached one — ``serve.engine`` plans prefill dispatch once for the
    worst case (B * max_len tokens) so re-prefills at every history
    length share a single plan-cache entry (capacity oversizes, results
    are unchanged: excess slots carry zero combine weight)."""
    cfg = model.cfg
    if cfg.family == "audio":
        return _prefill_encdec(model, params, inputs, max_len)
    x = model._embed_in(params, inputs)
    B, T = x.shape[:2]
    pos = model._positions(inputs, T, B)
    caches = []

    if cfg.family in ("dense", "vlm"):
        for i in range(cfg.n_layers):
            p_l = _stack_slice(params["blocks"], i)
            w = int(model.windows[i])
            h = rms_norm(x, p_l["ln1"])
            a, c = _prefill_attn(p_l["attn"], h, pos, cfg, w, max_len)
            if cfg.sandwich_norm:
                a = rms_norm(a, p_l["ln1_post"])
            x = x + a
            h = rms_norm(x, p_l["ln2"])
            m = mlp(p_l["mlp"], h, cfg.act)
            if cfg.sandwich_norm:
                m = rms_norm(m, p_l["ln2_post"])
            x = x + m
            caches.append(c)
    elif cfg.family == "moe":
        for i in range(cfg.first_dense_layers):
            p_l = _stack_slice(params["dense0"], i)
            h = rms_norm(x, p_l["ln1"])
            if cfg.mla:
                ckv0 = jnp.zeros(
                    (B, max_len, cfg.kv_lora + cfg.qk_rope_dim), cfg.dtype
                )
                a, ckv = mla_attention(p_l["attn"], h, pos, cfg,
                                       cache=ckv0, kv_len=0)
                c = {"ckv": ckv}
            else:
                a, c = _prefill_attn(p_l["attn"], h, pos, cfg, 0, max_len)
            x = x + a
            x = x + mlp(p_l["mlp"], rms_norm(x, p_l["ln2"]), cfg.act)
            caches.append(c)
        L = cfg.n_layers - cfg.first_dense_layers
        for i in range(L):
            p_l = _stack_slice(params["blocks"], i)
            h = rms_norm(x, p_l["ln1"])
            if cfg.mla:
                ckv0 = jnp.zeros(
                    (B, max_len, cfg.kv_lora + cfg.qk_rope_dim), cfg.dtype
                )
                a, ckv = mla_attention(p_l["attn"], h, pos, cfg,
                                       cache=ckv0, kv_len=0)
                c = {"ckv": ckv}
            else:
                a, c = _prefill_attn(p_l["attn"], h, pos, cfg, cfg.window,
                                     max_len)
            x = x + a
            h = rms_norm(x, p_l["ln2"])
            x = x + _moe_ffn(model, p_l, h, B * T, moe_plan=moe_plan)
            caches.append(c)
    elif cfg.family == "ssm":
        for i in range(cfg.n_layers):
            p_l = _stack_slice(params["blocks"], i)
            x, st = mamba_block(p_l, x, cfg, state=None,
                                return_state=True)
            caches.append(st)
    elif cfg.family == "hybrid":
        x0 = x
        per = cfg.shared_attn_period
        n_seg = cfg.n_layers // per
        li = 0
        for seg in range(n_seg):
            for j in range(per):
                p_l = _stack_slice(params["mamba_main"], li)
                x, st = mamba_block(p_l, x, cfg, return_state=True)
                caches.append(st)
                li += 1
            sb = _stack_slice(params["shared"],
                              seg % cfg.n_shared_attn_blocks)
            cat = jnp.concatenate([x, x0], axis=-1)
            h = rms_norm(cat, sb["ln1"])
            a, c = _prefill_attn(sb["attn"], h, pos, cfg, 0, max_len)
            x = x + a
            x = x + mlp(sb["mlp"], rms_norm(x, sb["ln2"]), cfg.act)
            caches.append(c)
        tail = cfg.n_layers - n_seg * per
        for j in range(tail):
            p_l = _stack_slice(params["mamba_tail"], j)
            x, st = mamba_block(p_l, x, cfg, return_state=True)
            caches.append(st)
    logits = model._logits(params, rms_norm(x[:, -1:], params["final_norm"]))
    return logits[:, 0], tuple(caches)


def _prefill_encdec(model: Model, params, inputs, max_len):
    cfg = model.cfg
    enc = inputs["enc_embeds"].astype(cfg.dtype)
    B, Se = enc.shape[:2]
    pos_e = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
    from .blocks import dense_block
    for i in range(cfg.n_enc_layers):
        p_l = _stack_slice(params["enc_blocks"], i)
        enc, _ = dense_block(p_l, enc, pos_e, cfg, causal=False)
    memory = rms_norm(enc, params["enc_norm"])

    tokens = inputs["tokens"]             # decoder prompt (BOS etc.)
    B, T = tokens.shape
    x = model._embed_in(params, {"tokens": tokens})
    pos = model._positions({}, T, B)
    caches = []
    for i in range(cfg.n_dec_layers):
        p_l = _stack_slice(params["dec_blocks"], i)
        h = rms_norm(x, p_l["ln1"])
        a, c = _prefill_attn(p_l["attn"], h, pos, cfg, 0, max_len)
        x = x + a
        hx = rms_norm(x, p_l["ln_x"])
        ckv = project_cross_kv(p_l["cross"], memory, cfg)
        x = x + gqa_cross_from_cache(p_l["cross"], hx, ckv, cfg)
        x = x + mlp(p_l["mlp"], rms_norm(x, p_l["ln2"]), cfg.act)
        caches.append({**c, "cross_k": ckv[0], "cross_v": ckv[1]})
    logits = model._logits(params, rms_norm(x[:, -1:], params["final_norm"]))
    return logits[:, 0], tuple(caches)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode_step(model: Model, params: Dict, inputs: Dict,
                caches: Tuple, cur_len, moe_plan=None,
                return_moe_stats: bool = False):
    """One-token step. ``inputs``: {"tokens": [B,1]} or {"embeds": [B,1,d]}.
    ``cur_len``: number of tokens already in the caches (traced scalar ok).
    Returns (logits [B, V], new caches); with ``return_moe_stats=True``
    (moe family) additionally a stats dict: ``expert_counts`` — the step's
    measured routing histogram summed over MoE layers ([e_log] f32, the
    adaptive re-planner's observation) — and ``dropped`` (mean capacity
    drop fraction over MoE layers).  ``moe_plan`` pins a dispatch plan
    (adaptive serving) instead of the per-shape cached lookup."""
    cfg = model.cfg
    cur = jnp.asarray(cur_len, jnp.int32)
    x = model._embed_in(params, inputs)
    B = x.shape[0]
    new_caches = []
    ci = 0
    moe_counts = None
    moe_drop = jnp.zeros((), jnp.float32)
    n_moe = 0

    def nxt():
        nonlocal ci
        c = caches[ci]
        ci += 1
        return c

    if cfg.family in ("dense", "vlm"):
        for i in range(cfg.n_layers):
            p_l = _stack_slice(params["blocks"], i)
            w = int(model.windows[i])
            h = rms_norm(x, p_l["ln1"])
            a, c = _decode_attn(p_l["attn"], h, cur, cfg, w, nxt())
            if cfg.sandwich_norm:
                a = rms_norm(a, p_l["ln1_post"])
            x = x + a
            h = rms_norm(x, p_l["ln2"])
            m = mlp(p_l["mlp"], h, cfg.act)
            if cfg.sandwich_norm:
                m = rms_norm(m, p_l["ln2_post"])
            x = x + m
            new_caches.append(c)
    elif cfg.family == "moe":
        pos = jnp.broadcast_to(cur[None, None], (B, 1)).astype(jnp.int32)
        for i in range(cfg.first_dense_layers):
            p_l = _stack_slice(params["dense0"], i)
            h = rms_norm(x, p_l["ln1"])
            if cfg.mla:
                c = nxt()
                a, ckv = mla_attention(p_l["attn"], h, pos, cfg,
                                       cache=c["ckv"], kv_len=cur)
                c = {"ckv": ckv}
            else:
                a, c = _decode_attn(p_l["attn"], h, cur, cfg, 0, nxt())
            x = x + a
            x = x + mlp(p_l["mlp"], rms_norm(x, p_l["ln2"]), cfg.act)
            new_caches.append(c)
        L = cfg.n_layers - cfg.first_dense_layers
        for i in range(L):
            p_l = _stack_slice(params["blocks"], i)
            h = rms_norm(x, p_l["ln1"])
            if cfg.mla:
                c = nxt()
                a, ckv = mla_attention(p_l["attn"], h, pos, cfg,
                                       cache=c["ckv"], kv_len=cur)
                c = {"ckv": ckv}
            else:
                a, c = _decode_attn(p_l["attn"], h, cur, cfg, cfg.window,
                                    nxt())
            x = x + a
            h = rms_norm(x, p_l["ln2"])
            if return_moe_stats:
                y, counts, drop = _moe_ffn(model, p_l, h, B,
                                           moe_plan=moe_plan, collect=True)
                moe_counts = counts if moe_counts is None \
                    else moe_counts + counts
                moe_drop = moe_drop + drop
                n_moe += 1
            else:
                y = _moe_ffn(model, p_l, h, B, moe_plan=moe_plan)
            x = x + y
            new_caches.append(c)
    elif cfg.family == "ssm":
        for i in range(cfg.n_layers):
            p_l = _stack_slice(params["blocks"], i)
            x, st = mamba_block(p_l, x, cfg, state=nxt())
            new_caches.append(st)
    elif cfg.family == "hybrid":
        x0 = x
        per = cfg.shared_attn_period
        n_seg = cfg.n_layers // per
        li = 0
        for seg in range(n_seg):
            for j in range(per):
                p_l = _stack_slice(params["mamba_main"], li)
                x, st = mamba_block(p_l, x, cfg, state=nxt())
                new_caches.append(st)
                li += 1
            sb = _stack_slice(params["shared"],
                              seg % cfg.n_shared_attn_blocks)
            cat = jnp.concatenate([x, x0], axis=-1)
            h = rms_norm(cat, sb["ln1"])
            a, c = _decode_attn(sb["attn"], h, cur, cfg, 0, nxt())
            x = x + a
            x = x + mlp(sb["mlp"], rms_norm(x, sb["ln2"]), cfg.act)
            new_caches.append(c)
        for j in range(cfg.n_layers - n_seg * per):
            p_l = _stack_slice(params["mamba_tail"], j)
            x, st = mamba_block(p_l, x, cfg, state=nxt())
            new_caches.append(st)
    elif cfg.family == "audio":
        for i in range(cfg.n_dec_layers):
            p_l = _stack_slice(params["dec_blocks"], i)
            c = nxt()
            h = rms_norm(x, p_l["ln1"])
            a, cc = _decode_attn(p_l["attn"], h, cur, cfg, 0,
                                 {"k": c["k"], "v": c["v"]})
            x = x + a
            hx = rms_norm(x, p_l["ln_x"])
            x = x + gqa_cross_from_cache(
                p_l["cross"], hx, (c["cross_k"], c["cross_v"]), cfg
            )
            x = x + mlp(p_l["mlp"], rms_norm(x, p_l["ln2"]), cfg.act)
            new_caches.append({**cc, "cross_k": c["cross_k"],
                               "cross_v": c["cross_v"]})
    logits = model._logits(params, rms_norm(x, params["final_norm"]))
    if return_moe_stats:
        if moe_counts is None:
            moe_counts = jnp.zeros((max(1, cfg.n_experts),), jnp.float32)
        stats = {
            "expert_counts": moe_counts,
            "dropped": moe_drop / max(1, n_moe),
        }
        return logits[:, 0], tuple(new_caches), stats
    return logits[:, 0], tuple(new_caches)
