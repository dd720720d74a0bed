"""Model assembly: one Model class covering all ten assigned architectures.

Families
--------
dense / vlm   : scan over homogeneous GQA transformer blocks; per-layer
                window array realizes gemma3's 5 local : 1 global pattern
                and Mixtral SWA; vlm consumes precomputed patch embeddings
                (frontend stub) + M-RoPE 3-D positions.
moe           : attention (GQA or MLA) + expert-parallel MoE FFN via
                ``models.moe`` (the paper's locality-aware dispatch);
                optional shared experts + leading dense layers (DeepSeek).
ssm           : scan over Mamba-2 SSD blocks.
hybrid        : zamba2 — (period x mamba -> shared attn block) segments;
                the two shared transformer blocks alternate and read
                concat(x, x_emb) (2d) as attention input.
audio         : seamless enc-dec — bidirectional encoder over stub frame
                embeddings; causal decoder with cross-attention.

Serving: prefill() fills per-layer caches (rolling window caches for
sliding-window layers — a window layer never allocates more than
``window`` KV slots, which is what makes gemma3/mixtral long_500k fit);
decode_step() advances one token with O(1) (SSM) or O(cache) (attn) work.

Sharding: ``param_specs()`` returns a PartitionSpec pytree (Megatron-style
TP over 'model', vocab-parallel embed/logits; expert weights over the EP
axes; everything replicated over 'pod'/'data' unless fsdp=True adds a
'data' shard on the large dims).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from .attention import (
    gqa_attention,
    gqa_cross_from_cache,
    gqa_project_out,
    gqa_project_qkv,
    init_gqa,
    init_mla,
    mla_attention,
    project_cross_kv,
)
from .blocks import dense_block, init_dense_block, init_mlp, mlp
from .common import ArchConfig, Initializer, rms_norm
from ..core import default_plan_cache
from .moe import (
    MoEPlan,
    init_moe,
    make_moe_plan,
    moe_layer,
    moe_param_specs,
    moe_plan_for,
)
from .ssm import init_mamba, init_mamba_state, mamba_block


def _stack_slice(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


class Model:
    def __init__(
        self,
        cfg: ArchConfig,
        mesh: Optional[Mesh] = None,
        moe_mode: str = "auto",
        ep_over_pods: bool = True,
        remat: bool = True,
        fsdp: bool = False,
        moe_cap_factor: float = 1.25,
        scan_layers: bool = True,
        seq_shard: bool = False,
    ):
        self.cfg = cfg
        mesh = mesh if mesh is not None else jax.make_mesh(
            (1, 1), ("data", "model")
        )
        # the model's sharding constraints name mesh axes, which JAX
        # accepts only on Auto axes; jax.make_mesh builds Explicit ones
        self.mesh = mesh.update(
            axis_types=(AxisType.Auto,) * len(mesh.axis_names)
        )
        self.moe_mode = moe_mode
        self.ep_over_pods = ep_over_pods
        self.remat = remat
        self.fsdp = fsdp
        self.moe_cap_factor = moe_cap_factor
        # scan_layers=False unrolls layer loops: bigger HLO, but
        # cost_analysis() counts every layer (scan bodies count once) —
        # the dry-run uses unrolled for truthful roofline terms.
        self.scan_layers = scan_layers
        # Megatron-style sequence sharding of the residual stream between
        # blocks: remat residuals shrink by the TP degree; the compiler
        # inserts all-gather (entering attention/mlp) + reduce-scatter
        # (leaving) — trading memory for ICI traffic.
        self.seq_shard = seq_shard
        axes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        self.batch_axes = tuple(
            a for a in ("pod", "data") if a in axes
        )
        if cfg.family == "moe":
            self.e_phys = self._probe_plan().e_phys
        else:
            self.e_phys = 0
        # per-layer window schedule (dense/vlm/moe)
        self.windows = np.array(
            [
                0 if cfg.layer_is_global(i) else cfg.window
                for i in range(cfg.n_layers)
            ],
            dtype=np.int32,
        ) if cfg.window and cfg.local_global_period else np.full(
            cfg.n_layers, cfg.window, dtype=np.int32
        )

    def _probe_plan(self, tokens_per_lane: int = 8) -> MoEPlan:
        """Geometry-only plan (e_phys / param sharding don't depend on the
        transport, so ``auto`` probes with the flat-a2a geometry)."""
        return make_moe_plan(
            self.cfg, self.mesh, tokens_per_lane,
            mode=("a2a" if self.moe_mode == "auto" else self.moe_mode),
            ep_over_pods=self.ep_over_pods,
        )

    # ------------------------------------------------------------------ init

    def init_params(self, seed: int = 0, abstract: bool = False) -> Dict:
        cfg = self.cfg
        init = Initializer(seed, cfg.dtype, abstract=abstract)
        p: Dict[str, Any] = {
            "embed": init.tensor((cfg.vocab, cfg.d_model), fan_in=cfg.d_model),
            "final_norm": init.tensor((cfg.d_model,), zero=True),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = init.tensor((cfg.d_model, cfg.vocab),
                                       fan_in=cfg.d_model)
        fam = cfg.family
        if fam in ("dense", "vlm"):
            p["blocks"] = init_dense_block(init, cfg, cfg.n_layers)
        elif fam == "moe":
            L = cfg.n_layers - cfg.first_dense_layers
            blocks = {
                "ln1": init.tensor((L, cfg.d_model), zero=True),
                "ln2": init.tensor((L, cfg.d_model), zero=True),
                "attn": (init_mla(init, cfg, L) if cfg.mla
                         else init_gqa(init, cfg, L)),
                "moe": init_moe(init, cfg, L, self.e_phys),
            }
            p["blocks"] = blocks
            if cfg.first_dense_layers:
                p["dense0"] = init_dense_block(
                    init, cfg, cfg.first_dense_layers
                )
        elif fam == "ssm":
            p["blocks"] = init_mamba(init, cfg, cfg.n_layers)
        elif fam == "hybrid":
            per = cfg.shared_attn_period
            n_seg = cfg.n_layers // per
            tail = cfg.n_layers - n_seg * per
            p["mamba_main"] = init_mamba(init, cfg, n_seg * per)
            p["mamba_tail"] = init_mamba(init, cfg, tail) if tail else {}
            shared = {
                "ln1": init.tensor((cfg.n_shared_attn_blocks, 2 * cfg.d_model),
                                   zero=True),
                "attn": init_gqa(init, cfg, cfg.n_shared_attn_blocks,
                                 d_in=2 * cfg.d_model),
                "ln2": init.tensor((cfg.n_shared_attn_blocks, cfg.d_model),
                                   zero=True),
                "mlp": init_mlp(init, cfg.d_model, cfg.d_ff,
                                cfg.n_shared_attn_blocks),
            }
            p["shared"] = shared
        elif fam == "audio":
            p["enc_blocks"] = init_dense_block(init, cfg, cfg.n_enc_layers)
            p["enc_norm"] = init.tensor((cfg.d_model,), zero=True)
            p["dec_blocks"] = init_dense_block(init, cfg, cfg.n_dec_layers,
                                               cross=True)
        else:
            raise ValueError(fam)
        return p

    # ---------------------------------------------------------------- specs

    def param_specs(self) -> Dict:
        cfg = self.cfg
        axes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        fsdp_ax = "data" if (self.fsdp and axes.get("data", 1) > 1) else None
        moe_plan = self._probe_plan() if cfg.family == "moe" else None
        moe_specs = moe_param_specs(cfg, moe_plan) if moe_plan else {}

        col = {"wq", "wk", "wv", "wz", "wx", "wB", "wC", "wdt",
               "w_gate", "w_up", "ws_gate", "ws_up", "w_uk", "w_uv"}
        row = {"wo", "w_down", "ws_down"}
        bias = {"bq", "bk", "bv"}

        def rule(path, leaf) -> P:
            names = [getattr(k, "key", getattr(k, "name", None))
                     for k in path]
            name = names[-1]
            under_moe = "moe" in names
            nd = len(leaf.shape)
            if under_moe and name in moe_specs:
                return moe_specs[name]
            if name == "embed":
                return P("model", fsdp_ax)
            if name == "lm_head":
                return P(fsdp_ax, "model")
            if name in col:
                lead = (None,) * (nd - 2)
                return P(*lead, fsdp_ax, "model")
            if name in row:
                lead = (None,) * (nd - 2)
                return P(*lead, "model", fsdp_ax)
            if name in bias:
                lead = (None,) * (nd - 1)
                return P(*lead, "model")
            if name in ("conv_x", "conv_B", "conv_C"):
                return P(None, None, "model")
            return P()  # norms, scalars, routers, w_dkv, A_log, D, ...

        params = self.init_params(abstract=True)
        return jax.tree_util.tree_map_with_path(rule, params)

    # -------------------------------------------------------------- forward

    def _positions(self, inputs: Dict, T: int, B: int):
        if "positions" in inputs:
            return inputs["positions"]
        if self.cfg.mrope_sections is not None:
            pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
            return jnp.broadcast_to(pos[:, None, :], (B, 3, T))
        return jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def _embed_in(self, params, inputs) -> jnp.ndarray:
        if "embeds" in inputs:
            return inputs["embeds"].astype(self.cfg.dtype)
        x = params["embed"][inputs["tokens"]]
        return x * jnp.asarray(np.sqrt(self.cfg.d_model), x.dtype)

    def _logits(self, params, x):
        head = (params["embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])
        logits = x @ head.astype(x.dtype)
        spec = P(self.batch_axes if len(self.batch_axes) > 1
                 else (self.batch_axes[0] if self.batch_axes else None),
                 None, "model")
        from jax.sharding import NamedSharding
        return jax.lax.with_sharding_constraint(
            logits, NamedSharding(self.mesh, spec)
        )

    def _maybe_remat(self, fn):
        return jax.checkpoint(fn) if self.remat else fn

    def _sp(self, x):
        """Sequence-shard the residual stream over 'model' (if enabled)."""
        if not self.seq_shard:
            return x
        axes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        if x.shape[1] % axes.get("model", 1):
            return x
        from jax.sharding import NamedSharding
        b = (self.batch_axes if len(self.batch_axes) > 1
             else (self.batch_axes[0] if self.batch_axes else None))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(b, "model", None))
        )

    def _scan_or_loop(self, body, carry, xs):
        """lax.scan when scan_layers else an unrolled python loop.
        ``xs``: pytree stacked on the leading (layer) axis."""
        fn = self._maybe_remat(body)
        if self.scan_layers:
            carry, _ = jax.lax.scan(fn, carry, xs)
            return carry
        L = jax.tree.leaves(xs)[0].shape[0]
        for i in range(L):
            carry, _ = fn(carry, _stack_slice(xs, i))
        return carry

    def forward(self, params: Dict, inputs: Dict,
                return_hidden: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Training/eval forward. Returns (logits [B,S,V], aux loss);
        return_hidden=True returns the final-norm hidden states instead
        (the chunked xent projects them block-by-block)."""
        cfg = self.cfg
        if cfg.family == "audio":
            return self._forward_encdec(params, inputs, return_hidden)
        if "embeds" in inputs:
            B, T = inputs["embeds"].shape[:2]
        else:
            B, T = inputs["tokens"].shape
        x = self._embed_in(params, inputs)
        pos = self._positions(inputs, T, B)
        aux = jnp.zeros((), jnp.float32)

        if cfg.family in ("dense", "vlm"):
            win = jnp.asarray(self.windows)

            def body(h, per):
                p_l, w_l = per
                h, _ = dense_block(p_l, h, pos, cfg, window=w_l)
                return self._sp(h), None

            x = self._scan_or_loop(body, x, (params["blocks"], win))
        elif cfg.family == "moe":
            x, aux = self._forward_moe(params, x, pos)
        elif cfg.family == "ssm":
            def body(h, p_l):
                h, _ = mamba_block(p_l, h, cfg)
                return self._sp(h), None

            x = self._scan_or_loop(body, x, params["blocks"])
        elif cfg.family == "hybrid":
            x = self._forward_hybrid(params, x, pos)
        h = rms_norm(x, params["final_norm"])
        if return_hidden:
            return h, aux
        return self._logits(params, h), aux

    def _forward_moe(self, params, x, pos):
        cfg = self.cfg
        B, T = x.shape[0], x.shape[1]
        n_tok_dev = B * T // max(
            1, int(np.prod([dict(zip(self.mesh.axis_names,
                                     self.mesh.devices.shape))[a]
                            for a in self.batch_axes]))
        )
        axes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        lanes = axes["model"]
        # cached planning: repeated forwards on an unchanged mesh and token
        # count hit the plan cache (mode="auto" -> Section-5 selection)
        plan = moe_plan_for(
            cfg, self.mesh, max(1, n_tok_dev // lanes),
            mode=self.moe_mode, ep_over_pods=self.ep_over_pods,
            cap_factor=self.moe_cap_factor,
        )
        if cfg.first_dense_layers:
            for i in range(cfg.first_dense_layers):
                x, _ = dense_block(_stack_slice(params["dense0"], i), x, pos,
                                   cfg, window=0)

        def body(carry, p_l):
            h, aux = carry
            hn = rms_norm(h, p_l["ln1"])
            if cfg.mla:
                a, _ = mla_attention(p_l["attn"], hn, pos, cfg)
            else:
                a, _ = gqa_attention(p_l["attn"], hn, pos, cfg,
                                     window=cfg.window)
            h = h + a
            hn = rms_norm(h, p_l["ln2"])
            y, aux_l, _drop = moe_layer(hn, p_l["moe"], plan, cfg, self.mesh,
                                        self.batch_axes,
                                        cache=default_plan_cache())
            if cfg.n_shared_experts:
                y = y + mlp({"w_" + k[3:]: v for k, v in p_l["moe"].items()
                             if k.startswith("ws_")}, hn, cfg.act)
            return (h + y, aux + aux_l), None

        x, aux = self._scan_or_loop(
            body, (x, jnp.zeros((), jnp.float32)), params["blocks"]
        )
        return x, aux * self.cfg.router_aux_coef

    def _shared_attn_block(self, p_s, x, x0, pos):
        """zamba2 shared block: attention over concat(x, x0)."""
        cfg = self.cfg
        cat = jnp.concatenate([x, x0], axis=-1)
        h = rms_norm(cat, p_s["ln1"])
        q, k, v = gqa_project_qkv(p_s["attn"], h, pos, cfg)
        from ..kernels.flash_attention import attention as flash
        o = flash(q, k, v, causal=True)
        x = x + gqa_project_out(p_s["attn"], o, cfg)
        h = rms_norm(x, p_s["ln2"])
        return x + mlp(p_s["mlp"], h, cfg.act)

    def _forward_hybrid(self, params, x, pos):
        cfg = self.cfg
        per = cfg.shared_attn_period
        n_seg = cfg.n_layers // per
        x0 = x
        main = jax.tree.map(
            lambda a: a.reshape((n_seg, per) + a.shape[1:]),
            params["mamba_main"],
        )

        def seg_body(h, inp):
            seg_params, seg_idx = inp

            def inner(hh, p_l):
                hh, _ = mamba_block(p_l, hh, cfg)
                return hh, None

            if self.scan_layers:
                h, _ = jax.lax.scan(inner, h, seg_params)
            else:
                for j in range(per):
                    h, _ = inner(h, _stack_slice(seg_params, j))
            sb = jax.tree.map(
                lambda a: a[seg_idx % cfg.n_shared_attn_blocks],
                params["shared"],
            )
            h = self._shared_attn_block(sb, h, x0, pos)
            return h, None

        x = self._scan_or_loop(seg_body, x, (main, jnp.arange(n_seg)))
        if params.get("mamba_tail"):
            def tail_body(h, p_l):
                h, _ = mamba_block(p_l, h, cfg)
                return h, None
            x = self._scan_or_loop(tail_body, x, params["mamba_tail"])
        return x

    def _forward_encdec(self, params, inputs, return_hidden=False):
        cfg = self.cfg
        enc = inputs["enc_embeds"].astype(cfg.dtype)   # [B, Se, d] stub
        B, Se = enc.shape[:2]
        pos_e = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))

        def ebody(h, p_l):
            h, _ = dense_block(p_l, h, pos_e, cfg, causal=False)
            return h, None

        enc = self._scan_or_loop(ebody, enc, params["enc_blocks"])
        memory = rms_norm(enc, params["enc_norm"])

        tokens = inputs["tokens"]
        B, T = tokens.shape
        x = self._embed_in(params, {"tokens": tokens})
        pos = self._positions(inputs, T, B)

        def dbody(h, p_l):
            h, _ = dense_block(p_l, h, pos, cfg, memory=memory)
            return h, None

        x = self._scan_or_loop(dbody, x, params["dec_blocks"])
        h = rms_norm(x, params["final_norm"])
        if return_hidden:
            return h, jnp.zeros((), jnp.float32)
        return self._logits(params, h), jnp.zeros((), jnp.float32)

    # ----------------------------------------------------------------- loss

    def _xent(self, x: jnp.ndarray, head: jnp.ndarray,
              labels: jnp.ndarray, mask: jnp.ndarray,
              block: int = 512) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Fused, vocab-parallel, sequence-chunked softmax cross entropy.

        Memory discipline for 256k vocabs: logits are produced per sequence
        block inside a checkpointed scan, so neither the [B,S,V] logits nor
        their f32 backward ever materialize (the projection is recomputed
        per block in the backward pass).  The vocab reduction never gathers:
        lse and the label logit are *reductions* over the model-sharded
        vocab dim (tiny [B,blk] all-reduces).
        Returns (ce_sum [scalar], z_sum [scalar]) — caller normalizes."""
        B, S, _ = x.shape
        if S % block or S <= block:
            block = S
        nb = S // block
        xb = jnp.moveaxis(x.reshape(B, nb, block, -1), 1, 0)
        lb = jnp.moveaxis(labels.reshape(B, nb, block), 1, 0)
        mb = jnp.moveaxis(mask.reshape(B, nb, block), 1, 0)

        def body(carry, inp):
            ce_sum, z_sum = carry
            xc, lc, mc = inp
            logits = xc @ head.astype(xc.dtype)          # [B, blk, V/tp]
            m = jax.lax.stop_gradient(
                jnp.max(logits, axis=-1, keepdims=True)
            ).astype(jnp.float32)
            ef = jnp.exp(logits.astype(jnp.float32) - m)
            lse = jnp.log(jnp.sum(ef, axis=-1)) + m[..., 0]
            iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
            ll = jnp.sum(
                jnp.where(iota == lc[..., None],
                          logits.astype(jnp.float32), 0.0),
                axis=-1,
            )
            ce_sum = ce_sum + jnp.sum((lse - ll) * mc)
            z_sum = z_sum + jnp.sum(jnp.square(lse) * mc)
            return (ce_sum, z_sum), None

        (ce_sum, z_sum), _ = jax.lax.scan(
            jax.checkpoint(body),
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (xb, lb, mb),
        )
        return ce_sum, z_sum

    def loss(self, params: Dict, batch: Dict) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg
        x, aux = self.forward(params, batch, return_hidden=True)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        labels = batch["labels"]
        mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        ce_sum, z_sum = self._xent(x, head, labels, mask)
        ce = ce_sum / denom
        zloss = 1e-4 * z_sum / denom
        total = ce + zloss + aux
        return total, {"ce": ce, "aux": aux, "zloss": zloss}
