import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (including jax and
# repro.*): jax locks the device count at first initialization, and the
# multi-pod dry-run needs 512 placeholder host devices.  Do not set this
# flag anywhere global — smoke tests and benchmarks see 1 device.
#
# Multi-pod dry-run driver (deliverable e):
#   for every (architecture x input shape x mesh) cell, build the jitted
#   step (train_step / prefill / serve_step), .lower().compile() it on the
#   production mesh, and record memory_analysis / cost_analysis /
#   collective bytes into benchmarks/results/dryrun/<cell>.json.
#
# Usage:
#   python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k \
#       --mesh multi
#   python -m repro.launch.dryrun --all        # sweep (subprocess per cell)

import argparse
import json
import subprocess
import sys
import time
import traceback

RESULTS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))),
    "benchmarks", "results", "dryrun",
)


def cell_path(arch: str, shape: str, mesh: str, moe_mode: str,
              fsdp: bool = False, remat: bool = True,
              variant: str = "", out_dir: str = RESULTS_DIR) -> str:
    tag = f"{arch}__{shape}__{mesh}"
    if moe_mode != "hier":
        tag += f"__{moe_mode}"
    if fsdp:
        tag += "__fsdp"
    if not remat:
        tag += "__noremat"
    if variant:
        tag += f"__{variant}"
    return os.path.join(out_dir, tag + ".json")


def run_cell(arch: str, shape_name: str, mesh_kind: str, moe_mode: str,
             fsdp: bool = False, remat: bool = True,
             cache_shard: str = "auto", seq_shard: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .. import configs
    from ..configs.shapes import SHAPES, skip_reason
    from ..models import Model, serving
    from ..train import TrainerConfig, jit_train_step, make_train_state
    from ..train.trainer import batch_specs, state_specs
    from .mesh import make_production_mesh, mesh_axis_sizes
    from .roofline import (
        analytic_attention_flops,
        analytic_memory_estimate,
        collective_bytes_from_hlo,
        dci_bytes_from_hlo,
        dci_message_count_from_hlo,
        model_flops,
        roofline_terms,
    )

    t_start = time.time()
    spec = SHAPES[shape_name]
    reason = skip_reason(arch, shape_name)
    if reason:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    axes = mesh_axis_sizes(mesh)
    chips = int(np.prod(list(axes.values())))
    cfg = configs.get(arch)
    spec_kind = SHAPES[shape_name].kind
    model = Model(cfg, mesh=mesh, moe_mode=moe_mode, ep_over_pods=True,
                  remat=remat, fsdp=fsdp,
                  scan_layers=(spec_kind == "train"), seq_shard=seq_shard)

    B, S = spec.global_batch, spec.seq_len
    n_batch_dev = int(np.prod([axes[a] for a in model.batch_axes]))
    b_ax = (model.batch_axes if len(model.batch_axes) > 1
            else model.batch_axes[0])
    b_spec = b_ax if B % n_batch_dev == 0 else None

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    def sh(spec_):
        return NamedSharding(mesh, spec_)

    def batch_sds(T, with_labels):
        d = {}
        if cfg.family == "audio":
            d["enc_embeds"] = sds((B, T, cfg.d_model), cfg.dtype)
            d["tokens"] = sds((B, T), jnp.int32)
        elif cfg.family == "vlm":
            d["embeds"] = sds((B, T, cfg.d_model), cfg.dtype)
            d["positions"] = sds((B, 3, T), jnp.int32)
        else:
            d["tokens"] = sds((B, T), jnp.int32)
        if with_labels:
            d["labels"] = sds((B, T), jnp.int32)
        return d

    def batch_shardings(d):
        out = {}
        for k, v in d.items():
            lead = (b_spec,) + (None,) * (len(v.shape) - 1)
            out[k] = sh(P(*lead))
        return out

    def cache_sharding_rule(leaf):
        """Pick shardable dims for cache leaves: dim0 over batch axes when
        divisible, then one more dim over 'model'.  cache_shard policy:
        'auto' = first divisible dim; 'dh' = prefer the LAST dim (head_dim
        stays local per chip, attention reduces over it); 'seq' = prefer
        the sequence dim (forces gather/permute at use)."""
        shp = leaf.shape
        entries = [None] * len(shp)
        if len(shp) and B % n_batch_dev == 0 and shp[0] == B:
            entries[0] = b_ax
        m = axes.get("model", 1)
        order = range(1, len(shp))
        if cache_shard == "dh":
            order = range(len(shp) - 1, 0, -1)
        for i in order:
            if shp[i] % m == 0 and shp[i] >= m:
                entries[i] = "model"
                break
        return sh(P(*entries))

    pspecs = model.param_specs()
    pshard = jax.tree.map(lambda s: sh(s), pspecs,
                          is_leaf=lambda s: isinstance(s, P))
    params_sds = model.init_params(abstract=True)

    ladder = None
    if spec.kind == "train":
        # FULL model compiles with scanned layers (fast; proves sharding
        # coherence + gives memory_analysis).  Exact per-layer costs come
        # from a 2-point "ladder" of small UNROLLED variants (1 and 2
        # layer-periods) and extrapolate linearly — exact for identical
        # layers, +/- a few % for mixed-period archs (gemma3/zamba tail).
        tcfg = TrainerConfig()
        step_jit, _ = jit_train_step(model, tcfg)
        state_sds = make_train_state(model, tcfg, abstract=True)
        lowered = step_jit.lower(state_sds, batch_sds(S, True))
        tokens = B * S

        import dataclasses as _dc

        def _ladder_cfgs():
            fam = cfg.family
            if fam == "audio":
                c1 = _dc.replace(cfg, n_layers=2, n_enc_layers=1,
                                 n_dec_layers=1)
                c2 = _dc.replace(cfg, n_layers=4, n_enc_layers=2,
                                 n_dec_layers=2)
                units = cfg.n_enc_layers  # enc+dec pairs
                return c1, c2, units
            per = (cfg.local_global_period
                   or (cfg.shared_attn_period if fam == "hybrid" else 0)
                   or 1)
            off = cfg.first_dense_layers
            c1 = _dc.replace(cfg, n_layers=off + per)
            c2 = _dc.replace(cfg, n_layers=off + 2 * per)
            units = (cfg.n_layers - off) / per
            return c1, c2, units

        def _train_costs(cfg_x):
            m_x = Model(cfg_x, mesh=mesh, moe_mode=moe_mode,
                        ep_over_pods=True, remat=remat, fsdp=fsdp,
                        scan_layers=False, seq_shard=seq_shard)
            sj, _ = jit_train_step(m_x, tcfg)
            st = make_train_state(m_x, tcfg, abstract=True)
            comp = sj.lower(st, batch_sds(S, True)).compile()
            c = comp.cost_analysis()
            txt = comp.as_text()
            cl = collective_bytes_from_hlo(txt)
            dc = (dci_bytes_from_hlo(txt) if mesh_kind == "multi"
                  else {"ici": 0, "dci": 0})
            dm = (dci_message_count_from_hlo(txt) if mesh_kind == "multi"
                  else 0)
            return (float(c.get("flops", 0.0)),
                    float(c.get("bytes accessed", 0.0)), cl, dc, dm)

        c1, c2, units = _ladder_cfgs()
        f1, b1, cl1, dc1, dm1 = _train_costs(c1)
        f2, b2, cl2, dc2, dm2 = _train_costs(c2)
        ladder = {
            "flops": f1 + (units - 1) * (f2 - f1),
            "bytes": b1 + (units - 1) * (b2 - b1),
            "coll": {k: cl1[k] + (units - 1) * (cl2[k] - cl1[k])
                     for k in cl1},
            "dci": {k: dc1[k] + (units - 1) * (dc2[k] - dc1[k])
                    for k in dc1},
            "dci_msgs": dm1 + (units - 1) * (dm2 - dm1),
            "units": units,
        }
    elif spec.kind == "prefill":
        bsds = batch_sds(S, False)
        fn = jax.jit(
            lambda p, i: serving.prefill(model, p, i, max_len=S),
            in_shardings=(pshard, batch_shardings(bsds)),
        )
        lowered = fn.lower(params_sds, bsds)
        tokens = B * S
    cache_bytes_dev = 0.0
    if spec.kind == "decode":
        prompt = batch_sds(8, False)
        cache_sds = jax.eval_shape(
            lambda p, i: serving.prefill(model, p, i, max_len=S)[1],
            params_sds, prompt,
        )
        cache_shardings = jax.tree.map(cache_sharding_rule, cache_sds)
        isds = batch_sds(1, False)
        fn = jax.jit(
            lambda p, i, c, n: serving.decode_step(model, p, i, c, n),
            in_shardings=(pshard, batch_shardings(isds), cache_shardings,
                          None),
        )
        lowered = fn.lower(params_sds, isds, cache_sds,
                           sds((), jnp.int32))
        tokens = B  # one new token per sequence
        # exact per-device cache bytes under the chosen shardings
        for leaf, shd in zip(jax.tree.leaves(cache_sds),
                             jax.tree.leaves(cache_shardings)):
            import math as _m
            total = _m.prod(leaf.shape) * leaf.dtype.itemsize
            spec_ = shd.spec
            shards = 1
            for e in spec_:
                if e is None:
                    continue
                for ax in (e if isinstance(e, tuple) else (e,)):
                    shards *= axes[ax]
            cache_bytes_dev += total / shards

    t_lower = time.time()
    compiled = lowered.compile()
    t_compile = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    dci = dci_bytes_from_hlo(hlo) if mesh_kind == "multi" else None
    dci_msgs = (dci_message_count_from_hlo(hlo) if mesh_kind == "multi"
                else None)

    # per-device quantities (the compiled module is the SPMD program)
    if ladder is not None:  # train: ladder-extrapolated exact per-layer costs
        flops = ladder["flops"]
        hbm_bytes = ladder["bytes"]
        coll = {k: float(v) for k, v in ladder["coll"].items()}
        if mesh_kind == "multi":
            dci = {k: float(v) for k, v in ladder["dci"].items()}
            dci_msgs = float(ladder["dci_msgs"])
    else:
        flops = float(cost.get("flops", 0.0))
        hbm_bytes = float(cost.get("bytes accessed", 0.0))
    coll_total = float(sum(coll.values()))
    # attention runs as a chunked scan (flash dataflow): XLA counts its body
    # once, so add the analytic attention FLOPs (x3 for fwd+bwd in training)
    if spec.kind == "train":
        attn_fl = 3.0 * analytic_attention_flops(cfg, B, S, S)
        # the chunked xent counts the lm_head projection once per scan:
        # add the missing (nb-1)/nb of 3*2*T*d*V analytically
        nb = S // 512 if S % 512 == 0 and S > 512 else 1
        attn_fl += 6.0 * B * S * cfg.d_model * cfg.vocab * (nb - 1) / nb
    elif spec.kind == "prefill":
        attn_fl = analytic_attention_flops(cfg, B, S, S)
    else:
        attn_fl = analytic_attention_flops(cfg, B, 1, S, decode=True)
    flops_corr = flops + attn_fl / chips
    terms = roofline_terms(flops_corr, hbm_bytes, coll_total, chips)
    mfl = model_flops(cfg, spec.kind, tokens)  # global
    mfl_dev = mfl / chips

    def mem_attr(name):
        return int(getattr(mem, name, 0) or 0)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "moe_mode": moe_mode,
        "fsdp": fsdp,
        "seq_shard": seq_shard,
        "cache_shard": cache_shard,
        "status": "ok",
        "chips": chips,
        "tokens_per_step": tokens,
        "cost_method": ("scan+ladder-extrapolation" if ladder is not None
                        else "full-unrolled"),
        "ladder_units": (ladder or {}).get("units"),
        "lower_s": round(t_lower - t_start, 1),
        "compile_s": round(t_compile - t_lower, 1),
        "memory": {
            "argument_bytes": mem_attr("argument_size_in_bytes"),
            "output_bytes": mem_attr("output_size_in_bytes"),
            "temp_bytes": mem_attr("temp_size_in_bytes"),
            "peak_bytes": (
                mem_attr("argument_size_in_bytes")
                + mem_attr("temp_size_in_bytes")
            ),
        },
        "memory_analytic": analytic_memory_estimate(
            cfg, spec.kind, B, S, axes, fsdp, cache_bytes_dev,
            seq_shard=seq_shard),
        "hlo_flops_per_device": flops,
        "attn_flops_analytic_per_device": attn_fl / chips,
        "flops_per_device_corrected": flops_corr,
        "hlo_bytes_per_device": hbm_bytes,
        "collective_bytes_per_device": coll,
        "ici_dci_bytes_per_device": dci,
        "dci_msgs_per_device": dci_msgs,
        "collective_bytes_total_per_device": coll_total,
        "model_flops_global": mfl,
        "model_flops_per_device": mfl_dev,
        "useful_flops_ratio": (mfl_dev / flops_corr) if flops_corr else 0.0,
        **terms,
    }
    return result


def write_cell(result: dict, path: str):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--moe-mode", default="hier",
                    choices=["dense", "a2a", "hier", "hier_dedup"])
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--cache-shard", default="auto",
                    choices=["auto", "dh", "seq"])
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=3000)
    ap.add_argument("--out-dir", default=RESULTS_DIR,
                    help="directory the cell JSON is written to")
    args = ap.parse_args()

    if args.all:
        from ..configs.shapes import SHAPES
        # cheapest-to-compile first so an interrupted sweep still covers
        # the most cells; single-pod first (it feeds the roofline table)
        order = ["qwen1.5-0.5b", "qwen2-0.5b", "gemma3-1b",
                 "seamless-m4t-medium", "mamba2-780m", "qwen2-vl-2b",
                 "deepseek-v2-lite-16b", "mixtral-8x7b", "nemotron-4-15b",
                 "zamba2-7b"]
        todo = [
            (a, s, m)
            for m in ("single", "multi") for a in order for s in SHAPES
        ]
        failures = []
        for a, s, m in todo:
            path = cell_path(a, s, m, args.moe_mode, out_dir=args.out_dir)
            if os.path.exists(path) and not args.force:
                try:
                    with open(path) as f:
                        prev = json.load(f)
                except Exception:
                    prev = {}
                if prev.get("status") in ("ok", "skipped"):
                    print(f"[cached] {a} {s} {m}")
                    continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--mesh", m,
                   "--moe-mode", args.moe_mode, "--out-dir", args.out_dir]
            if args.fsdp:
                cmd.append("--fsdp")
            if args.no_remat:
                cmd.append("--no-remat")
            print(f"[run] {a} {s} {m} ...", flush=True)
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout)
            dt = time.time() - t0
            if r.returncode != 0:
                failures.append((a, s, m))
                write_cell({"arch": a, "shape": s, "mesh": m,
                            "status": "error",
                            "error": r.stderr[-3000:]}, path)
                print(f"  FAILED in {dt:.0f}s")
            else:
                print(f"  ok in {dt:.0f}s")
        print(f"done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    result = run_cell(args.arch, args.shape, args.mesh, args.moe_mode,
                      fsdp=args.fsdp, remat=not args.no_remat,
                      cache_shard=args.cache_shard,
                      seq_shard=args.seq_shard)
    variant = "" if args.cache_shard == "auto" else f"cache{args.cache_shard}"
    if args.seq_shard:
        variant = (variant + "_" if variant else "") + "seqshard"
    path = cell_path(args.arch, args.shape, args.mesh, args.moe_mode,
                     fsdp=args.fsdp, remat=not args.no_remat,
                     variant=variant, out_dir=args.out_dir)
    write_cell(result, path)
    print(json.dumps(result, indent=1))
    if result["status"] == "error":
        sys.exit(1)


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        sys.exit(1)
