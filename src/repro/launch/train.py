"""Training launcher: config -> mesh -> data -> jitted step -> checkpoints.

Single-host it runs real steps on the local devices; the same entry point
is what each host of a multi-pod fleet would execute (jax.distributed
initialization is the only per-deployment addition).  Includes heartbeat
bookkeeping, straggler detection, elastic restart from the latest
checkpoint, and periodic async checkpointing.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 50 --batch 8 --seq 256 --reduced --ckpt /tmp/ck
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from .compile_cache import configure_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=0,
                    help="override vocab (speeds up CPU demos)")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--moe-mode", default="auto",
                help="MoE dispatch: auto (Section-5 selection) | a2a | hier | hier_dedup | dense")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()
    configure_compile_cache()

    from .. import configs
    from ..models import Model
    from ..runtime import CheckpointManager, StragglerDetector
    from ..train import (
        AdamWConfig, DataConfig, TokenStream, TrainerConfig,
        make_train_state, make_train_step,
    )

    cfg = configs.reduced(args.arch) if args.reduced else configs.get(args.arch)
    if args.vocab:
        import dataclasses
        cfg = dataclasses.replace(cfg, vocab=args.vocab)
    import dataclasses
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)

    model = Model(cfg, moe_mode=args.moe_mode)
    tcfg = TrainerConfig(
        opt=AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 20),
                        total_steps=args.steps),
        microbatches=args.microbatches,
        compress_grads=args.compress_grads,
    )
    data = TokenStream(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch))
    step_fn = jax.jit(make_train_step(model, tcfg))
    state = make_train_state(model, tcfg, seed=0)
    start = 0
    mgr = None
    if args.ckpt:
        mgr = CheckpointManager(args.ckpt, keep=3)
        got = mgr.restore_latest(state)
        if got is not None:
            start, state = got
            state = jax.tree.map(jnp.asarray, state)
            print(f"[train] resumed from step {start}")

    det = StragglerDetector(n_hosts=1)
    n_params = cfg.param_count()
    print(f"[train] arch={cfg.name} params={n_params:,} steps={args.steps}")
    t_last = time.time()
    for i in range(start, args.steps):
        batch = jax.tree.map(jnp.asarray, data.global_batch_at(i))
        state, metrics = step_fn(state, batch)
        if mgr and (i + 1) % args.ckpt_every == 0:
            mgr.save(i + 1, state)
        if (i + 1) % args.log_every == 0 or i == start:
            loss = float(metrics["loss"])
            dt = time.time() - t_last
            t_last = time.time()
            det.update(np.array([dt]))
            tps = args.batch * args.seq * args.log_every / max(dt, 1e-9)
            print(f"[train] step {i + 1:5d} loss={loss:.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['gnorm']):.2f} tok/s={tps:,.0f}")
    if mgr:
        mgr.save(args.steps, state)
        mgr.wait()
    print("[train] done")


if __name__ == "__main__":
    main()
