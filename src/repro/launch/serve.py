"""Serving launcher: batched prefill + greedy decode loop.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
        --batch 4 --prompt-len 16 --new-tokens 24

Elastic demo (``--elastic``): drives a ``ServeEngine(elastic=True)``
through a mid-decode shrink to ``--shrink-to`` devices at step
``--shrink-at`` and a grow-back, printing each ``ResizeEvent`` with its
plan-cache delta (the grow-back is warm — see docs/OPERATIONS.md):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
        --reduced --elastic --batch 2 --new-tokens 12 --shrink-at 4
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from .compile_cache import configure_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0)
    ap.add_argument("--moe-mode", default="auto",
                help="MoE dispatch: auto (Section-5 selection) | a2a | hier | hier_dedup | dense")
    ap.add_argument("--elastic", action="store_true",
                    help="drive ServeEngine(elastic=True) through a "
                    "mid-decode shrink/grow (see module docstring)")
    ap.add_argument("--shrink-at", type=int, default=4,
                    help="engine step at which half the devices 'time out'")
    ap.add_argument("--shrink-to", type=int, default=0,
                    help="surviving device count (default: half)")
    args = ap.parse_args()
    configure_compile_cache()

    if args.elastic:
        return _main_elastic(args)

    from .. import configs
    from ..models import Model, serving

    cfg = configs.reduced(args.arch) if args.reduced else configs.get(args.arch)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    model = Model(cfg, moe_mode=args.moe_mode, remat=False)
    params = model.init_params(seed=0)
    max_len = args.max_len or (args.prompt_len + args.new_tokens + 8)

    rng = np.random.default_rng(0)
    B, T = args.batch, args.prompt_len
    inputs = {}
    if cfg.family == "audio":
        inputs["enc_embeds"] = jnp.asarray(
            rng.normal(size=(B, T, cfg.d_model)).astype(np.float32))
        inputs["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab, size=(B, T)).astype(np.int32))
    elif cfg.family == "vlm":
        inputs["embeds"] = jnp.asarray(
            rng.normal(size=(B, T, cfg.d_model)).astype(np.float32))
        pos = np.broadcast_to(np.arange(T, dtype=np.int32), (B, T))
        inputs["positions"] = jnp.asarray(
            np.broadcast_to(pos[:, None, :], (B, 3, T)).copy())
    else:
        inputs["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab, size=(B, T)).astype(np.int32))

    t0 = time.time()
    prefill_fn = jax.jit(
        lambda p, i: serving.prefill(model, p, i, max_len=max_len))
    logits, caches = prefill_fn(params, inputs)
    logits.block_until_ready()
    print(f"[serve] prefill {B}x{T} in {time.time() - t0:.2f}s "
          f"({B * T / (time.time() - t0):,.0f} tok/s)")

    decode_fn = jax.jit(
        lambda p, i, c, n: serving.decode_step(model, p, i, c, n))
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    out_tokens = [np.asarray(tok)]
    t0 = time.time()
    for step in range(args.new_tokens):
        if cfg.family == "vlm":
            emb = params["embed"][tok[:, 0]][:, None]
            step_in = {"embeds": emb}
        else:
            step_in = {"tokens": tok}
        logits, caches = decode_fn(params, step_in, caches,
                                   jnp.asarray(T + step, jnp.int32))
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(np.asarray(tok))
    jax.block_until_ready(logits)
    dt = time.time() - t0
    print(f"[serve] decoded {args.new_tokens} tokens x {B} seqs in "
          f"{dt:.2f}s ({B * args.new_tokens / dt:,.1f} tok/s)")
    gen = np.concatenate(out_tokens, axis=1)
    print(f"[serve] sample row 0: {gen[0][:24].tolist()}")


def _main_elastic(args):
    """Mid-decode shrink/grow through ``ServeEngine(elastic=True)``."""
    from .. import configs
    from ..models import Model
    from ..serve import Request, ServeEngine

    cfg = configs.reduced(args.arch) if args.reduced \
        else configs.get(args.arch)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    n_dev = jax.device_count()
    shrink_to = args.shrink_to or max(1, n_dev // 2)
    max_len = args.max_len or (args.prompt_len + args.new_tokens + 8)

    mesh = jax.make_mesh((1, n_dev), ("data", "model"))
    model = Model(cfg, mesh=mesh, moe_mode=args.moe_mode, remat=False)
    params = model.init_params(seed=0)
    eng = ServeEngine(model, params, batch_slots=args.batch,
                      max_len=max_len, elastic=True)
    print(f"[serve/elastic] engine up on {n_dev} devices "
          f"(mesh {dict(zip(mesh.axis_names, mesh.devices.shape))})")

    rng = np.random.default_rng(0)
    for rid in range(args.batch):
        prompt = rng.integers(0, cfg.vocab,
                              size=(args.prompt_len,)).astype(np.int32)
        eng.submit(Request(rid=rid, prompt=prompt,
                           max_new_tokens=args.new_tokens))

    t0 = time.time()
    done = []
    for step in range(args.new_tokens + 1):
        if step == args.shrink_at:
            print(f"[serve/elastic] step {step}: {n_dev - shrink_to} "
                  f"devices time out -> shrink to {shrink_to}")
            ev = eng.resize(shrink_to, reason="heartbeat")
            print(f"[serve/elastic]   {ev}")
        done.extend(eng.step())
    print(f"[serve/elastic] decoded {args.new_tokens} tokens x "
          f"{args.batch} seqs in {time.time() - t0:.2f}s "
          f"(shrink at step {args.shrink_at})")

    ev = eng.resize(n_dev, reason="requested")
    print(f"[serve/elastic] devices return -> grow back: {ev}")
    print(f"[serve/elastic]   warm resize: {ev.warm} "
          f"(plans for the seen geometry survived in the cache)")
    done.extend(eng.run_until_drained())
    for req in done:
        print(f"[serve/elastic] rid {req.rid} generated: "
              f"{req.generated[:16]}")
    print(f"[serve/elastic] drained {len(done)} request(s); "
          f"resize events: {len(eng.resize_events)}")


if __name__ == "__main__":
    main()
