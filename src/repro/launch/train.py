"""Training launcher: data pipeline -> sharded train_step -> checkpointed,
fault-tolerant loop.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --preset smoke --steps 50 --batch 8 --seq 256

Presets:
  smoke  — reduced same-family config (CPU-friendly)
  100m   — ~100M-param dense config (deliverable b's end-to-end driver)
  full   — the assigned config (use on real hardware)

On a single CPU host this runs on a 1x1 mesh; on a pod the same script uses
``make_production_mesh()`` (the sharding rules are mesh-shape agnostic).
Fault tolerance: periodic async checkpoints + restore-from-LATEST on
restart (--resume) — the ResilientLoop path is exercised in tests with
injected failures.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpointing import store
from repro.configs import ARCHS, smoke_config
from repro.configs.base import ArchConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import sharding as sh
from repro.launch.mesh import make_smoke_mesh
from repro.launch.steps import build_train_step
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.optim import init_state


def preset_config(arch_id: str, preset: str) -> ArchConfig:
    cfg = ARCHS[arch_id]
    if preset == "full":
        return cfg
    if preset == "smoke":
        return smoke_config(cfg)
    if preset == "100m":
        # ~100M params: emb 2*50304*640=64M + 10 layers x ~3.6M
        return dataclasses.replace(
            smoke_config(cfg), n_layers=10, d_model=640, n_heads=10,
            n_kv_heads=min(cfg.n_kv_heads, 10) if cfg.n_kv_heads > 1 else 1,
            d_ff=2048, vocab=50304, head_dim=64, remat="none",
            param_dtype="float32")
    raise ValueError(preset)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=list(ARCHS))
    ap.add_argument("--preset", default="smoke",
                    choices=["smoke", "100m", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--log-file", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = preset_config(args.arch, args.preset)
    mesh = make_smoke_mesh()
    print(f"arch={args.arch} preset={args.preset} "
          f"params={cfg.n_params()/1e6:.1f}M "
          f"devices={len(jax.devices())}", flush=True)

    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch))
    params = registry.init_params(cfg, jax.random.key(0))
    opt = init_state(params, moment_dtype=jnp.dtype(cfg.moment_dtype))

    start_step = 0
    if args.resume and args.ckpt_dir and store.latest_step(args.ckpt_dir):
        (params, opt), start_step = store.restore(
            args.ckpt_dir, (params, opt))
        print(f"resumed from step {start_step}", flush=True)

    pshapes = jax.eval_shape(lambda: params)
    pspecs = sh.param_spec_tree(cfg, mesh, pshapes)
    ospecs = type(opt)(step=jax.sharding.PartitionSpec(), mu=pspecs,
                       nu=pspecs)
    step_fn = build_train_step(cfg, peak_lr=args.lr, warmup=args.warmup,
                               total_steps=max(args.steps, 100))
    with mesh:
        jitted = jax.jit(
            step_fn,
            in_shardings=(sh.named(mesh, pspecs), sh.named(mesh, ospecs),
                          None),
            out_shardings=(sh.named(mesh, pspecs), sh.named(mesh, ospecs),
                           None),
            donate_argnums=(0, 1))

        losses = []
        pending = None
        t_start = time.time()
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
            if cfg.family == "vlm":
                batch["vision_embeds"] = jnp.zeros(
                    (args.batch, cfg.n_patches, cfg.d_model), jnp.float32)
                batch["positions"] = jnp.broadcast_to(
                    jnp.arange(args.seq, dtype=jnp.int32)[None, None],
                    (3, args.batch, args.seq))
            if cfg.family == "encdec":
                batch["frames"] = jnp.zeros(
                    (args.batch, args.seq // cfg.frames_ratio, cfg.d_model),
                    jnp.float32)
            t0 = time.time()
            params, opt, metrics = jitted(params, opt, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.time() - t0
            if step % args.log_every == 0 or step == args.steps - 1:
                tok_s = args.batch * args.seq / dt
                msg = (f"step {step:5d} loss {loss:.4f} "
                       f"gnorm {float(metrics['grad_norm']):.3f} "
                       f"lr {float(metrics['lr']):.2e} "
                       f"{dt:.2f}s/step {tok_s:,.0f} tok/s")
                print(msg, flush=True)
                if args.log_file:
                    with open(args.log_file, "a") as f:
                        f.write(msg + "\n")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                if pending is not None:
                    pending.join()
                pending = store.save(args.ckpt_dir, step + 1, (params, opt),
                                     blocking=False)
        if pending is not None:
            pending.join()

    wall = time.time() - t_start
    first = float(np.mean(losses[:5]))
    last = float(np.mean(losses[-5:]))
    print(f"done: {len(losses)} steps in {wall:.0f}s  "
          f"loss {first:.4f} -> {last:.4f} "
          f"({'DECREASED' if last < first else 'check convergence'})",
          flush=True)
    if args.log_file:
        with open(args.log_file + ".json", "w") as f:
            json.dump({"arch": args.arch, "preset": args.preset,
                       "steps": len(losses), "wall_s": wall,
                       "loss_first5": first, "loss_last5": last,
                       "losses": losses}, f)


if __name__ == "__main__":
    main()
