"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2_1_5b \
        --smoke --steps 50 [--ckpt-dir /tmp/ckpt]

``--smoke`` trains the reduced same-family config (the full configs are
for the pod dry-run / real TPU deployment, where this same launcher runs
under `jax.distributed.initialize()` with the production mesh — see
repro/launch/dryrun.py for the sharding entry points). Compiled programs
go to the persistent cache that ``repro.launch.cache`` sets up.
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp

from repro.configs.base import get_config, list_archs, smoke_config
from repro.launch.cache import setup_compile_cache
from repro.models.layers import ModelOptions
from repro.train.optimizer import AdamWConfig
from repro.train.step import TrainConfig
from repro.train.train_loop import LoopConfig, fit


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_1_5b",
                    choices=list(list_archs()))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    args = ap.parse_args()
    setup_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    print(f"arch={cfg.name} params={cfg.n_params()/1e6:.1f}M "
          f"(active {cfg.n_active_params()/1e6:.1f}M)")

    res = fit(
        cfg,
        opts=ModelOptions(dtype=jnp.float32, remat=False),
        tcfg=TrainConfig(
            adamw=AdamWConfig(lr=args.lr,
                              warmup_steps=max(10, args.steps // 20),
                              total_steps=args.steps),
            accum_steps=args.accum),
        loop=LoopConfig(steps=args.steps, seq_len=args.seq,
                        global_batch=args.batch, log_every=10,
                        save_every=args.save_every if args.ckpt_dir else 0,
                        ckpt_dir=args.ckpt_dir))
    print(f"done: loss {res.losses[0]:.4f} → {res.losses[-1]:.4f} "
          f"({res.steps_done} steps)")


if __name__ == "__main__":
    main()
