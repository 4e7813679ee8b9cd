"""End-to-end training driver: train a DiT for a few hundred steps on the
synthetic latent pipeline with checkpointing, then sample from it.

    PYTHONPATH=src:. python examples/train_dit.py --steps 300 \
        --ckpt /tmp/dit.ckpt [--arch dit-xl-256]
"""
import sys
sys.path[:0] = ["src", "."]

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro import checkpoint, compile_cache, configs
from repro.core import diffusion, solvers
from repro.core.executor import SmoothCacheExecutor
from repro.data import BlobLatents, CondLatents


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dit-xl-256")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--ckpt", default="/tmp/repro_dit.ckpt")
    args = ap.parse_args()

    cfg = configs.get(args.arch, "smoke")
    kind = "rf" if args.arch.startswith("opensora") else "eps"
    if cfg.num_classes:
        data = BlobLatents(cfg.latent_shape, cfg.num_classes, args.batch)
    else:
        data = CondLatents(cfg.latent_shape, cfg.input_memory_dim, 8,
                           args.batch)
    print(f"[train_dit] {cfg.name}: {cfg.num_layers} blocks, "
          f"latents {cfg.latent_shape}, {args.steps} steps")
    params, sched, losses = common.train_small_dit(
        cfg, jax.random.PRNGKey(0), steps=args.steps, batch=args.batch,
        lr=args.lr, data=data, loss_kind=kind)
    print(f"[train_dit] loss: {losses[0]:.4f} → "
          f"{np.mean(losses[-20:]):.4f} (last-20 mean)")
    checkpoint.save(args.ckpt, {"params": params},
                    {"arch": args.arch, "steps": args.steps, "kind": kind})
    print(f"[train_dit] saved {args.ckpt}")

    # sample from the trained model to prove the checkpoint round-trips
    tree, meta = checkpoint.restore(args.ckpt)
    solver = (solvers.rectified_flow(30) if kind == "rf" else solvers.ddim(50))
    ex = SmoothCacheExecutor(cfg, solver,
                             cfg_scale=1.5 if cfg.num_classes else None)
    cond = {}
    if cfg.num_classes:
        cond["label"] = jnp.arange(4) % cfg.num_classes
    else:
        cond["memory"] = data.batch_at(0)[1][:4]
    x = ex.sample(tree["params"], jax.random.PRNGKey(1), 4, **cond)
    print(f"[train_dit] sampled {x.shape}, finite={bool(jnp.all(jnp.isfinite(x)))}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
