"""Quickstart: SmoothCache end to end in ~2 minutes on CPU, via the
`repro.cache` policy API.

1. train a small class-conditional DiT on synthetic latents,
2. build a `DiffusionPipeline` and run one 10-sample calibration pass
   (paper §3.1 uses 10) — this yields a serializable `CacheArtifact`,
3. sweep cache policies by registry spec string (Eq. 4 α-schedules vs
   No-Cache and FORA static intervals),
4. report measured wall-clock speedup + sample-quality proxy.

    PYTHONPATH=src:. python examples/quickstart.py
"""
import sys, os
sys.path[:0] = ["src", "."]

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro import cache, compile_cache, configs
from repro.core import solvers
from repro.data import BlobLatents


def main():
    cfg = configs.get("dit-xl-256", "smoke")
    print(f"model: {cfg.name} ({cfg.num_layers} blocks, d={cfg.d_model}, "
          f"latents {cfg.latent_shape}), types={cfg.layer_types()}")

    print("training small DiT on synthetic class-conditional latents ...")
    params, _, losses = common.train_small_dit(
        cfg, jax.random.PRNGKey(0), steps=150)
    print(f"  loss {losses[0]:.3f} -> {losses[-1]:.3f}")

    pipe = cache.DiffusionPipeline(cfg, solvers.ddim(50),
                                   "smoothcache:alpha=0.18", cfg_scale=1.5)
    label = jnp.arange(10) % cfg.num_classes

    print("calibration pass (10 samples, 50 DDIM steps) ...")
    artifact = pipe.calibrate(params, jax.random.PRNGKey(1), 10,
                              cond_args={"label": label})
    for t, c in artifact.curves.items():
        print(f"  {t:5s} lag-1 err: start={c[1,1]:.3f} "
              f"mid={c[25,1]:.3f} end={c[-1,1]:.3f}")

    data = BlobLatents(cfg.latent_shape, cfg.num_classes, 32, seed=7)
    ref_x0, ref_label = data.batch_at(0)

    def sample(sch):
        return pipe.generate(params, jax.random.PRNGKey(3), 32,
                             schedule=sch, label=ref_label)

    base = sample(None)
    t_base = common.time_call(lambda: sample(None), iters=2)
    fd_base = common.frechet_distance(np.asarray(base), np.asarray(ref_x0))
    print(f"\n{'policy':24s} {'ms/batch':>9s} {'speedup':>8s} "
          f"{'frechet':>9s} {'compute%':>9s}")
    print(f"{'no_cache':24s} {t_base/1e3:9.0f} {1.0:8.2f}x {fd_base:9.4f} "
          f"{100.0:8.0f}%")
    for spec in ("smoothcache:alpha=0.08", "smoothcache:alpha=0.18",
                 "static:n=2", "static:n=3"):
        sch = pipe.schedule_for(spec)     # resolved against the one artifact
        x = sample(sch)
        t = common.time_call(lambda: sample(sch), iters=2)
        fd = common.frechet_distance(np.asarray(x), np.asarray(ref_x0))
        frac = 100 * np.mean([sch.compute_fraction(ty) for ty in sch.skip])
        print(f"{spec:24s} {t/1e3:9.0f} {t_base/t:8.2f}x {fd:9.4f} "
              f"{frac:8.0f}%")


if __name__ == "__main__":
    compile_cache.enable()
    main()
