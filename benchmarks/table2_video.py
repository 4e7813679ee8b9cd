"""Paper Table 2 — OpenSora, Rectified Flow 30 steps.

TMACs ratios on the full OpenSora-like STDiT config (paper: α=0.02 →
1388.5/1612.1 = 0.861; α=0.03 → 1321.1/1612.1 = 0.819) plus measured
speedup / PSNR-vs-no-cache proxies (the paper's LPIPS/PSNR/SSIM are
computed relative to non-cached videos) on a small trained model.  Caching
is driven by `repro.cache` policies resolved against one calibration
artifact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro import cache, configs
from repro.core import solvers
from repro.data import CondLatents
from repro.utils import flops

PAPER = [("a0.02", 0.861), ("a0.03", 0.819)]


def run():
    full = configs.get("opensora-v12")
    t_, s_ = 16, (32 // full.patch) ** 2
    steps = 30

    cfg = configs.get("opensora-v12", "smoke")
    key = jax.random.PRNGKey(0)
    data = CondLatents(cfg.latent_shape, cfg.input_memory_dim, 8, 8)
    params, _, losses = common.train_small_dit(cfg, key, steps=100,
                                               data=data, loss_kind="rf")
    pipe = cache.DiffusionPipeline(cfg, solvers.rectified_flow(steps),
                                   "smoothcache:alpha=0.1,k_max=5")
    x0, memory = data.batch_at(0)
    artifact = pipe.calibrate(params, jax.random.PRNGKey(1), 8,
                              cond_args={"memory": memory})
    assert set(artifact.curves) == {"s_attn", "s_xattn", "s_ffn",
                                    "t_attn", "t_xattn", "t_ffn"}, \
        sorted(artifact.curves)

    ntok = t_ * s_
    base = flops.sampler_tmacs(full, pipe.schedule_for("none"), ntok, 1,
                               video_shape=(t_, s_))
    common.emit("table2/no_cache/tmacs", 0.0,
                f"tmacs={base:.1f};paper=1612.1_unit_note")
    for name, paper_ratio in PAPER:
        sch = pipe.schedule_for(f"budget:target={paper_ratio},k_max=5")
        t = flops.sampler_tmacs(full, sch, ntok, 1, video_shape=(t_, s_))
        common.emit(f"table2/smoothcache_{name}/tmacs", 0.0,
                    f"tmacs={t:.1f};ratio={t/base:.3f};paper_ratio={paper_ratio:.3f}")

    # e2e on the small model: PSNR relative to non-cached output
    def sample_with(schedule):
        return pipe.generate(params, jax.random.PRNGKey(2), 4,
                             schedule=schedule, memory=memory[:4])

    ref = sample_with(None)
    t_base = common.time_call(lambda: sample_with(None), iters=2)
    common.emit("table2/no_cache/e2e", t_base, "psnr=inf")
    for alpha in (0.1, 0.3):
        sch = pipe.schedule_for(f"smoothcache:alpha={alpha},k_max=5")
        x = sample_with(sch)
        t = common.time_call(lambda: sample_with(sch), iters=2)
        mse = float(jnp.mean((x - ref) ** 2))
        rng = float(jnp.max(ref) - jnp.min(ref))
        psnr = 10 * np.log10(rng ** 2 / max(mse, 1e-12))
        frac = np.mean([sch.compute_fraction(ty) for ty in sch.skip])
        common.emit(f"table2/smoothcache_a{alpha}/e2e", t,
                    f"psnr={psnr:.1f};speedup={t_base/t:.2f};compute_frac={frac:.3f}")


if __name__ == "__main__":
    run()
