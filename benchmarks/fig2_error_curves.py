"""Paper Fig. 2 — L1 relative error curves per layer type with 95% CIs
from 10 calibration samples, across the three modality models.

Emits per-type curve summaries + the cross-sample CI width (the paper's
key observation: curves are nearly input-independent, CI ≪ mean).  The
curves come straight out of `DiffusionPipeline.calibrate`'s artifact, and
the full artifact (curves + provenance) is what gets dumped to disk."""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro import cache, configs
from repro.core import solvers
from repro.data import BlobLatents, CondLatents

SETUPS = [
    ("dit-xl-256", "ddim", 50, 1.5, "eps"),
    ("opensora-v12", "rectified_flow", 30, None, "rf"),
    ("stable-audio-open", "dpmpp_3m_sde", 25, 7.0, "eps"),
]


def run():
    os.makedirs(os.path.join(common.RESULTS_DIR, "fig2"), exist_ok=True)
    for arch, solver_name, steps, cfg_scale, kind in SETUPS:
        cfg = configs.get(arch, "smoke")
        key = jax.random.PRNGKey(0)
        if cfg.num_classes:
            data = BlobLatents(cfg.latent_shape, cfg.num_classes, 10)
            params, _, _ = common.train_small_dit(cfg, key, steps=80,
                                                  data=data, loss_kind=kind)
            x0, label = data.batch_at(0)
            cond = {"label": label}
        else:
            data = CondLatents(cfg.latent_shape, cfg.input_memory_dim, 8, 10)
            params, _, _ = common.train_small_dit(cfg, key, steps=80,
                                                  data=data, loss_kind=kind)
            _, memory = data.batch_at(0)
            cond = {"memory": memory}
        solver = solvers.SOLVERS[solver_name](steps)
        pipe = cache.DiffusionPipeline(cfg, solver, "smoothcache:alpha=0.18",
                                       cfg_scale=cfg_scale)
        artifact = pipe.calibrate(params, jax.random.PRNGKey(1), 10,
                                  cond_args=cond)
        artifact.save(os.path.join(common.RESULTS_DIR, "fig2",
                                   f"{arch}.cache.json"))
        per_sample = pipe.per_sample
        dump = {}
        for t, c in artifact.curves.items():
            ps = per_sample[t][:, :, 1]                 # lag-1, (B, S)
            mean = np.nanmean(ps, axis=0)
            ci = 1.96 * np.nanstd(ps, axis=0) / np.sqrt(ps.shape[0])
            rel_ci = float(np.nanmean(ci[1:] / (mean[1:] + 1e-9)))
            common.emit(f"fig2/{arch}/{t}", 0.0,
                        f"mean_err_lag1={np.nanmean(mean[1:]):.4f};"
                        f"rel_ci95={rel_ci:.3f}")
            dump[t] = {"mean": mean.tolist(), "ci95": ci.tolist(),
                       "curves": np.nan_to_num(c).tolist()}
        with open(os.path.join(common.RESULTS_DIR, "fig2",
                               f"{arch}.json"), "w") as f:
            json.dump(dump, f)


if __name__ == "__main__":
    run()
