"""Generate from any assigned architecture (reduced config) — exercises the
prefill + KV/state-cache decode path across all six arch families.

    PYTHONPATH=src:. python examples/lm_generate.py --arch mamba2-1.3b
    PYTHONPATH=src:. python examples/lm_generate.py --arch recurrentgemma-2b
"""
import sys
sys.path[:0] = ["src", "."]

import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache, configs
from repro.data import TokenStream, text_memory, vit_patch_embeds
from repro.launch.serve import generate
from repro.models import transformer as T


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b",
                    choices=list(configs.REGISTRY))
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=12)
    args = ap.parse_args()

    cfg = configs.get(args.arch, "smoke")
    if cfg.task != "lm":
        raise SystemExit(f"{args.arch} is a diffusion model — "
                         "use examples/serve_diffusion.py")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    stream = TokenStream(cfg.vocab_size, args.prompt_len, args.batch,
                         num_codebooks=cfg.num_codebooks)
    prompts, _ = stream.batch_at(0)
    memory = (text_memory(jax.random.PRNGKey(3), args.batch, 8, cfg.cond_dim)
              if cfg.cond_dim else None)
    print(f"[{cfg.name}] families: "
          f"{sorted(set(t for t in cfg.layer_types()))}; prompts {prompts.shape}")
    t0 = time.time()
    toks = generate(cfg, params, prompts, args.gen, memory=memory,
                    key=jax.random.PRNGKey(1))
    print(f"[{cfg.name}] generated {toks.shape} in {time.time()-t0:.1f}s")
    print(f"[{cfg.name}] sample:", jax.device_get(toks[0]).tolist()[:12])


if __name__ == "__main__":
    compile_cache.enable()
    main()
