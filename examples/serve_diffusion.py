"""End-to-end SERVING driver — a thin CLI over ``repro.serve``.

A calibration process runs once and saves `CacheArtifact`s (curves +
resolved schedule + plan + provenance); the serving process *loads* them
into an `ArtifactStore` — it never recalibrates — and drains an open-loop
queue of generation requests with synthetic Poisson arrivals through the
continuous-batching `ServeEngine`: power-of-two micro-batch buckets per
(artifact, signature) group, step-interleaved scheduling over the
executor's resumable segment runs, and the segment-compiled path by
default (``--eager`` falls back to the reference sampler).

Three scenarios share one arrival trace: every request on ``no_cache``,
every request on the calibrated policy, and a heterogeneous queue mixing
both with an adaptive policy.  The report separates p50/p95 queue wait
from service time (arrivals are real timestamps, not one shared t0).

    PYTHONPATH=src:. python examples/serve_diffusion.py --requests 24 \
        --batch 8 --policy "smoothcache:alpha=0.18" --rate 2.0
"""
import sys
sys.path[:0] = ["src", "."]

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro import cache, compile_cache, configs, serve
from repro.core import solvers
from repro.core.executor import SmoothCacheExecutor

CFG_SCALE = 1.5


def build_store(cfg, solver, policy, adaptive_spec, paths):
    """Serving-side store: calibration-free baseline + artifact entries."""
    store = serve.ArtifactStore(cfg, solver, cfg_scale=CFG_SCALE)
    store.add_policy("no_cache", "none")
    store.add_artifact(policy, paths["static"])
    store.add_artifact(adaptive_spec, paths["adaptive"])
    return store


def make_requests(n, policies, rng, cfg, rate):
    """Open-loop trace: Poisson arrivals, random labels/seeds, policies
    assigned round-robin (the heterogeneous case passes several)."""
    arrivals = serve.poisson_arrivals(rate, n, rng)
    return [serve.Request(
        rid=i, seed=int(rng.randint(1 << 30)),
        policy=policies[i % len(policies)],
        label=int(rng.randint(cfg.num_classes)),
        arrival=a) for i, a in enumerate(arrivals)]


def serve_scenario(name, policies, *, executor, params, store, args, cfg):
    """Drain one Poisson trace; returns the engine report."""
    # identical trace across scenarios: reseed the arrival/label RNG
    rng = np.random.RandomState(0)
    eng = serve.ServeEngine(
        executor, params, store, max_batch=args.batch,
        max_wait=args.max_wait, max_inflight=args.max_inflight,
        eager=args.eager)
    t0 = eng.clock.now()
    reqs = make_requests(args.requests, policies, rng, cfg, args.rate)
    for r in reqs:
        r.arrival += t0
    eng.submit(*reqs)
    eng.run_until_drained()
    rep = eng.report()
    qw, sv = rep["queue_wait_s"], rep["service_s"]
    print(f"[serve] {name:16s}: {rep['requests']} req "
          f"{rep['throughput_rps']:6.2f} req/s | "
          f"queue p50/p95 {qw['p50']:.2f}/{qw['p95']:.2f}s | "
          f"service p50/p95 {sv['p50']:.2f}/{sv['p95']:.2f}s | "
          f"compute {rep['compute_fraction']:.2f} | "
          f"programs {rep['compiles']['xla_programs']}"
          f"≤{rep['program_budget']}")
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--batch", type=int, default=8,
                    help="max micro-batch bucket (power of two)")
    ap.add_argument("--policy", default="smoothcache:alpha=0.18",
                    help="calibrated policy spec for the static artifact")
    ap.add_argument("--tau", type=float, default=0.3,
                    help="adaptive threshold for the mixed-queue scenario")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--max-wait", type=float, default=0.5,
                    help="batching window before a partial bucket forms")
    ap.add_argument("--max-inflight", type=int, default=2)
    ap.add_argument("--train-steps", type=int, default=120)
    ap.add_argument("--eager", action="store_true",
                    help="escape hatch: serve on the eager reference "
                         "sampler instead of the segment-compiled path")
    ap.add_argument("--artifact-dir", default="",
                    help="directory for calibration artifacts "
                         "(default: results/)")
    args = ap.parse_args()

    cache.get(args.policy)                 # fail fast on a bad spec
    adaptive_spec = f"adaptive:base={args.policy.replace(':', '(', 1)}" \
                    + (")" if ":" in args.policy else "") \
                    + f",tau={args.tau:g}"
    cfg = configs.get("dit-xl-256", "smoke")
    print("[serve] training small DiT ...")
    params, _, _ = common.train_small_dit(cfg, jax.random.PRNGKey(0),
                                          steps=args.train_steps)

    # --- calibration process: calibrate once, save artifacts ----------------
    outdir = args.artifact_dir or common.RESULTS_DIR
    paths = {}
    for kind, spec in [("static", args.policy), ("adaptive", adaptive_spec)]:
        calib = cache.DiffusionPipeline(cfg, solvers.ddim(args.steps), spec,
                                        cfg_scale=CFG_SCALE)
        calib.calibrate(params, jax.random.PRNGKey(1), 8,
                        cond_args={"label": jnp.arange(8) % cfg.num_classes})
        paths[kind] = calib.save_artifact(
            os.path.join(outdir, f"serve_{cfg.name}.{kind}.cache.json"))
        print(f"[serve] saved {paths[kind]}")

    # --- serving process: load, validate, never recalibrate -----------------
    solver = solvers.ddim(args.steps)
    executor = SmoothCacheExecutor(cfg, solver, cfg_scale=CFG_SCALE)
    store = build_store(cfg, solver, args.policy, adaptive_spec, paths)
    print("[serve] " + store.summary().replace("\n", "\n[serve] "))

    scenarios = [
        ("no_cache", ["no_cache"]),
        (args.policy, [args.policy]),
        ("mixed+adaptive", ["no_cache", args.policy, adaptive_spec]),
    ]
    for name, policies in scenarios:
        serve_scenario(name, policies, executor=executor, params=params,
                       store=store, args=args, cfg=cfg)


if __name__ == "__main__":
    compile_cache.enable()
    main()
