"""Smoke run of the serving path on one TPU chip at published widths.

One process, through the library's normal entry points:

1. picks the TPU (exits non-zero, with no CPU fallback, when JAX finds
   none) and turns the persistent compilation cache on;
2. checks the Pallas flash-attention kernel, compiled natively, against
   ``kernels.ref.flash_attention_ref`` at the DiT-XL attention shape;
3. builds ``dit-xl-256`` at its published widths (28 blocks, d=1152, 16
   heads of 72, 256 tokens) with seeded random float32 weights, and gives
   the adaLN-zero modulation and output leaves small seeded values (at
   their zero init every gate is 0 and the model outputs exactly 0);
4. calibrates ``smoothcache:alpha=0.18`` and an adaptive policy over it on
   10 samples of DDIM-50 with CFG 1.5 (paper §3.1), choosing τ from the
   calibrated curves, and saves both artifacts under
   ``results/chip_smoke/``;
5. drains a few requests per policy (no_cache, static SmoothCache, fused
   adaptive) through ``serve.ServeEngine`` on the wall clock, then checks
   that every latent is finite, that one served batch per policy equals a
   direct ``DiffusionPipeline.generate`` replay bitwise, that the fused
   path made no host syncs, and that the programs stay within the budget.

Times printed are those of one smoke run, not a benchmark.  The last line
of standard output is ``{"ok": true, "device": {...}}`` — printed only
when every check passed.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
ARCH = "dit-xl-256"
STEPS = 50
CFG_SCALE = 1.5
ALPHA = 0.18
CALIB_SAMPLES = 10
#: one full bucket per scenario: a single program per signature
MAX_BATCH = 4
INIT_STD = 0.02
#: ``tests/test_kernels.py``'s bf16 tolerance (atol = rtol), for float32
#: inputs too: at default precision Mosaic runs float32 matmuls on the
#: MXU as bf16 passes, as XLA does for the rest of the model (max |err|
#: 4.4e-3 against a ``highest``-precision reference on a v5e), so the
#: interpret-mode float32 tolerance of 5e-5 does not apply on the chip
FLASH_TOL = 5e-2


def tpu_device():
    """The first device, refusing anything that is not a TPU."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    return dev, len(devs)


class Checks:
    """Records each named check; any failure makes the run exit non-zero."""

    def __init__(self):
        self.failures = []

    def __call__(self, ok: bool, what: str) -> bool:
        print(f"[check] {'PASS' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failures.append(what)
        return ok

    def phase(self, name, fn, *args):
        """Run one phase; an exception fails the run (after the traceback)
        but lets the independent phases after it still report."""
        try:
            return fn(*args)
        except Exception:
            traceback.print_exc()
            self(False, f"phase {name} raised")
            return None


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def check_flash_kernel(check: Checks):
    """Native Mosaic flash attention vs the float32 reference (run at
    ``highest`` matmul precision) at DiT-XL's CFG-doubled attention shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    shape = (2 * MAX_BATCH, 256, 16, 72)
    for dtype in (jnp.float32, jnp.bfloat16):
        ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
        q, k, v = (jax.random.normal(kk, shape, dtype) for kk in ks)
        compiled = ops.flash_attention.lower(q, k, v, causal=False).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"flash {dtype.__name__}: native Mosaic kernel in the HLO")
        out = np.asarray(compiled(q, k, v), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.flash_attention_ref(q, k, v, causal=False),
                              np.float32)
        tol = FLASH_TOL
        err = float(np.max(np.abs(out - want)))
        check(bool(np.all(np.abs(out - want) <= tol + tol * np.abs(want))),
              f"flash {dtype.__name__} {shape}: max |err| {err:.3g} within "
              f"atol=rtol={tol:g}")


def seeded_adaln(params, key, std: float = INIT_STD):
    """Replace the adaLN-zero leaves (every block's ``mod``, the final
    ``final_mod`` and the ``out`` projection) with seeded N(0, std²)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(leaves):
        names = {getattr(p, "key", None) for p in path}
        if names & {"mod", "final_mod", "out"}:
            a = std * jax.random.normal(jax.random.fold_in(key, i), a.shape,
                                        a.dtype)
        out.append(a)
    return jax.tree_util.tree_unflatten(treedef, out)


def build_model(cfg):
    import jax
    from repro.core import diffusion
    key = jax.random.PRNGKey(SEED)
    params = diffusion.init_params(key, cfg)
    return seeded_adaln(params, jax.random.fold_in(key, 1))


def calibrate(cfg, params, outdir):
    """Calibrate the static and the adaptive policy; returns
    ``{name: (spec, artifact path)}``.  τ is the median calibrated lag-1
    error, so the runtime rule reuses on some steps and not on others."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import cache
    from repro.core import solvers

    labels = jnp.arange(CALIB_SAMPLES, dtype=jnp.int32) % cfg.num_classes
    static_spec = f"smoothcache:alpha={ALPHA:g}"
    out = {}

    def run(name, spec):
        t0 = time.perf_counter()
        pipe = cache.DiffusionPipeline(cfg, solvers.ddim(STEPS), spec,
                                       cfg_scale=CFG_SCALE)
        art = pipe.calibrate(params, jax.random.PRNGKey(SEED + 1),
                             CALIB_SAMPLES, cond_args={"label": labels})
        path = pipe.save_artifact(os.path.join(outdir,
                                               f"{name}.cache.json"))
        print(f"[calibrate] {name}: {spec} on {CALIB_SAMPLES} samples, "
              f"{time.perf_counter() - t0:.2f} s (compile included), "
              f"compute fraction {pipe.compute_fraction():.3f} -> {path}",
              flush=True)
        out[name] = (spec, path)
        return art

    art = run("static", static_spec)
    lag1 = np.concatenate([c[:, 1] for c in art.curves.values()])
    tau = float(np.median(lag1[np.isfinite(lag1)]))
    run("adaptive", f"adaptive:base=smoothcache(alpha={ALPHA:g}),"
                    f"tau={tau:.6g}")
    return out


def serve_scenario(check: Checks, dev, name, cfg, params, store, replay):
    """Warm-up drain (compiles) then a timed drain of the same trace on a
    fresh executor; checks the served latents and the compile budget."""
    import jax.numpy as jnp
    import numpy as np
    from repro import serve
    from repro.core import solvers
    from repro.core.executor import SmoothCacheExecutor

    ex = SmoothCacheExecutor(cfg, solvers.ddim(STEPS), cfg_scale=CFG_SCALE)
    rng = np.random.RandomState(SEED)
    seeds = [int(s) for s in rng.randint(1 << 30, size=MAX_BATCH)]
    labels = [int(c) for c in rng.randint(cfg.num_classes, size=MAX_BATCH)]

    def drain():
        eng = serve.ServeEngine(ex, params, store, max_batch=MAX_BATCH,
                                max_inflight=1)
        now = eng.clock.now()
        eng.submit(*[serve.Request(rid=i, seed=s, policy=name, label=c,
                                   arrival=now)
                     for i, (s, c) in enumerate(zip(seeds, labels))])
        t0 = time.perf_counter()
        res = eng.run_until_drained()
        return eng, res, time.perf_counter() - t0

    _, _, setup_s = drain()
    kinds = serve.ServeEngine.MODEL_PROGRAM_KINDS
    before = sum(ex.xla_program_count(k) for k in kinds)
    eng, res, drain_s = drain()
    rep = eng.report()
    programs = rep["compiles"]["xla_programs"]
    print(f"[serve] {name}: set-up (first drain, compiles) {setup_s:.2f} s | "
          f"drain {drain_s:.2f} s for {len(res)} requests | programs "
          f"{programs} (budget {rep['program_budget']}) | compute fraction "
          f"{rep['compute_fraction']:.3f} | peak device bytes so far "
          f"{peak_bytes(dev)}", flush=True)

    check(sorted(res) == list(range(MAX_BATCH)),
          f"{name}: every request served")
    shape = tuple(cfg.latent_shape)
    check(all(x.shape == shape and np.isfinite(x).all()
              for x in res.values()),
          f"{name}: every served latent is finite, of shape {shape}")
    check(programs <= rep["program_budget"],
          f"{name}: {programs} programs <= budget {rep['program_budget']}")
    check(programs == before, f"{name}: no compile inside the timed drain")
    if name == "static":
        check(rep["compute_fraction"] < 1.0,
              f"{name}: the schedule skips branch evaluations")
    rec = eng.records[0]
    lab = jnp.asarray(rec.labels, jnp.int32)
    key = serve.batch_key(rec.seeds)
    if rec.decisions is not None:
        x, dec = replay.generate(params, key, rec.bucket, label=lab,
                                 return_decisions=True)
        check(dec == rec.decisions, f"{name}: replay decisions match")
        skipped = sum(len(d) for d in rec.decisions)
        check(skipped > 0, f"{name}: the runtime rule skipped {skipped} "
                           f"branch evaluations")
        check(ex.compiled_variant_count("fused") >= 1,
              f"{name}: served on the fused adaptive path")
        check(ex.host_sync_count == 0,
              f"{name}: host_sync_count {ex.host_sync_count} == 0")
    else:
        x = replay.generate(params, key, rec.bucket, label=lab)
    x = np.asarray(x)
    same = all(np.array_equal(x[j], res[rid])
               for j, rid in enumerate(rec.rids))
    diff = max(float(np.max(np.abs(x[j] - res[rid])))
               for j, rid in enumerate(rec.rids))
    check(same, f"{name}: served batch {list(rec.rids)} == generate replay "
                f"bitwise (max |diff| {diff:.3g})")
    return res


def smoke(check: Checks, dev, cfg, outdir):
    """Model, calibration and the three serving scenarios."""
    import jax
    import numpy as np
    from repro import cache, serve
    from repro.core import solvers

    t0 = time.perf_counter()
    params = jax.block_until_ready(build_model(cfg))
    n_params = sum(a.size for a in jax.tree.leaves(params))
    print(f"[model] {cfg.name}: d_model={cfg.d_model}, "
          f"{sum(st.repeat for st in cfg.stages)} blocks, {n_params} "
          f"float32 parameters, built in {time.perf_counter() - t0:.2f} s",
          flush=True)
    arts = check.phase("calibrate", calibrate, cfg, params, outdir)
    if arts is None:
        return

    solver = solvers.ddim(STEPS)
    store = serve.ArtifactStore(cfg, solver, cfg_scale=CFG_SCALE)
    store.add_policy("no_cache", "none")
    replays = {"no_cache": cache.DiffusionPipeline(
        cfg, solver, "none", cfg_scale=CFG_SCALE)}
    for name, (spec, path) in arts.items():
        store.add_artifact(name, path)
        replays[name] = cache.DiffusionPipeline(cfg, solver, spec,
                                                cfg_scale=CFG_SCALE)
        replays[name].load_artifact(path)
    served = {name: check.phase(f"serve {name}", serve_scenario, check,
                                dev, name, cfg, params, store, replays[name])
              for name in ("no_cache", "static", "adaptive")}
    ref = served["no_cache"]
    for name in ("static", "adaptive"):
        if ref is None or served[name] is None:
            continue
        # same seeds and labels in every scenario: caching only
        # approximates the uncached latents (bound of
        # tests/test_smoothcache.py::test_cached_sampling_close_but_cheaper)
        rel = max(float(np.linalg.norm(served[name][r] - ref[r])
                        / (np.linalg.norm(ref[r]) + 1e-9)) for r in ref)
        check(rel < 0.5, f"{name}: latents within relative L2 {rel:.3g} "
                         f"of no_cache (< 0.5)")


def main() -> int:
    dev, count = tpu_device()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro import compile_cache, configs

    cache_dir = compile_cache.enable()
    events = {"hits": 0, "misses": 0}

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    print(f"[smoke] one smoke run, not a benchmark; compile cache at "
          f"{cache_dir}", flush=True)

    t0 = time.perf_counter()
    check = Checks()
    check.phase("flash kernel", check_flash_kernel, check)
    outdir = os.path.join(ROOT, "results", "chip_smoke")
    check.phase("smoke", smoke, check, dev, configs.get(ARCH, "full"),
                outdir)
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"[smoke] total {time.perf_counter() - t0:.2f} s | persistent "
          f"cache hits {events['hits']} misses {events['misses']}, "
          f"{entries} entries in {cache_dir} | peak device bytes "
          f"{peak_bytes(dev)}", flush=True)
    if check.failures:
        print("[smoke] FAILED: " + "; ".join(check.failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
