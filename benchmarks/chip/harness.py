"""The chip benchmark's harness: one run of one cell of ``BENCHMARK.json``.

A cell names a configuration (``configs/<config>.json``: the model's
sizes, its sampler, the engine settings, its reference) and a traffic
mix (``traffic/<mix>.json``, read by ``traffic.py``).  Its correctness
limits are in ``checks/<cell>.json`` and each metric is read by
``metrics/<metric>.py``.  So a later cell, mix or metric is new files and
new entries; nothing here names one.

A run:

1. calibrates, when the mix asks for a calibrated policy, in a process
   of its own (``calibrate.py``), so that calibration stays out of the
   serving process's peak memory; the artifact comes back as a file and
   is loaded through ``ArtifactStore.add_artifact``, as a deployment
   loads it;
2. refuses any device that is not a TPU, or fewer chips than the cell
   asks for;
3. makes the weights on the device in one jitted call: the
   configuration's one model with its FFN units and attention heads
   reordered by the seed (``references/<name>.py``, ``make_weights``),
   builds the executor, store and ``ServeEngine`` of the configuration,
   and serves one batch of each bucket shape the traffic forms (warm-up);
4. drives a fresh engine on the wall clock for the window: the loop of
   ``ServeEngine.run_until_drained``, with the traffic's requests
   submitted on their schedule, and profiler annotations around the
   engine's step, the clock's sleeps and the result readback;
5. reads the peak device memory, frees the program's state, and compares
   a sample of the served latents, drawn from the seed, with the plain
   reference run on the same weights, noise and labels, under the skip
   mask the reference derives by its own calibration; and the served
   mask with the policy's rule under the reference's error curves;
6. prints the metrics, the device, and each compared number with its
   limit as the last line of standard output.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import importlib.util
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time
import types
from typing import Dict, List, Optional

import numpy as np

import traffic as traffic_lib

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
#: the one store entry every request of a cell names
POLICY = "cell"
#: the first arrival of the window comes this long after the requests
#: are submitted
LEAD_S = 0.05


class BenchError(RuntimeError):
    """The run cannot measure (wrong device, bad cell); no result."""


# ---------------------------------------------------------------------------
# Cells
# ---------------------------------------------------------------------------

def _json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str):
    """The cell, its configuration, mix, checks and metric entries."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def listed(m):
        return workload in m.get("workloads", [workload])

    return types.SimpleNamespace(
        name=workload, chips=int(cell["chips"]),
        conf=_json(os.path.join(ROOT, conf_entry["file"])),
        mix=_json(os.path.join(BENCH_DIR, "traffic",
                               cell["traffic"] + ".json")),
        checks=_json(os.path.join(BENCH_DIR, "checks", workload + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if listed(m)],
        per_layer=[m for m in bench["per_layer"] if listed(m)])


def reference(cell):
    return _reference(cell.conf["reference"])


@functools.lru_cache(maxsize=None)
def _reference(name: str):
    # one module per reference, so that its jitted functions compile once
    return load_module(os.path.join(BENCH_DIR, "references", name + ".py"))


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------

def tpu_devices(chips: int):
    """The devices, refusing anything but at least ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set), holding every program,
    however quick to compile, so that only a cell's first run compiles."""
    import jax
    from repro import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads while active."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self.count += 1

    def _duration(self, event, duration, **kw):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def program_config(conf):
    """The program's configuration, checked against the file's sizes."""
    from repro import configs
    ov = {k: tuple(v) if isinstance(v, list) else v
          for k, v in conf["program"]["overrides"].items()}
    cfg = configs.get(conf["program"]["base"],
                      conf["program"]["variant"]).replace(**ov)
    m = conf["model"]
    blocks = [b for st in cfg.stages for b in st.unit for _ in
              range(st.repeat)]
    b = blocks[0]
    got = {"depth": len(blocks), "hidden_size": cfg.d_model,
           "num_heads": b.mixer.num_heads, "head_dim": b.mixer.head_dim,
           "mlp_hidden": b.ffn.d_ff, "patch_size": cfg.patch,
           "latent_shape": list(cfg.latent_shape),
           "num_classes": cfg.num_classes}
    bad = {k: (v, m[k]) for k, v in got.items() if v != m[k]}
    if bad:
        raise BenchError(f"program config differs from the file: {bad}")
    return cfg


def make_params(cell, seed: int, ref):
    """The weights from the seed, checked against the program's tree."""
    import jax
    from repro.core import diffusion
    cfg = program_config(cell.conf)
    params = jax.block_until_ready(
        ref.make_weights(cell.conf["model"], seed, cell.conf["dtype"]))
    want = jax.eval_shape(lambda k: diffusion.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    if (jax.tree.structure(want) != jax.tree.structure(params)
            or any(a.shape != b.shape or a.dtype != b.dtype for a, b in
                   zip(jax.tree.leaves(want), jax.tree.leaves(params)))):
        raise BenchError("the reference's weight layout differs from the "
                         "program's parameter tree")
    return cfg, params


def solver_of(conf):
    from repro.core import diffusion, solvers
    s = conf["sampler"]
    if s["solver"] != "ddim":
        raise BenchError(f"unknown solver {s['solver']!r}")
    return solvers.ddim(s["steps"], sched=diffusion.vp_schedule(
        s["num_train_steps"], s["beta_start"], s["beta_end"]),
        num_train_steps=s["num_train_steps"])


def calibration_labels(cell):
    """The calibration batch's labels.  Like the model, the calibration
    batch is the deployment's, the same for every seed (``MODEL_SEED``)."""
    rng = traffic_lib.rng_for(reference(cell).MODEL_SEED, "calibration")
    n = int(cell.mix["calibration_samples"])
    return rng.integers(0, cell.conf["model"]["num_classes"], size=n)


def calibration_key(ref):
    """The key of the calibration batch's noise, shared by the program's
    calibration and the reference's."""
    import jax
    return jax.random.fold_in(ref.seed_key(ref.MODEL_SEED), 11)


def calibrate_artifact(cell, seed: int, out: str, cfg=None, params=None):
    """Calibrate the mix's policy on its samples and save the artifact."""
    import jax.numpy as jnp
    from repro import cache
    if params is None:
        cfg, params = make_params(cell, seed, reference(cell))
    s = cell.conf["sampler"]
    pipe = cache.DiffusionPipeline(cfg, solver_of(cell.conf),
                                   cell.mix["policy"],
                                   cfg_scale=s["cfg_scale"])
    labels = jnp.asarray(calibration_labels(cell), jnp.int32)
    key = calibration_key(reference(cell))
    pipe.calibrate(params, key, int(labels.shape[0]),
                   cond_args={"label": labels})
    return pipe.save_artifact(out)


def calibrate_in_child(cell, seed: int, out: str) -> float:
    """Run ``calibrate.py`` and return its wall time."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "calibrate.py"),
         "--workload", cell.name, "--seed", str(seed), "--out", out],
        stdout=sys.stderr, stderr=sys.stderr)
    if proc.returncode != 0:
        raise BenchError(f"calibration process exited {proc.returncode}")
    return time.monotonic() - t0


def build_program(cell, cfg, artifact: Optional[str]):
    from repro import serve
    from repro.core.executor import SmoothCacheExecutor
    s = cell.conf["sampler"]
    solver = solver_of(cell.conf)
    ex = SmoothCacheExecutor(cfg, solver, cfg_scale=s["cfg_scale"])
    store = serve.ArtifactStore(cfg, solver, cfg_scale=s["cfg_scale"])
    if artifact is not None:
        store.add_artifact(POLICY, artifact)
    else:
        store.add_policy(POLICY, cell.mix["policy"])
    return ex, store


def new_engine(cell, ex, params, store):
    from repro import serve
    e = cell.conf["engine"]
    return serve.ServeEngine(
        ex, params, store, max_batch=e["max_batch"], max_wait=e["max_wait"],
        max_inflight=e["max_inflight"], scheduler=e["scheduler"],
        continuous=e["continuous"])


def warm_up(cell, ex, params, store, buckets, seed: int) -> None:
    """Serve one batch of each bucket shape, largest first."""
    from repro import serve
    rng = traffic_lib.rng_for(seed, "warmup")
    for b in sorted(buckets, reverse=True):
        eng = new_engine(cell, ex, params, store)
        now = eng.clock.now()
        eng.submit(*[serve.Request(
            rid=i, seed=int(rng.integers(0, 1 << 31)), policy=POLICY,
            label=int(rng.integers(0, cell.conf["model"]["num_classes"])),
            arrival=now) for i in range(b)])
        if len(eng.run_until_drained()) != b:
            raise BenchError(f"warm-up of bucket {b} did not finish")


def drive(eng, traf, annotate):
    """The window: the loop of ``ServeEngine.run_until_drained`` on the
    wall clock, fed by the traffic.  Open-loop requests are submitted up
    front with their scheduled arrivals; a backlog is topped up to its
    depth until the window ends, when the requests still queued are taken
    back and the batches in flight run to their end.  Returns ``(t0, t_end,
    requests)``."""
    from repro import serve
    clock = eng.clock
    requests: List = []

    def make(offset, seed, label):
        r = serve.Request(rid=len(requests), seed=seed, policy=POLICY,
                          label=label, arrival=t0 + offset)
        requests.append(r)
        return r

    t0 = clock.now() + LEAD_S
    t_end = t0 + traf.seconds
    eng.submit(*[make(*x) for x in traf.initial()])
    finish = eng._finish

    def read_back(fl):
        with annotate("bench.readback"):
            return finish(fl)
    eng._finish = read_back
    clock.sleep_until(t0)
    window = annotate("bench.window")
    window.__enter__()
    open_window = True
    while True:
        now = clock.now()
        if not traf.open_loop:
            if now < t_end:
                while len(eng.queue) < traf.depth:
                    eng.submit(make(now - t0, *traf.draw()))
            elif open_window:
                eng.queue.drain_all()
                window.__exit__(None, None, None)
                open_window = False
        with annotate("bench.step"):
            progressed = eng.step()
        if progressed:
            continue
        if len(eng.queue) == 0 and (traf.open_loop or not open_window):
            break
        now = clock.now()
        t = eng.batcher.next_event(now)
        if t is None:
            raise BenchError("engine stalled: queued requests but no "
                             "schedulable event")
        if open_window and not traf.open_loop:
            t = min(t, t_end)
        if t > now:
            with annotate("bench.sleep"):
                clock.sleep_until(t)
    if open_window:
        window.__exit__(None, None, None)
        t_end = clock.now()
    return t0, t_end, requests


def _record(r) -> Dict:
    return {"bucket": r.bucket, "rids": list(r.rids), "seeds": list(r.seeds),
            "labels": list(r.labels), "num_steps": r.num_steps,
            "compute_fraction": r.compute_fraction,
            "formed_at": r.formed_at, "finished_at": r.finished_at}


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

def check_sample(cell, seed: int, finished: List[int]) -> List[int]:
    """The requests compared: drawn from the seed among the finished."""
    rng = traffic_lib.rng_for(seed, "sample")
    k = min(int(cell.checks["samples"]), len(finished))
    return sorted(int(x) for x in rng.choice(sorted(finished), size=k,
                                             replace=False))


def reference_check(cell, seed: int, rows: Dict, *, dtype="float32",
                    precision="highest"):
    """The reference's answers on its own weights from the seed:
    ``(latents, curves, skip)``.  Where the mix's policy is calibrated,
    the reference calibrates itself on the deployment's calibration batch
    (``calibration_labels``, ``calibration_key``), giving its error
    ``curves`` and the ``skip`` mask
    its rule derives; ``latents`` maps each rid in ``rows`` (rid →
    ``(batch seeds, row, label)``) to its final latent sampled under that
    mask, in blocks of rows."""
    import jax.numpy as jnp
    ref = reference(cell)
    m, s = cell.conf["model"], cell.conf["sampler"]
    dt = jnp.dtype(dtype)
    params = ref.make_weights(m, seed, cell.conf["dtype"])
    curves = skip = None
    if cell.mix["policy"] != "none":
        alpha, k_max = ref.policy_rule(cell.mix["policy"])
        labels = calibration_labels(cell)
        noise = ref.key_noise(calibration_key(ref), len(labels),
                              m["latent_shape"])
        curves = ref.error_curves(m, s, params, noise, labels, k_max,
                                  dtype=dt, precision=precision)
        skip = ref.smoothcache_schedule(curves, alpha, k_max)
    rids = sorted(rows)
    block = int(cell.checks.get("block") or len(rids) or 1)
    out = {}
    for i in range(0, len(rids), block):
        part = rids[i:i + block]
        noise = np.stack([np.asarray(ref.batch_noise(
            rows[r][0], m["latent_shape"]))[rows[r][1]] for r in part])
        labels = np.asarray([rows[r][2] for r in part], np.int32)
        want = ref.sample(m, s, params, noise, labels, skip, dtype=dt,
                          precision=precision)
        out.update(zip(part, want))
    return out, curves, skip


def compared(cell, served: Dict, want: Dict, served_skip, curves) -> Dict:
    """The numbers ``correct`` is decided on: the largest relative L2 gap
    of a served latent to the reference's (``latent_rel_err``) and, for a
    calibrated policy, how far the served skip mask breaks the policy's
    rule under the reference's error curves (``schedule_violation``)."""
    gaps = rel_gaps(served, want)
    out = {"latent_rel_err": max(gaps) if gaps else None}
    if curves is not None:
        alpha, k_max = reference(cell).policy_rule(cell.mix["policy"])
        out["schedule_violation"] = reference(cell).schedule_violation(
            curves, served_skip or {}, alpha, k_max)
    return out


def rel_gaps(got: Dict, want: Dict) -> List[float]:
    """Relative L2 gap of each latent in ``got`` to ``want``'s."""
    out = []
    for r in sorted(got):
        g = np.asarray(got[r], np.float64)
        w = np.asarray(want[r], np.float64)
        out.append(float(np.linalg.norm(g - w) / np.linalg.norm(w)))
    return out


def judge(cell, values: Dict[str, float]):
    """``(correct, checks)``: each compared number beside its limit."""
    checks, ok = {}, bool(values)
    for name, limit in cell.checks["limits"].items():
        v = values.get(name)
        checks[name] = {"value": v, "limit": limit}
        ok = ok and v is not None and math.isfinite(v) and v <= limit
    return ok, checks


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------

def read_metrics(entries, run) -> Dict:
    out = {}
    for m in entries:
        v = load_module(os.path.join(BENCH_DIR, "metrics",
                                     m["name"] + ".py")).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             *, require_tpu: bool = True, calibrate_in_process: bool = False,
             trace_events=None, keep: Optional[Dict] = None):
    """One run; returns the result line as a dict.  ``require_tpu=False``
    and ``calibrate_in_process=True`` drive the same steps on any device
    (the tests, at a small size).  ``trace_events`` replaces the reading
    of the profiler's trace file (the tests).  ``keep`` receives what the
    control needs: the served latents, their rows, the served mask, the
    reference's latents, curves and mask, and the compared numbers."""
    setup: Dict[str, float] = {}
    ref = reference(cell)
    traf = traffic_lib.Traffic(cell.mix, seed, seconds,
                               cell.conf["model"]["num_classes"])
    calibrated = cell.mix["policy"] != "none"
    work_dir = tempfile.mkdtemp(prefix="chipbench-")
    artifact = os.path.join(work_dir, "cell.cache.json") if calibrated \
        else None
    try:
        if calibrated and not calibrate_in_process:
            setup["calibrate_s"] = calibrate_in_child(cell, seed, artifact)
        import jax
        devs = tpu_devices(cell.chips) if require_tpu else jax.devices()
        enable_compile_cache()
        counter = CompileCounter()
        t = time.monotonic()
        cfg, params = make_params(cell, seed, ref)
        setup["weights_s"] = time.monotonic() - t
        if calibrated and calibrate_in_process:
            t = time.monotonic()
            calibrate_artifact(cell, seed, artifact, cfg, params)
            setup["calibrate_s"] = time.monotonic() - t
        skip = None
        if artifact is not None:
            skip = {k: [bool(x) for x in v] for k, v in
                    _json(artifact)["schedule"]["skip"].items()}
        ex, store = build_program(cell, cfg, artifact)
        t = time.monotonic()
        warm_up(cell, ex, params, store,
                traf.buckets(cell.conf["engine"]["max_batch"]), seed)
        setup["warmup_s"] = time.monotonic() - t
        eng = new_engine(cell, ex, params, store)
        annotate = contextlib.nullcontext
        logdir = None
        if trace:
            annotate = jax.profiler.TraceAnnotation
            logdir = os.path.join(work_dir, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(logdir, profiler_options=opts)
        setup["setup_s"] = time.monotonic() - t_start
        counter.active = True
        t0, t_end, requests = drive(eng, traf, annotate)
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
        peak = int((devs[0].memory_stats() or {}).get("peak_bytes_in_use",
                                                      -1))
        report = eng.report()
        records = [_record(r) for r in eng.records]
        attempted = [r for r in requests if r.started is not None
                     or traf.open_loop]
        finished = [r.rid for r in attempted if r.finished is not None]
        sample = check_sample(cell, seed, finished)
        served = {rid: np.array(eng.results[rid]) for rid in sample}
        rows = {}
        for rec in records:
            for j, rid in enumerate(rec["rids"]):
                if rid in served:
                    rows[rid] = (rec["seeds"], j, requests[rid].label)
        reduced = None
        if trace:
            devices, host = (trace_events(t0, t_end) if trace_events
                             else _load_trace(logdir))
            from trace_reduce import reduce
            reduced = reduce(devices, host)
        run = types.SimpleNamespace(
            requests=[{"rid": r.rid, "arrival": r.arrival,
                       "started": r.started, "finished": r.finished}
                      for r in attempted],
            window=(t0, t_end), records=records, report=report,
            setup=setup, memory_peak_bytes=peak, trace=reduced, skip=skip,
            model=cell.conf["model"],
            cfg_scale=cell.conf["sampler"]["cfg_scale"],
            peaks=_peaks(devs[0], require_tpu))
        metrics = read_metrics(cell.per_layer if trace else cell.end_to_end,
                               run)
        # the program's state goes before the reference runs
        del eng, ex, store, params
        gc.collect()
        want, curves, ref_skip = reference_check(cell, seed, rows)
        values = compared(cell, served, want, skip, curves)
        if keep is not None:
            keep.update(served=served, rows=rows, skip=skip, want=want,
                        curves=curves, ref_skip=ref_skip, values=values)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    correct, checks = judge(cell, values)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(attempted),
           "failed": len(attempted) - len(finished), "metrics": metrics,
           "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["window_compiles"] = counter.count
    out["setup"] = setup
    out["checks"] = checks
    return out


def _load_trace(logdir):
    from trace_reduce import find_xplane, load
    return load(find_xplane(logdir))


def _peaks(dev, require_tpu: bool):
    from peaks import peaks_for
    if require_tpu:
        return peaks_for(dev.device_kind)
    try:
        return peaks_for(dev.device_kind)
    except KeyError:
        return None


def print_result(out: Dict) -> None:
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
