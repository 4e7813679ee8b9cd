"""Knee sweep of an open-loop cell: the highest arrival rate at which the
backlog does not grow over the window.

One process: weights, calibration, warm-up, then one window per rate on
a fresh engine.  For each rate it prints the latency median and tail, the
mean latency of the first and last third of the requests (a growing
backlog makes the last third wait longer), and how long the last request
finished after the window closed.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
        --seconds 20 --rates 4,6,8,10
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.join(os.path.dirname(os.path.dirname(
    BENCH_DIR)), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import harness
    import traffic as traffic_lib
    cell = harness.load_cell(args.workload)
    harness.tpu_devices(cell.chips)
    harness.enable_compile_cache()
    ref = harness.reference(cell)
    cfg, params = harness.make_params(cell, args.seed, ref)
    artifact = None
    with tempfile.TemporaryDirectory() as work:
        if cell.mix["policy"] != "none":
            artifact = os.path.join(work, "cell.cache.json")
            harness.calibrate_artifact(cell, args.seed, artifact, cfg, params)
        ex, store = harness.build_program(cell, cfg, artifact)
    mb = cell.conf["engine"]["max_batch"]
    harness.warm_up(cell, ex, params, store, traffic_lib.buckets(mb),
                    args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate)
        traf = traffic_lib.Traffic(mix, args.seed + i, args.seconds,
                                   cell.conf["model"]["num_classes"])
        eng = harness.new_engine(cell, ex, params, store)
        t0, t_end, reqs = harness.drive(eng, traf,
                                            contextlib.nullcontext)
        lat = np.array([r.finished - r.arrival for r in reqs])
        third = max(1, len(lat) // 3)
        print(json.dumps({
            "rate": rate, "requests": len(reqs),
            "p50": float(np.percentile(lat, 50)),
            "p95": float(np.percentile(lat, 95)),
            "first_third_mean": float(lat[:third].mean()),
            "last_third_mean": float(lat[-third:].mean()),
            "tail_after_window_s": t_end - (t0 + args.seconds),
            "batch_mean": float(np.mean([r.bucket for r in eng.records])),
            "compute_fraction": eng.report()["compute_fraction"],
            "served_per_s": len(reqs) / (t_end - t0)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
