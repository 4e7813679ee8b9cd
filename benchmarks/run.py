"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Usage:

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig5]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro import compile_cache

MODULES = [
    ("fig5", "benchmarks.fig5_compute"),        # fast, analytic
    ("fig2", "benchmarks.fig2_error_curves"),
    ("table1", "benchmarks.table1_dit"),
    ("executor", "benchmarks.executor_bench"),
    ("adaptive", "benchmarks.adaptive_bench"),
    ("serve", "benchmarks.serve_bench"),
    ("slo", "benchmarks.slo_bench"),
    ("resilience", "benchmarks.resilience_bench"),
    ("continuous", "benchmarks.continuous_bench"),
    ("durability", "benchmarks.durability_bench"),
    ("table2", "benchmarks.table2_video"),
    ("table3", "benchmarks.table3_audio"),
    ("kernels", "benchmarks.kernel_bench"),
    ("ablation", "benchmarks.ablation_calibration"),
    ("beyond_ar", "benchmarks.beyond_ar_cache"),
    ("roofline", "benchmarks.roofline_table"),
]
# benchmarks.beyond_mesh_cache needs 512 placeholder devices — run it
# standalone: PYTHONPATH=src python -m benchmarks.beyond_mesh_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: "
                         + ",".join(k for k, _ in MODULES))
    args = ap.parse_args()
    compile_cache.enable()
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failures = []
    for key, modname in MODULES:
        if only and key not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(modname, fromlist=["run"])
            mod.run()
            print(f"{key}/_elapsed,{(time.time()-t0)*1e6:.0f},ok")
        except Exception as e:  # keep the harness going; report at the end
            failures.append((key, e))
            traceback.print_exc()
            print(f"{key}/_elapsed,{(time.time()-t0)*1e6:.0f},FAIL:{type(e).__name__}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
