"""Calibration process of a cell: calibrates the mix's cache policy on the
cell's weights and writes the artifact, then exits, so that the serving
process never holds calibration's memory.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seed <n> --out <path>
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
# the TPU runtime would log under a fixed path in /tmp; a run writes
# nothing outside its checkout and its own temporary directory
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [BENCH_DIR, os.path.join(os.path.dirname(os.path.dirname(
    BENCH_DIR)), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import harness
    try:
        cell = harness.load_cell(args.workload)
        harness.tpu_devices(cell.chips)
        harness.enable_compile_cache()
        path = harness.calibrate_artifact(cell, args.seed, args.out)
    except harness.BenchError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    with open(path) as f:
        skip = json.load(f)["schedule"]["skip"]
    print("calibrate: skipped steps " + ", ".join(
        f"{t} {sum(v)}/{len(v)}" for t, v in sorted(skip.items())),
        file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
