"""Readings that set a cell's correctness limits.

For each seed, one short run of the cell at its own load (weights,
calibration in this process, warm-up, a window of ``--seconds``), then
the numbers ``correct`` is decided on (``harness.compared``): the
program's, and on the control seeds the control's.  The control is the
reference put in the program's place and computed in bfloat16 at the
default precision: it calibrates, derives its skip mask and samples the
same requests, and is compared with the float32 reference at ``highest``
as the program is.  Both are judged against the cell's limits by
``harness.judge``.  All seeds run in one process, so set-up compiles once.

``--fault-policy`` plants a fault in the program's calibration: it
calibrates and serves that policy (say, a looser alpha) while the
reference keeps the cell's, so the program's readings are the fault's.

Prints one JSON line per seed and a summary: the lower reading of each
number is the largest over the program's seeds, the upper the smallest
over the control's.

    python3 benchmarks/chip/control.py --workload <cell> --seconds 6 \\
        --seeds 1,2,...,12 --control-seeds 1,2,3 [--fault-policy <spec>]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.join(os.path.dirname(os.path.dirname(
    BENCH_DIR)), "src")]


def readings(cell, seed: int, seconds: float, control: bool, **kw):
    """``(program values, its checks, control values or None, its
    checks)`` of one seed."""
    import harness
    keep = {}
    out = harness.run_cell(cell, seed, seconds, False, time.monotonic(),
                           calibrate_in_process=True, keep=keep, **kw)
    if not control:
        return keep["values"], out["checks"], None, None
    low, _, low_skip = harness.reference_check(
        cell, seed, keep["rows"], dtype="bfloat16", precision="default")
    values = harness.compared(cell, low, keep["want"], low_skip,
                              keep["curves"])
    ok, checks = harness.judge(cell, values)
    return keep["values"], out["checks"], values, dict(checks, correct=ok)


def plant_calibration_fault(policy: str) -> None:
    """The program calibrates and serves ``policy`` instead of the mix's."""
    import harness
    orig = harness.calibrate_artifact

    def calibrate_artifact(cell, *a, **kw):
        faulty = types.SimpleNamespace(**vars(cell))
        faulty.mix = dict(cell.mix, policy=policy)
        return orig(faulty, *a, **kw)
    harness.calibrate_artifact = calibrate_artifact


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-policy")
    args = ap.parse_args(argv)
    import harness
    cell = harness.load_cell(args.workload)
    harness.tpu_devices(cell.chips)
    if args.fault_policy:
        plant_calibration_fault(args.fault_policy)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    progs, ctrls = [], []
    for seed in seeds:
        prog, prog_checks, ctrl, ctrl_checks = readings(
            cell, seed, args.seconds, seed in ctrl_seeds)
        progs.append(prog)
        if ctrl is not None:
            ctrls.append(ctrl)
        print(json.dumps({"seed": seed, "program": prog_checks,
                          "control": ctrl_checks}), flush=True)
    names = sorted(cell.checks["limits"])
    print(json.dumps({
        "workload": cell.name, "fault_policy": args.fault_policy,
        "lower": {n: max(p[n] for p in progs) for n in names},
        "upper": {n: min(c[n] for c in ctrls) for n in names} if ctrls
        else None,
        "program": progs, "control": ctrls}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
