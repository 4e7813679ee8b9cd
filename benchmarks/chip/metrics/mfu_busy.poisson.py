"""FLOPs of the branch evaluations that ran (the realized skip masks
through ``flops.py``), over the device's busy time in the trace times the
chip's peak, in percent."""
import flops


def read(run):
    if run.trace is None or not run.trace["busy_s"] or run.peaks is None:
        return None
    total = sum(r["bucket"] * flops.sample_flops(
        run.model, run.skip, r["num_steps"], run.cfg_scale)
        for r in run.records)
    return 100.0 * total / (run.trace["busy_s"]
                            * run.peaks["bf16_flops_per_s"])
