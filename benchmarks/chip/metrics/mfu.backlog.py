"""FLOPs done inside the window (credited per step), over the window
times the chip's peak, in percent."""
import flops
import work


def read(run):
    if run.peaks is None:
        return None
    lo, hi = run.window
    total = sum(r["bucket"] * work.inside(s, f, lo, hi) * flops.sample_flops(
        run.model, run.skip, r["num_steps"], run.cfg_scale)
        for r, s, f in work.spans(run.records))
    return 100.0 * total / ((hi - lo) * run.peaks["bf16_flops_per_s"])
