"""Samples completed per second: the denoising steps done inside the
window, credited per step, in samples, over the window (host clock)."""
import work


def read(run):
    lo, hi = run.window
    return work.credited_samples(run.records, lo, hi) / (hi - lo)
