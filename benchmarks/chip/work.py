"""Work done inside a window, credited per denoising step.

A served batch runs its steps back to back between the moment the device
could start it (its formation, or the finish of the batch before it,
since one chip runs one batch at a time) and its finish.  Its steps are
spread evenly over that span, so a batch that straddles an edge of the
window is credited with the share of its steps that fell inside.
"""
from __future__ import annotations

from typing import Dict, List, Sequence


def spans(records: Sequence[Dict]) -> List[tuple]:
    """``(record, start, finish)`` in order of finish."""
    out, prev = [], None
    for r in sorted(records, key=lambda r: r["finished_at"]):
        start = r["formed_at"] if prev is None else max(r["formed_at"], prev)
        out.append((r, start, r["finished_at"]))
        prev = r["finished_at"]
    return out


def inside(start: float, finish: float, lo: float, hi: float) -> float:
    """The share of [start, finish] that lies in [lo, hi]."""
    if finish <= start:
        return 1.0 if lo <= finish <= hi else 0.0
    return max(0.0, min(finish, hi) - max(start, lo)) / (finish - start)


def credited_samples(records: Sequence[Dict], lo: float, hi: float) -> float:
    """Samples' worth of denoising steps done inside [lo, hi]."""
    return sum(r["bucket"] * inside(s, f, lo, hi)
               for r, s, f in spans(records))
