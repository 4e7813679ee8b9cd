"""Reduction of a profiler trace (``.xplane.pb``) to the device numbers.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:`` plane), inside the window,
  averaged over the devices;
* idle share: 1 − busy / window;
* top device operations by self time (an operation's time less that of
  the operations nested in it on the same line);
* the longest idle gaps, each named after the innermost host annotation
  that covers the middle of the gap.  The benchmark's serving loop writes
  those annotations (``bench.step``, ``bench.sleep``, ``bench.readback``);
  ``bench.window`` spans the measured window and sets its bounds.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW = "bench.window"

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


def short_name(name: str, width: int = 96) -> str:
    """An operation's name without its operands: the instruction and the
    start of its result type (a TPU trace names each operation by its
    whole HLO text)."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:width]
    return (lhs.lstrip("%") + " = " + rhs)[:width]


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def events(data) -> Tuple[List[List[Event]], List[Event]]:
    """``(device events per device, host annotations)`` of a
    ``jax.profiler.ProfileData``."""
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            evs = [(short_name(e.name), e.start_ns, e.end_ns)
                   for line in plane.lines if line.name == DEVICE_LINE
                   for e in line.events]
            if evs:
                devices.append(evs)
        elif plane.name.startswith("/host:"):
            host.extend((e.name, e.start_ns, e.end_ns)
                        for line in plane.lines for e in line.events
                        if e.name.startswith(HOST_PREFIX))
    return devices, host


def load(path: str):
    from jax.profiler import ProfileData
    return events(ProfileData.from_file(path))


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> List[List[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(evs: Sequence[Event]) -> Dict[str, float]:
    """Nanoseconds per operation name, less the time of the operations
    nested inside each event."""
    out: Dict[str, float] = {}
    stack: List[list] = []            # [name, end, duration, nested]

    def pop():
        name, _, dur, nested = stack.pop()
        out[name] = out.get(name, 0.0) + max(dur - nested, 0.0)

    for name, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            pop()
        if stack:
            stack[-1][3] += e - s
        stack.append([name, e, e - s, 0.0])
    while stack:
        pop()
    return out


def window_of(host: Sequence[Event], devices) -> Tuple[float, float]:
    spans = [(s, e) for n, s, e in host if n == WINDOW]
    if spans:
        return spans[0]
    allev = [x for evs in devices for x in evs]
    return min(x[1] for x in allev), max(x[2] for x in allev)


def innermost(host: Sequence[Event], t: float) -> str:
    best: Optional[Event] = None
    for ev in host:
        if ev[0] != WINDOW and ev[1] <= t <= ev[2]:
            if best is None or ev[2] - ev[1] < best[2] - best[1]:
                best = ev
    return best[0] if best is not None else "none"


def reduce(devices: Sequence[Sequence[Event]], host: Sequence[Event],
           window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Dict:
    """Busy and window seconds, idle share, top operations and longest
    idle gaps (of the first device) over ``window`` (ns; by default the
    ``bench.window`` annotation)."""
    if not devices:
        raise ValueError("the trace holds no device operations")
    lo, hi = window if window is not None else window_of(host, devices)
    window_ns = hi - lo
    busy = []
    for evs in devices:
        busy.append(sum(e - s for s, e in
                        clip(union([(s, e) for _, s, e in evs]), lo, hi)))
    busy_ns = sum(busy) / len(busy)
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in devices[0]
              if min(e, hi) > max(s, lo)]
    ops = sorted(self_times(inside).items(), key=lambda kv: -kv[1])[:top]
    merged = clip(union([(s, e) for _, s, e in devices[0]]), lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[innermost(host, (s + e) / 2), (e - s) / 1e9]
             for s, e in gaps[:top]]
    return {"busy_s": busy_ns / 1e9, "window_s": window_ns / 1e9,
            "idle_share": 1.0 - busy_ns / window_ns if window_ns else None,
            "device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": named}
