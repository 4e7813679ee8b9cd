"""A second reading of the profiler trace (``.xplane.pb``): device time per
branch scope, device idle time the host caused, and idle gaps named after
the program's own spans.

* scopes: device self time (``trace_reduce.self_times``) per branch
  scope: the last component of an op's name scope that is one of
  ``SCOPES`` (the model's ``jax.named_scope``s, named by the SmoothCache
  branch types), ``other`` for ops under none;
* host idle: the device's idle seconds inside the window that a
  ``serve.*`` span of the engine covers, ``serve.sleep`` excepted: the
  chip waited on the host, not on traffic;
* idle gaps: the longest ones, as ``trace_reduce`` finds them, each named
  after the innermost ``bench.*`` or ``serve.*`` span over its middle.

``jax.profiler.ProfileData`` shows an event's own stats but not its
metadata's, and the TPU keeps an op's name scope there: the ``tf_op``
stat of the ``XLA Ops`` event metadata, such as
``jit(fn)/while/body/closed_call/ffn/dot_general:``.  So this module reads
the file through a partial schema of the XSpace proto, and resolves a
scope once per distinct op, never per event.  Where no device plane
exists (the CPU), the XLA thunk events of the host threads stand for the
device's operations, and their name scopes come from the HLO protos the
profiler keeps in ``/host:metadata``: the harness's CPU tests take that
path.

The harness hands its metric readers its own reduction, not the trace.
:func:`augment` finds the trace of the run in progress and adds
``scopes`` and ``host_idle_s`` to that reduced dict, and renames its idle
gaps.  A program without branch scopes or ``serve.*`` spans gets neither
key, and its gaps keep their names.
"""
from __future__ import annotations

import functools
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import trace_reduce

SCOPES = ("attn", "xattn", "ffn", "adaln", "embed", "final", "solver")
OTHER = "other"
HOST_PREFIXES = ("bench.", "serve.")
SERVE_PREFIX = "serve."
SLEEP = "serve.sleep"

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


def scope_of(op_name: str) -> str:
    """The innermost branch scope of a name-scope path, else ``other``."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return OTHER


# ---------------------------------------------------------------------------
# The file
# ---------------------------------------------------------------------------

#: (message, [(field, number, type, repeated, message type)]), proto2;
#: field numbers from tsl/profiler/protobuf/xplane.proto and xla/xla.proto
_SCHEMA = [
    ("XStat", [("metadata_id", 1, "int64", False, None),
               ("uint64_value", 3, "uint64", False, None),
               ("int64_value", 4, "int64", False, None),
               ("str_value", 5, "string", False, None),
               ("bytes_value", 6, "bytes", False, None),
               ("ref_value", 7, "uint64", False, None)]),
    ("XEvent", [("metadata_id", 1, "int64", False, None),
                ("offset_ps", 2, "int64", False, None),
                ("duration_ps", 3, "int64", False, None),
                ("stats", 4, "message", True, "XStat")]),
    ("XLine", [("name", 2, "string", False, None),
               ("timestamp_ns", 3, "int64", False, None),
               ("events", 4, "message", True, "XEvent")]),
    ("XEventMetadata", [("id", 1, "int64", False, None),
                        ("name", 2, "string", False, None),
                        ("stats", 5, "message", True, "XStat")]),
    ("XStatMetadata", [("id", 1, "int64", False, None),
                       ("name", 2, "string", False, None)]),
    # map<int64, V> fields, read as their wire form: repeated entries
    ("EventMetadataEntry", [("key", 1, "int64", False, None),
                            ("value", 2, "message", False,
                             "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, "int64", False, None),
                           ("value", 2, "message", False, "XStatMetadata")]),
    ("XPlane", [("name", 2, "string", False, None),
                ("lines", 3, "message", True, "XLine"),
                ("event_metadata", 4, "message", True, "EventMetadataEntry"),
                ("stat_metadata", 5, "message", True, "StatMetadataEntry")]),
    ("XSpace", [("planes", 1, "message", True, "XPlane")]),
    ("OpMetadata", [("op_name", 2, "string", False, None)]),
    ("HloInstructionProto", [("name", 1, "string", False, None),
                             ("metadata", 7, "message", False,
                              "OpMetadata")]),
    ("HloComputationProto", [("instructions", 2, "message", True,
                              "HloInstructionProto")]),
    ("HloModuleProto", [("computations", 3, "message", True,
                         "HloComputationProto")]),
    ("HloProto", [("hlo_module", 1, "message", False, "HloModuleProto")]),
]


@functools.lru_cache(maxsize=None)
def _messages():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto2")
    F = descriptor_pb2.FieldDescriptorProto
    for name, fields in _SCHEMA:
        m = fd.message_type.add(name=name)
        for fname, number, typ, repeated, mtype in fields:
            f = m.field.add(name=fname, number=number,
                            type=getattr(F, "TYPE_" + typ.upper()),
                            label=(F.LABEL_REPEATED if repeated
                                   else F.LABEL_OPTIONAL))
            if mtype:
                f.type_name = ".bench_xplane." + mtype
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane." + name))
        for name, _ in _SCHEMA}


def _stat(st, names: Dict[int, str]):
    if st.HasField("str_value"):
        return st.str_value
    if st.HasField("ref_value"):
        return names.get(st.ref_value, "")
    if st.HasField("int64_value"):
        return st.int64_value
    if st.HasField("uint64_value"):
        return st.uint64_value
    if st.HasField("bytes_value"):
        return st.bytes_value
    return None


def _stats(stats, names: Dict[int, str]) -> Dict[str, object]:
    return {names.get(st.metadata_id, ""): _stat(st, names) for st in stats}


def _span_name(name: str) -> str:
    # a TraceMe that kept its args in the name reads ``name#k=v#``
    return name.split("#", 1)[0]


def _hlo_op_names(plane, names: Dict[int, str]) -> Dict[tuple, str]:
    """``(program id, instruction) → op name`` from ``/host:metadata``."""
    hlo = _messages()["HloProto"]
    out = {}
    for entry in plane.event_metadata:
        m = re.search(r"\((\d+)\)$", entry.value.name)
        blob = _stats(entry.value.stats, names).get("Hlo Proto")
        if m is None or not isinstance(blob, bytes):
            continue
        proto = hlo.FromString(blob)
        for comp in proto.hlo_module.computations:
            for ins in comp.instructions:
                out[(int(m.group(1)), ins.name)] = ins.metadata.op_name
    return out


#: scope index of an op: ``SCOPES`` in order, then ``OTHER``
NAMES = SCOPES + (OTHER,)


class Line(NamedTuple):
    """One line of device ops: start and end in ns (float, rounded as
    ``ProfileData`` rounds them, so both readings find the same
    intervals) and each op's index into ``NAMES``."""
    start: np.ndarray
    end: np.ndarray
    scope: np.ndarray


def _line(ts_ns: int, offset_ps, duration_ps, scope) -> Line:
    start = (ts_ns + np.asarray(offset_ps, np.int64) // 1000).astype(
        np.float64)
    return Line(start, start + np.asarray(duration_ps, np.int64) // 1000,
                np.asarray(scope, np.int64))


def events(space) -> Tuple[List[Line], List[Event]]:
    """``(device lines, host spans)`` of a parsed XSpace: the first
    device's op lines, and every ``bench.*`` and ``serve.*`` host span
    as ``(name, start_ns, end_ns)``."""
    device: List[Line] = []
    host: List[Event] = []
    planes = [(p, {e.key: e.value.name for e in p.stat_metadata},
               {e.key: e.value for e in p.event_metadata})
              for p in space.planes]
    for plane, names, meta in planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lut = np.full(max(meta, default=0) + 1, NAMES.index(OTHER))
            for k, m in meta.items():
                tf_op = _stats(m.stats, names).get("tf_op", "")
                lut[k] = NAMES.index(scope_of(str(tf_op)))
            for line in plane.lines:
                if line.name == trace_reduce.DEVICE_LINE and line.events:
                    ev = np.fromiter(
                        (v for e in line.events for v in
                         (e.metadata_id, e.offset_ps, e.duration_ps)),
                        np.int64, 3 * len(line.events)).reshape(-1, 3)
                    device.append(_line(line.timestamp_ns, ev[:, 1],
                                        ev[:, 2], lut[ev[:, 0]]))
            if device:
                break
    thunks = []
    hlo_names: Dict[tuple, str] = {}
    for plane, names, meta in planes:
        if plane.name == "/host:metadata" and not device:
            hlo_names = _hlo_op_names(plane, names)
        elif plane.name.startswith("/host:"):
            spans = {k: _span_name(m.name) for k, m in meta.items()
                     if m.name.startswith(HOST_PREFIXES)}
            for line in plane.lines:
                ops = []
                for e in line.events:
                    if e.metadata_id in spans:
                        start = float(line.timestamp_ns + e.offset_ps // 1000)
                        host.append((spans[e.metadata_id], start,
                                     start + e.duration_ps // 1000))
                    elif not device and e.stats:
                        st = _stats(e.stats, names)
                        if "hlo_op" in st:
                            ops.append((st.get("program_id"), st["hlo_op"],
                                        e.offset_ps, e.duration_ps))
                if ops:
                    thunks.append((line.timestamp_ns, ops))
    # the CPU: XLA's thunks on the host threads are the device's ops
    for ts_ns, ops in thunks:
        device.append(_line(
            ts_ns, [o[2] for o in ops], [o[3] for o in ops],
            [NAMES.index(scope_of(hlo_names.get(o[:2], ""))) for o in ops]))
    return device, host


def load(path: str):
    with open(path, "rb") as f:
        return events(_messages()["XSpace"].FromString(f.read()))


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------

def busy_intervals(starts, ends, lo: float, hi: float):
    """``trace_reduce.clip(trace_reduce.union(...))`` on arrays: the
    merged busy intervals inside ``[lo, hi)`` as ``(starts, ends)``."""
    if len(starts) == 0:
        return np.empty(0), np.empty(0)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    first = np.flatnonzero(np.r_[True, s[1:] > reach[:-1]])
    s, e = np.maximum(s[first], lo), np.minimum(
        np.maximum.reduceat(e, first), hi)
    keep = e > s
    return s[keep], e[keep]


def self_times(line: Line, lo: float, hi: float) -> np.ndarray:
    """Nanoseconds per scope index of ``trace_reduce.self_times`` over the
    line's ops clipped to ``[lo, hi)``: each op's time less that of the
    ops nested directly in it.  Ops on a device line nest (a while loop
    holds its body's ops), so an op's depth is the number of earlier ops
    still running at its start, and its parent the last earlier op one
    level up."""
    s, e = np.maximum(line.start, lo), np.minimum(line.end, hi)
    keep = e > s
    s, e, scope = s[keep], e[keep], line.scope[keep]
    order = np.lexsort((-e, s))
    s, e, scope = s[order], e[order], scope[order]
    dur = e - s
    depth = np.arange(len(s)) - np.searchsorted(np.sort(e), s, "right")
    nested = np.zeros(len(s))
    for d in range(1, int(depth.max(initial=0)) + 1):
        at, up = np.flatnonzero(depth == d), np.flatnonzero(depth == d - 1)
        parent = up[np.searchsorted(up, at) - 1]
        nested += np.bincount(parent, weights=dur[at], minlength=len(s))
    return np.bincount(scope, weights=np.maximum(dur - nested, 0.0),
                       minlength=len(NAMES))


def _overlap(a_start, a_end, b: Sequence[Sequence[float]]) -> float:
    """Total length of the intersection of two sorted disjoint interval
    lists."""
    total = 0.0
    for lo, hi in b:
        i, j = np.searchsorted(a_end, lo, "right"), np.searchsorted(
            a_start, hi, "left")
        if j > i:
            total += float((np.minimum(a_end[i:j], hi)
                            - np.maximum(a_start[i:j], lo)).sum())
    return total


def reduce(device: Sequence[Line], host: Sequence[Event],
           window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Dict:
    """Busy and window seconds, device seconds per scope, host-caused
    idle seconds and the longest idle gaps of the device's op lines over
    ``window`` (ns; by default the ``bench.window`` span)."""
    if not device or not any(len(line.start) for line in device):
        raise ValueError("the trace holds no device operations")
    if window is None:
        spans = [(s, e) for n, s, e in host if n == trace_reduce.WINDOW]
        window = spans[0] if spans else (
            min(float(line.start.min()) for line in device if len(line.start)),
            max(float(line.end.max()) for line in device if len(line.end)))
    lo, hi = window
    bs, be = busy_intervals(np.concatenate([line.start for line in device]),
                            np.concatenate([line.end for line in device]),
                            lo, hi)
    ns = sum(self_times(line, lo, hi) for line in device)
    gs, ge = np.r_[lo, be], np.r_[bs, hi]
    keep = ge > gs
    gs, ge = gs[keep], ge[keep]
    engine = trace_reduce.clip(trace_reduce.union(
        [(s, e) for n, s, e in host
         if n.startswith(SERVE_PREFIX) and n != SLEEP]), lo, hi)
    longest = np.argsort(gs - ge, kind="stable")[:top]
    return {"busy_s": float((be - bs).sum()) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "scopes": {n: float(t) / 1e9 for n, t in zip(NAMES, ns) if t},
            "host_idle_s": _overlap(gs, ge, engine) / 1e9,
            "serve_spans": sum(1 for n, _, _ in host
                               if n.startswith(SERVE_PREFIX)),
            "idle_gaps": [[trace_reduce.innermost(host, (gs[i] + ge[i]) / 2),
                           float(ge[i] - gs[i]) / 1e9] for i in longest]}


# ---------------------------------------------------------------------------
# The run in progress
# ---------------------------------------------------------------------------

def _trace_dir() -> Optional[str]:
    """The profiler directory of the harness's run in progress: the
    ``logdir`` of the ``run_cell`` frame that is reading metrics."""
    frame = sys._getframe(1)
    while frame is not None:
        if frame.f_code.co_name == "run_cell":
            logdir = frame.f_locals.get("logdir")
            return logdir if isinstance(logdir, str) else None
        frame = frame.f_back
    return None


def augment(run) -> None:
    """Add ``scopes`` and ``host_idle_s`` to ``run.trace`` from the trace
    of the run in progress, and name its idle gaps after the innermost
    ``bench.*`` or ``serve.*`` span; once per run.  Nothing is added where
    no trace can be read, where no op carries a branch scope
    (``scopes``), or where the program wrote no ``serve.*`` span
    (``host_idle_s``)."""
    if run.trace is None or getattr(run, "scope_reading", False):
        return
    run.scope_reading = True
    logdir = _trace_dir()
    if logdir is None:
        return
    try:
        path = trace_reduce.find_xplane(logdir)
    except FileNotFoundError:
        return
    device, host = load(path)
    if not device:
        return
    r = reduce(device, host)
    if set(r["scopes"]) - {OTHER}:
        run.trace["scopes"] = r["scopes"]
        run.trace["scope_busy_s"] = r["busy_s"]
    if r["serve_spans"]:
        run.trace["host_idle_s"] = r["host_idle_s"]
        run.trace["host_window_s"] = r["window_s"]
        gaps = run.trace.get("idle_gaps") or []
        if len(gaps) == len(r["idle_gaps"]) and all(
                abs(a[1] - b[1]) <= 1e-9 for a, b in
                zip(gaps, r["idle_gaps"])):
            for a, b in zip(gaps, r["idle_gaps"]):
                a[0] = b[0]


def share(run, key: str) -> Optional[float]:
    """``scopes[key]`` over the busy time of the same reading, in
    percent; None where the trace carries no branch scopes."""
    augment(run)
    scopes = (run.trace or {}).get("scopes")
    if scopes is None or not run.trace.get("scope_busy_s"):
        return None
    return 100.0 * scopes.get(key, 0.0) / run.trace["scope_busy_s"]
