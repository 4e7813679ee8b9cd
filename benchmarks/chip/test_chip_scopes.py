"""CPU tests of ``trace_scopes.py``: device time per branch scope, the
idle time the host caused, and idle gaps named after the program's spans,
on a small trace in the profiler's own format; and that the reduction of
``trace_reduce.py`` reads the same trace as before."""
import copy
import os
import random
import types

import numpy as np
import pytest

import harness
import trace_reduce
import trace_scopes

US = 1000000                              # 1 µs in ps

#: device ops of one TPU, µs into the window [0, 100): (name, start, end,
#: name scope or None); the while loop holds the ffn and attn fusions
OPS = [("while.1", 10, 40, None),
       ("fusion.ffn", 12, 22, "jit(seg)/while/body/closed_call/ffn/dot:"),
       ("fusion.attn", 25, 35, "jit(seg)/while/body/closed_call/attn/dot:"),
       ("copy.2", 60, 80, "jit(seg)/solver/sub:"),
       ("fusion.mod", 85, 90, "jit(seg)/while/body/closed_call/adaln/dot:")]

#: host spans, µs: the benchmark's loop and, with ``serve``, the engine's
BENCH = [("bench.window", 0, 100), ("bench.step", 0, 45),
         ("bench.sleep", 45, 60), ("bench.step", 80, 100),
         ("bench.readback", 80.5, 98.5)]
SERVE = [("serve.step", 1, 44), ("serve.admit", 1, 9),
         ("serve.launch", 2, 8), ("serve.advance", 10, 40),
         ("serve.sleep", 46, 59), ("serve.step", 80, 99),
         ("serve.finish", 81, 98), ("serve.finish.wait", 81, 86),
         ("serve.finish.copy", 90, 95)]


def trace_text(scoped: bool, serve: bool) -> str:
    """The trace as a text proto (times from 1e6 ns)."""
    dev_events, dev_meta = [], []
    for i, (name, s, e, scope) in enumerate(OPS, 1):
        dev_events.append(f"events {{ metadata_id: {i} offset_ps: {s * US} "
                          f"duration_ps: {(e - s) * US} }}")
        stat = (f' stats {{ metadata_id: 1 str_value: "{scope}" }}'
                if scoped and scope else "")
        dev_meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{name}"{stat} }} }}')
    host = BENCH + (SERVE if serve else [])
    host_events, host_meta = [], []
    for i, (name, s, e) in enumerate(host, 1):
        args = (" stats { metadata_id: 1 int64_value: 3 }"
                if name == "serve.advance" else "")
        host_events.append(f"events {{ metadata_id: {i} "
                           f"offset_ps: {int(s * US)} "
                           f"duration_ps: {int((e - s) * US)}{args} }}")
        host_meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                         f'name: "{name}" }} }}')
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000
    {" ".join(dev_events)} }}
  {" ".join(dev_meta)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 7 name: "python" timestamp_ns: 1000000
    {" ".join(host_events)} }}
  {" ".join(host_meta)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "serial" }} }}
}}
"""


def write_trace(logdir, scoped=True, serve=True) -> str:
    from jax.profiler import ProfileData
    d = os.path.join(str(logdir), "plugins", "profile", "run")
    os.makedirs(d)
    path = os.path.join(d, "host.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            trace_text(scoped, serve)))
    return path


def first_reading(path):
    """What the harness's own reduction reads from the file."""
    devices, host = trace_reduce.load(path)
    return trace_reduce.reduce(devices, host)


def run_cell(run, logdir):
    """Stands in for the harness's frame, whose ``logdir`` the metric
    readers find."""
    return harness.read_metrics(
        [m for m in harness.load_cell(CELL).per_layer
         if m["name"] in NEW + ("admit_lag_p95_s",)], run)


CELL = "dit-xl-256.smoothcache.poisson"
NEW = ("attn_device_share.poisson", "ffn_device_share.poisson",
       "host_idle_share.poisson")

#: the first reading of the trace, every key: the program's spans and
#: scopes move none of them
PINNED = {"busy_s": 55e-6, "window_s": 100e-6, "idle_share": 0.45,
          "device_ops": {"copy.2": 20e-6, "while.1": 10e-6,
                         "fusion.ffn": 10e-6, "fusion.attn": 10e-6,
                         "fusion.mod": 5e-6},
          "idle_gaps": [["bench.sleep", 20e-6], ["bench.step", 10e-6],
                        ["bench.readback", 10e-6],
                        ["bench.readback", 5e-6]]}


@pytest.mark.parametrize("scoped,serve", [(False, False), (True, True)],
                         ids=["parent", "scoped"])
def test_first_reading_is_unchanged_by_scopes_and_spans(tmp_path, scoped,
                                                        serve):
    r = first_reading(write_trace(tmp_path, scoped, serve))
    assert set(r) == set(PINNED)
    for key in ("busy_s", "window_s", "idle_share"):
        assert r[key] == pytest.approx(PINNED[key])
    assert dict(r["device_ops"]) == pytest.approx(PINNED["device_ops"])
    assert [n for n, _ in r["idle_gaps"]] == \
        [n for n, _ in PINNED["idle_gaps"]]
    assert [t for _, t in r["idle_gaps"]] == \
        pytest.approx([t for _, t in PINNED["idle_gaps"]])


def test_scopes_split_busy_time_by_self_time(tmp_path):
    device, host = trace_scopes.load(write_trace(tmp_path))
    r = trace_scopes.reduce(device, host)
    assert r["scopes"] == pytest.approx({
        "ffn": 10e-6, "attn": 10e-6, "solver": 20e-6, "adaln": 5e-6,
        "other": 10e-6})                      # the while loop's own time
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"])
    assert r["busy_s"] == pytest.approx(PINNED["busy_s"])


def test_host_idle_and_gap_names_follow_the_engine_spans(tmp_path):
    device, host = trace_scopes.load(write_trace(tmp_path))
    assert ("serve.advance", 1010000.0, 1040000.0) in host
    r = trace_scopes.reduce(device, host)
    # idle [0,10) [40,60) [80,85) [90,100) µs under serve.* spans other
    # than serve.sleep ([1,44) and [80,99)): 9 + 4 + 5 + 9
    assert r["host_idle_s"] == pytest.approx(27e-6)
    assert r["idle_gaps"] == [["serve.sleep", pytest.approx(20e-6)],
                              ["serve.launch", pytest.approx(10e-6)],
                              ["serve.finish.copy", pytest.approx(10e-6)],
                              ["serve.finish.wait", pytest.approx(5e-6)]]


def test_metrics_read_the_run_in_progress(tmp_path):
    path = write_trace(tmp_path)
    first = first_reading(path)
    run = types.SimpleNamespace(trace=copy.deepcopy(first),
                                report={"admit_lag_s": {"p95": 0.25}})
    got = run_cell(run, str(tmp_path))
    assert got["attn_device_share.poisson"]["value"] == \
        pytest.approx(100 * 10 / 55)
    assert got["ffn_device_share.poisson"]["value"] == \
        pytest.approx(100 * 10 / 55)
    assert got["host_idle_share.poisson"]["value"] == pytest.approx(27.0)
    assert got["admit_lag_p95_s"]["value"] == 0.25
    # the first reading's keys keep their values; its gaps are renamed
    for key in ("busy_s", "window_s", "idle_share", "device_ops"):
        assert run.trace[key] == first[key]
    assert [t for _, t in run.trace["idle_gaps"]] == \
        [t for _, t in first["idle_gaps"]]
    assert [n for n, _ in run.trace["idle_gaps"]] == [
        "serve.sleep", "serve.launch", "serve.finish.copy",
        "serve.finish.wait"]


def test_a_program_without_scopes_or_spans_reads_nothing(tmp_path):
    first = first_reading(write_trace(tmp_path, scoped=False, serve=False))
    run = types.SimpleNamespace(trace=copy.deepcopy(first), report={})
    got = run_cell(run, str(tmp_path))
    assert not set(NEW + ("admit_lag_p95_s",)) & set(got)
    assert run.trace == first


def test_no_trace_directory_reads_nothing():
    run = types.SimpleNamespace(trace={"idle_gaps": []}, report={})
    assert trace_scopes.share(run, "attn") is None
    assert run.trace == {"idle_gaps": []}


def nested_ops(rng, lo, hi, depth):
    """Ops in ``[lo, hi)`` as a while loop's do: in order, some holding
    ops of their own (``(scope index, start, end)``, integer ns)."""
    out, t = [], lo
    while t < hi - 4:
        s = rng.randint(t, min(hi - 2, t + 40))
        e = rng.randint(s + 1, min(hi, s + 60))
        out.append((rng.randrange(len(trace_scopes.NAMES)), float(s),
                    float(e)))
        if depth and e - s > 4 and rng.random() < 0.5:
            out += nested_ops(rng, s, e, depth - 1)
        t = e
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_array_reduction_matches_the_first_readings_arithmetic(seed):
    """The second reading's array arithmetic gives what
    ``trace_reduce``'s loops give on the same ops: busy intervals, and
    self time (here per scope)."""
    rng = random.Random(seed)
    ops = nested_ops(rng, 0, 4000, depth=3)
    line = trace_scopes.Line(np.array([o[1] for o in ops]),
                             np.array([o[2] for o in ops]),
                             np.array([o[0] for o in ops]))
    lo, hi = 100.0, 3900.0
    bs, be = trace_scopes.busy_intervals(line.start, line.end, lo, hi)
    want = trace_reduce.clip(trace_reduce.union(
        [(o[1], o[2]) for o in ops]), lo, hi)
    assert [[a, b] for a, b in zip(bs, be)] == want
    inside = [(trace_scopes.NAMES[n], max(s, lo), min(e, hi))
              for n, s, e in ops if min(e, hi) > max(s, lo)]
    want = trace_reduce.self_times(inside)
    got = trace_scopes.self_times(line, lo, hi)
    assert {n: t for n, t in zip(trace_scopes.NAMES, got) if t} == \
        pytest.approx({n: t for n, t in want.items() if t})


@pytest.mark.parametrize("op_name,scope", [
    ("jit(seg)/while/body/closed_call/attn/dot_general:", "attn"),
    ("jit(seg)/solver/ffn/mul:", "ffn"),          # the innermost wins
    ("jit(seg)/while/body/squeeze:", "other"),
    ("", "other")])
def test_scope_of(op_name, scope):
    assert trace_scopes.scope_of(op_name) == scope
