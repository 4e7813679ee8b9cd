"""repro.obs — tracer/registry/cache-report units, the ServerMetrics
registry view, and the zero-sync telemetry regression on the smoke DiT.

The fast half runs against a virtual clock and a local fake executor
(same pattern as ``tests/test_serve.py`` — engine behavior is exact,
deterministic assertions).  The slow half (``small_dit`` fixture) pins
the acceptance invariants: fused step telemetry keeps
``executor.host_sync_count`` at 0, and per-row :class:`CacheReport`
realized decisions bit-match the host dispatch loop's
``return_decisions``.
"""
import dataclasses
import json
import math

import numpy as np
import pytest

from repro import serve
from repro.core import plan as plan_lib
from repro.obs import (CacheReport, MetricsRegistry, NULL_TRACER,
                       NullTracer, Tracer, TimeSeries, run_cache_reports,
                       schedule_cache_report, validate_chrome_trace)
from repro.serve.metrics import ServerMetrics, _dist, percentile
from repro.serve.request import VirtualClock

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_tracer_spans_export_and_validate():
    clock = VirtualClock()
    tr = Tracer(clock)
    t1 = tr.new_track("batch#1")
    tr.begin(t1, "run", group="g", bucket=2)
    clock.advance(1.0)
    tr.begin(t1, "advance")
    clock.advance(0.5)
    tr.end(t1, "advance", step_to=3)
    tr.instant("rung_move", rung=1)
    clock.advance(0.5)
    tr.end(t1, "run", outcome="done")
    obj = tr.to_chrome_trace()
    n = validate_chrome_trace(obj)
    assert n == 5                             # 2 B + 2 E + 1 i
    evs = obj["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {e["args"]["name"] for e in meta} == {"engine", "batch#1"}
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t" and inst["tid"] == 0
    # ts is microseconds of the virtual clock
    ends = [e for e in evs if e["ph"] == "E"]
    assert ends[0]["ts"] == pytest.approx(1.5e6)
    assert ends[1]["ts"] == pytest.approx(2.0e6)
    assert not tr.open_spans()


def test_tracer_span_contextmanager_and_len():
    tr = Tracer(VirtualClock())
    with tr.span(0, "outer"):
        with tr.span(0, "inner"):
            pass
    assert len(tr) == 4
    validate_chrome_trace(tr.to_chrome_trace())


def test_tracer_end_discipline():
    tr = Tracer(VirtualClock())
    with pytest.raises(ValueError, match="no open span"):
        tr.end(0)
    tr.begin(0, "run")
    with pytest.raises(ValueError, match="open .*span is 'run'"):
        tr.end(0, "advance")
    # the mismatch left the stack intact — the right end still works
    tr.end(0, "run")
    assert not tr.open_spans()


def test_tracer_open_spans_reported():
    tr = Tracer(VirtualClock())
    t1 = tr.new_track("b")
    tr.begin(t1, "run")
    assert tr.open_spans() == {t1: ("run",)}


def test_validate_rejects_malformed_traces():
    def ev(ph, ts, tid, name):
        return {"ph": ph, "ts": ts, "pid": 1, "tid": tid, "name": name}
    # dangling B
    with pytest.raises(ValueError, match="unclosed"):
        validate_chrome_trace({"traceEvents": [ev("B", 0, 1, "run")]})
    # E without B
    with pytest.raises(ValueError, match="without an open B"):
        validate_chrome_trace({"traceEvents": [ev("E", 0, 1, "run")]})
    # E name mismatch
    with pytest.raises(ValueError, match="closes"):
        validate_chrome_trace({"traceEvents": [
            ev("B", 0, 1, "run"), ev("E", 1, 1, "advance")]})
    # backwards timestamps within one track
    with pytest.raises(ValueError, match="backwards"):
        validate_chrome_trace({"traceEvents": [
            ev("B", 5, 1, "run"), ev("E", 1, 1, "run")]})


def test_null_tracer_is_inert():
    tr = NULL_TRACER
    assert isinstance(tr, NullTracer) and not tr.enabled
    assert tr.new_track("x") == 0
    tr.begin(3, "run")
    tr.end(3)                                 # no raise — no state at all
    tr.instant("anything")
    with tr.span(0, "s"):
        pass
    assert tr.to_chrome_trace() == {"traceEvents": []}
    with pytest.raises(ValueError, match="NullTracer"):
        tr.save("/tmp/never.json")


def test_tracer_save_roundtrip(tmp_path):
    tr = Tracer(VirtualClock())
    tr.instant("tick")
    path = tr.save(str(tmp_path / "t.trace.json"))
    with open(path) as f:
        assert validate_chrome_trace(json.load(f)) == 1


def _profiled(tmp_path, body):
    """Run ``body`` under a profiler session; return the host plane's
    events as ``{name: stats}``."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, dict(e.stats))
    return out


@pytest.mark.parametrize("recording", [False, True],
                         ids=["null_tracer", "tracer"])
def test_span_lands_on_the_profiler_timeline(tmp_path, recording):
    """One span API, two sinks: both tracers put the span and its args
    (start args and ``update`` args) on the profiler's host timeline, and
    a recording tracer also keeps its B/E pair on its own clock."""
    clock = VirtualClock()
    tr = Tracer(clock) if recording else NULL_TRACER

    def body():
        with tr.span(0, "serve.probe", serial=7, segment="seg[0] [0,4)",
                     rids=[3, 4]) as span:
            clock.advance(1.0)
            span.update(new_program=1)

    events = _profiled(tmp_path, body)
    assert events["serve.probe"] == {"serial": 7, "segment": "seg0 0;4",
                                     "rids": "3 4", "new_program": 1}
    if recording:
        evs = [e for e in tr.to_chrome_trace()["traceEvents"]
               if e["ph"] in "BE"]
        assert [(e["ph"], e["ts"]) for e in evs] == [("B", 0.0),
                                                      ("E", 1e6)]
        assert evs[0]["args"]["segment"] == "seg[0] [0,4)"
        assert evs[1]["args"] == {"new_program": 1}
    else:
        assert tr.to_chrome_trace() == {"traceEvents": []}


def test_span_pair_stays_matched_when_the_body_raises():
    tr = Tracer(VirtualClock())
    with pytest.raises(RuntimeError):
        with tr.span(0, "serve.advance"):
            raise RuntimeError("fault")
    assert not tr.open_spans()
    assert validate_chrome_trace(tr.to_chrome_trace()) == 2


# ---------------------------------------------------------------------------
# MetricsRegistry / TimeSeries
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.inc("serve.shed", reason="backlog")
    reg.inc("serve.shed", 2, reason="deadline")
    assert reg.counter("serve.shed", reason="backlog") == 1
    assert reg.counter_total("serve.shed") == 3
    assert reg.labeled("serve.shed", "reason") == {"backlog": 1,
                                                   "deadline": 2}
    reg.set_gauge("slo.step_cost_s", 0.25, group="g")
    assert reg.gauge("slo.step_cost_s", group="g") == 0.25
    assert reg.gauge("slo.step_cost_s") is None
    reg.observe("serve.queue_wait_s", 1.0)
    reg.observe("serve.queue_wait_s", 3.0)
    assert reg.samples("serve.queue_wait_s") == [1.0, 3.0]
    snap = reg.snapshot()
    assert snap["counters"]['serve.shed{reason="backlog"}'] == 1
    assert snap["histograms"]["serve.queue_wait_s"] == {
        "n": 2, "sum": 4.0, "min": 1.0, "max": 3.0}
    names = reg.names()
    assert "serve.shed" in names["counters"]
    assert "serve.queue_wait_s" in names["histograms"]


def test_registry_exposition_format():
    reg = MetricsRegistry()
    reg.inc("serve.batches", 4)
    reg.observe("serve.service_s", 2.0)
    reg.series("slo.rung").record(0.0, 1.0)
    text = reg.exposition()
    assert "# TYPE serve.batches counter\nserve.batches 4" in text
    assert "serve.service_s_count 1" in text
    assert "serve.service_s_sum 2" in text
    assert "# TYPE slo.rung gauge\nslo.rung 1" in text


def test_registry_rejects_non_finite():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="non-finite"):
        reg.inc("c", float("nan"))
    with pytest.raises(ValueError, match="non-finite"):
        reg.set_gauge("g", float("inf"))
    with pytest.raises(ValueError, match="non-finite"):
        reg.observe("h", float("-inf"))
    with pytest.raises(ValueError, match="non-finite"):
        reg.series("s").record(0.0, float("nan"))


def test_timeseries_ring_eviction():
    ts = TimeSeries("x", capacity=3)
    for i in range(5):
        ts.record(float(i), float(i * 10))
    assert len(ts) == 3
    assert ts.items() == [(2.0, 20.0), (3.0, 30.0), (4.0, 40.0)]
    assert ts.last() == (4.0, 40.0)
    with pytest.raises(ValueError):
        TimeSeries("bad", capacity=0)


def test_registry_series_get_or_create():
    reg = MetricsRegistry()
    s1 = reg.series("slo.p95_wait_s", capacity=4)
    s2 = reg.series("slo.p95_wait_s")
    assert s1 is s2 and s1.capacity == 4


# ---------------------------------------------------------------------------
# percentile / _dist edge cases (satellite)
# ---------------------------------------------------------------------------

def test_percentile_single_sample_all_p():
    for p in (0, 37.5, 50, 100):
        assert percentile([4.2], p) == 4.2


def test_percentile_two_samples_boundaries():
    assert percentile([1.0, 3.0], 0) == 1.0
    assert percentile([1.0, 3.0], 100) == 3.0
    assert percentile([1.0, 3.0], 50) == 2.0
    assert percentile([3.0, 1.0], 25) == 1.5  # order-independent


def test_percentile_rejects_bad_inputs():
    with pytest.raises(ValueError, match="empty"):
        percentile([], 50)
    for p in (-1, 101):
        with pytest.raises(ValueError, match=r"\[0, 100\]"):
            percentile([1.0], p)
    for bad in (float("nan"), float("inf"), float("-inf")):
        with pytest.raises(ValueError, match="non-finite"):
            percentile([1.0, bad], 95)
        with pytest.raises(ValueError, match="non-finite"):
            _dist([1.0, bad])
    # NaN would otherwise corrupt silently: sorted() leaves it in place
    assert math.isnan(sorted([1.0, float("nan"), 0.5])[1])


def test_dist_empty_is_null_shape():
    assert _dist([]) == {"mean": None, "p50": None, "p95": None,
                         "max": None, "n": 0}


# ---------------------------------------------------------------------------
# ServerMetrics as a registry view (satellite: first-class lineage)
# ---------------------------------------------------------------------------

def _req(rid, arrival=0.0, started=1.0, finished=2.0, joined_at=None):
    r = serve.Request(rid=rid, seed=rid, policy="p", arrival=arrival)
    r.started, r.finished, r.joined_at = started, finished, joined_at
    return r


def test_server_metrics_is_a_registry_view():
    reg = MetricsRegistry()
    m = ServerMetrics(registry=reg)
    assert m.registry is reg
    m.observe_request(_req(0))
    m.observe_request(_req(1, started=2.0, finished=5.0, joined_at=1.5))
    m.observe_batch("g", 2, 0.5, num_steps=4, num_types=2)
    m.observe_merge(kind="join")
    m.observe_merge(kind="coalesce")
    m.observe_lineage("join")
    m.observe_lineage("regroup", 3)
    m.observe_fault("g", "nan_latent")
    # the legacy attribute surface reads through the registry
    assert m.requests == 2 and m.batches == 1
    assert m.queue_waits == [1.0, 2.0]
    assert m.joined_queue_waits == [2.0]      # joiner-specific wait dist
    assert m.merges == 2
    assert m.lineage_events == {"join": 1, "regroup": 3}
    assert m.fault_kinds == {"nan_latent": 1}
    # and the same numbers are visible in the raw registry
    assert reg.counter("continuous.merges", kind="coalesce") == 1
    assert reg.samples("serve.queue_wait_joined_s") == [2.0]


def test_report_extends_continuous_with_lineage_and_joined_waits():
    m = ServerMetrics()
    m.observe_request(_req(0))
    m.observe_request(_req(1, started=2.0, finished=5.0, joined_at=1.5))
    m.observe_join(1)
    m.observe_merge(kind="join")
    m.observe_lineage("join")
    rep = m.report()
    cont = rep["continuous"]
    assert cont["joins"] == 1 and cont["join_merges"] == 1
    assert cont["coalesces"] == 0
    assert cont["lineage_events"] == {"join": 1}
    assert cont["joined_queue_wait_s"]["n"] == 1
    assert cont["joined_queue_wait_s"]["p50"] == 2.0
    json.dumps(rep)                           # JSON-safe end to end


# ---------------------------------------------------------------------------
# CacheReport builders
# ---------------------------------------------------------------------------

def _static_schedule(steps=4):
    from repro.core import schedule as S
    return S.fora(("attn", "ffn"), steps, 2)


def test_schedule_cache_report_matches_schedule():
    sch = _static_schedule(4)
    rep = schedule_cache_report(sch, tau=0.0)
    assert rep.num_steps == 4 and rep.types == ("attn", "ffn")
    assert rep.desired == rep.realized
    skipped = sum(len(s) for s in rep.realized)
    assert rep.realized_compute_fraction() == \
        pytest.approx(1.0 - skipped / 8.0)
    assert rep.skipped_per_type() == rep.desired_per_type()
    traj = rep.proxy_vs_threshold()
    assert len(traj) == 4 and traj[0]["proxy"] is None
    json.dumps(rep.to_jsonable())


def test_run_cache_reports_decisions_fallback():
    @dataclasses.dataclass
    class FakeState:
        decisions: tuple
        tau: float = 0.1
    rs = FakeState(decisions=((), ("attn",), ("attn", "ffn")))
    reps = run_cache_reports(rs, 2, schedule=_static_schedule(3))
    assert len(reps) == 2
    assert reps[0].desired == reps[0].realized == \
        ((), ("attn",), ("attn", "ffn"))
    assert reps[0].tau == 0.1
    assert reps[0].skipped_per_type() == {"attn": 2, "ffn": 1}


def test_run_cache_reports_schedule_fallback_and_empty():
    class Bare:
        pass
    assert run_cache_reports(Bare(), 2) == []
    reps = run_cache_reports(Bare(), 3, schedule=_static_schedule(4),
                             tau=0.2)
    assert len(reps) == 3 and reps[0].tau == 0.2


def test_cache_report_zero_steps_fraction():
    rep = CacheReport(tau=0.0, types=(), desired=(), realized=())
    assert rep.realized_compute_fraction() == 1.0


# ---------------------------------------------------------------------------
# Controller → registry/tracer hooks
# ---------------------------------------------------------------------------

def test_controller_records_series_and_rung_instants():
    from repro import slo
    reg = MetricsRegistry()
    tr = Tracer(VirtualClock())
    ctrl = slo.ElasticTauController(
        3, target_p95_wait_s=1.0, min_samples=2, interval_s=0.0,
        cooldown_s=0.0, registry=reg, tracer=tr)
    for t in (0.0, 1.0, 2.0):
        ctrl.observe_wait(10.0, t)
        ctrl.update(t)
    assert ctrl.rung >= 1
    p95 = reg.series("slo.p95_wait_s")
    assert len(p95) >= 1 and p95.last()[1] == pytest.approx(10.0)
    rungs = [v for _, v in reg.series("slo.rung").items()]
    assert rungs and rungs[0] == 1.0
    moves = [e for e in tr.to_chrome_trace()["traceEvents"]
             if e.get("name") == "rung_move"]
    assert moves and moves[0]["args"]["from_rung"] == 0


# ---------------------------------------------------------------------------
# Engine lifecycle tracing on the virtual clock (fake executor)
# ---------------------------------------------------------------------------

class _FakeCfg:
    name = "fake-arch"

    def layer_types(self):
        return ("attn", "ffn")


class _FakeSolver:
    name = "ddim"

    def __init__(self, num_steps=8):
        self.num_steps = num_steps


@dataclasses.dataclass
class _FakeRunState:
    plan: plan_lib.ExecutionPlan
    batch: int
    run_index: int = 0
    x: object = None
    decisions = None

    @property
    def done(self):
        return self.run_index >= len(self.plan.runs)


class _FakeExecutor:
    def __init__(self, clock, step_cost=1.0):
        self.clock = clock
        self.step_cost = step_cost
        self._programs = set()

    def start_run(self, params, key, batch, *, plan, schedule=None,
                  label=None, memory=None):
        return _FakeRunState(plan=plan, batch=batch)

    def advance_run(self, params, rs, *, check=False):
        run = rs.plan.runs[rs.run_index]
        self._programs.add(("seg", run.sig, rs.batch))
        computed = sum(1 for sk in run.sig.skip.values() if not sk)
        self.clock.advance(self.step_cost * run.length
                           * computed / max(len(run.sig.skip), 1))
        rs = dataclasses.replace(rs, run_index=rs.run_index + 1)
        if rs.done:
            rs.x = np.arange(rs.batch, dtype=np.float64)[:, None]
        return rs

    def compiled_variant_count(self, kind=None):
        if kind is None:
            return len(self._programs)
        return len({p for p in self._programs if p[0] == kind})

    def xla_program_count(self, kind=None):
        return self.compiled_variant_count(kind)


def _run_fake_engine(tracer=None, n=5):
    clock = serve.VirtualClock()
    store = serve.ArtifactStore(_FakeCfg(), _FakeSolver(8))
    store.add_policy("static2", "static:n=2")
    eng = serve.ServeEngine(_FakeExecutor(clock), params=None, store=store,
                            clock=clock, max_batch=4, tracer=tracer)
    eng.submit(*[serve.Request(rid=i, seed=i, policy="static2",
                               arrival=0.1 * i) for i in range(n)])
    res = eng.run_until_drained()
    return eng, res


def test_engine_traced_run_validates_and_is_identical(tmp_path):
    eng_off, res_off = _run_fake_engine(tracer=None)
    clock = serve.VirtualClock()              # tracer shares engine clock
    tr = Tracer(clock)
    store = serve.ArtifactStore(_FakeCfg(), _FakeSolver(8))
    store.add_policy("static2", "static:n=2")
    eng_on = serve.ServeEngine(_FakeExecutor(clock), params=None,
                               store=store, clock=clock, max_batch=4,
                               tracer=tr)
    assert eng_on.tracer is tr and eng_on.batcher.tracer is tr
    eng_on.submit(*[serve.Request(rid=i, seed=i, policy="static2",
                                  arrival=0.1 * i) for i in range(5)])
    res_on = eng_on.run_until_drained()
    # tracing changes nothing observable: same latents, same records
    assert sorted(res_on) == sorted(res_off)
    for rid in res_on:
        np.testing.assert_array_equal(res_on[rid], res_off[rid])
    assert [r.bucket for r in eng_on.records] \
        == [r.bucket for r in eng_off.records]
    # the exported trace validates and covers the full lifecycle
    obj = tr.to_chrome_trace()
    assert validate_chrome_trace(obj) > 0
    evs = obj["traceEvents"]
    names = {e["name"] for e in evs if e["ph"] != "M"}
    assert {"submit", "form", "run", "serve.advance", "serve.step",
            "serve.admit", "serve.launch", "serve.finish",
            "serve.finish.wait", "serve.finish.copy"} <= names
    # one track per launched batch, named by serial
    tracks = [e["args"]["name"] for e in evs if e["ph"] == "M"]
    batch_tracks = [t for t in tracks if t.startswith("batch#")]
    assert len(batch_tracks) == len(eng_on.records)
    # every run span ended with an outcome
    outcomes = [e["args"]["outcome"] for e in evs
                if e["ph"] == "E" and e["name"] == "run"]
    assert outcomes and all(o == "done" for o in outcomes)
    # plan advances carry the segment label from ExecutionPlan.run_label
    segs = [e["args"]["segment"] for e in evs
            if e["ph"] == "B" and e["name"] == "serve.advance"
            and "segment" in e.get("args", {})]
    assert segs and all(s.startswith("seg[") for s in segs)
    # the first advance of each program compiles it: new_program marks
    # exactly the advances that grew the executor's program table
    ends = [e["args"] for e in evs
            if e["ph"] == "E" and e["name"] == "serve.advance"]
    assert sum(a["new_program"] for a in ends) \
        == eng_on.executor.compiled_variant_count()
    path = tr.save(str(tmp_path / "serve.trace.json"))
    with open(path) as f:
        validate_chrome_trace(json.load(f))


def test_engine_shed_and_reject_instants():
    clock = serve.VirtualClock()
    tr = Tracer(clock)
    store = serve.ArtifactStore(_FakeCfg(), _FakeSolver(8))
    store.add_policy("static2", "static:n=2")
    eng = serve.ServeEngine(_FakeExecutor(clock), params=None, store=store,
                            clock=clock, max_batch=4, tracer=tr)
    eng.submit(serve.Request(rid=0, seed=0, policy="nope", arrival=0.0))
    eng.submit(serve.Request(rid=1, seed=1, policy="static2", arrival=0.0))
    eng.submit(serve.Request(rid=1, seed=1, policy="static2", arrival=0.0))
    eng.run_until_drained()
    evs = tr.to_chrome_trace()["traceEvents"]
    rejects = [e for e in evs if e.get("name") == "reject"]
    assert {e["args"]["reason"] for e in rejects} \
        == {"no_entry", "duplicate_rid"}
    assert eng.report()["faults"]["rejected_submissions"] \
        == {"duplicate_rid": 1, "no_entry": 1}


def test_admit_lag_is_the_time_the_engine_was_blocked():
    """A request arriving while the engine is inside an advance is seen
    only when the tick ends: with 1 s per segment on the virtual clock,
    arrivals at 0.25 and 0.6 are seen at 1.0, the rest at once."""
    clock = serve.VirtualClock()
    tr = Tracer(clock)
    store = serve.ArtifactStore(_FakeCfg(), _FakeSolver(8))
    store.add_policy("nocache", "none")
    eng = serve.ServeEngine(_FakeExecutor(clock, step_cost=0.125),
                            params=None, store=store, clock=clock,
                            max_batch=4, tracer=tr)
    arrivals = [0.0, 0.25, 0.6, 2.5]
    reqs = [serve.Request(rid=i, seed=i, policy="nocache", arrival=a)
            for i, a in enumerate(arrivals)]
    eng.submit(*reqs)
    eng.run_until_drained()
    assert [r.seen for r in reqs] == [0.0, 1.0, 1.0, 2.5]
    lag = eng.report()["admit_lag_s"]
    assert lag["n"] == 4 and lag["max"] == pytest.approx(0.75)
    assert lag["mean"] == pytest.approx((0.75 + 0.4) / 4)
    # the idle engine sleeps to the last arrival under a serve.sleep span
    sleeps = [(e["ph"], e["ts"]) for e in tr.to_chrome_trace()["traceEvents"]
              if e["name"] == "serve.sleep"]
    assert sleeps == [("B", 2e6), ("E", 2.5e6)]


def test_traced_drain_keeps_makespan_and_exports_the_registry(tmp_path):
    """A mixed two-policy drain with the tracer on serves the same rows
    and ends at the same virtual time as with it off; its trace validates
    from disk, and the registry's snapshot and exposition count the
    batches it served."""
    def drain(traced):
        clock = serve.VirtualClock()
        store = serve.ArtifactStore(_FakeCfg(), _FakeSolver(8))
        store.add_policy("static2", "static:n=2")
        store.add_policy("no_cache", "none")
        kw = {"tracer": Tracer(clock)} if traced else {}
        eng = serve.ServeEngine(_FakeExecutor(clock), params=None,
                                store=store, clock=clock, max_batch=4,
                                max_inflight=2, **kw)
        eng.submit(*[serve.Request(
            rid=i, seed=i, policy="static2" if i % 3 else "no_cache",
            arrival=0.05 * i) for i in range(48)])
        return eng, eng.run_until_drained(), clock.now()

    eng_off, res_off, end_off = drain(False)
    eng_on, res_on, end_on = drain(True)
    assert end_on == end_off
    assert sorted(res_on) == sorted(res_off) == list(range(48))
    for rid in res_on:
        np.testing.assert_array_equal(res_on[rid], res_off[rid])
    path = eng_on.tracer.save(str(tmp_path / "drain.trace.json"))
    with open(path) as f:
        assert validate_chrome_trace(json.load(f)) > 0
    snap = eng_on.registry.snapshot()
    json.dumps(snap)
    assert snap["counters"]["serve.batches"] == len(eng_on.records)
    assert "# TYPE serve.batches counter" in eng_on.registry.exposition()


def test_engine_run_label_helper():
    sch = _static_schedule(6)
    plan = plan_lib.analyze(sch)
    labels = [plan.run_label(i) for i in range(len(plan.runs))]
    assert all(lab.startswith("seg[") and "steps[" in lab
               for lab in labels)
    with pytest.raises(IndexError):
        plan.run_label(len(plan.runs))


def test_resilience_policy_deadline_helper():
    from repro.resilience import ResiliencePolicy
    pol = ResiliencePolicy(watchdog_factor=3.0, watchdog_floor_s=0.5)
    assert pol.deadline(2.0) == pytest.approx(6.5)
    none_pol = ResiliencePolicy(watchdog_factor=None)
    with pytest.raises(ValueError, match="watchdog_factor"):
        none_pol.deadline(1.0)


# ---------------------------------------------------------------------------
# Branch scopes in the model's op metadata
# ---------------------------------------------------------------------------

def _strip_metadata(hlo: str) -> str:
    """Compiled HLO text without op metadata and the stack-frame tables
    it indexes."""
    import re
    out, tables = [], False
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            tables = True
            continue
        if tables and re.match(r"\d+ ", line):
            continue
        tables = False
        out.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


def _compiled_denoise_step():
    """HLO text of one guided-free smoke DiT-XL denoiser call plus its
    DDIM step, compiled for the CPU (a fresh function each call, so no
    trace is reused)."""
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.core import diffusion, solvers
    cfg = configs.get("dit-xl-256", "smoke")
    solver = solvers.ddim(4)

    def fn(params, x, t, label):
        pred, _ = diffusion.apply(cfg, params, x, t, label=label)
        return solver.step(x, pred, 1, {}, None)[0]

    params = jax.eval_shape(lambda k: diffusion.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2,) + tuple(cfg.latent_shape), jnp.float32)
    t = jax.ShapeDtypeStruct((2,), jnp.float32)
    label = jax.ShapeDtypeStruct((2,), jnp.int32)
    return jax.jit(fn).lower(params, x, t, label).compile().as_text()


@pytest.fixture(scope="module")
def denoise_hlo():
    return _compiled_denoise_step()


@pytest.mark.parametrize("scope", ["attn", "ffn", "adaln", "embed", "final",
                                   "solver"])
def test_denoiser_ops_carry_their_branch_scope(denoise_hlo, scope):
    import re
    names = re.findall(r'op_name="([^"]*)"', denoise_hlo)
    assert any(scope in n.split("/") for n in names)


def test_scopes_change_only_op_metadata(denoise_hlo, monkeypatch):
    import contextlib

    import jax
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_denoise_step()
    assert "/attn/" not in plain
    assert _strip_metadata(plain) == _strip_metadata(denoise_hlo)


# ---------------------------------------------------------------------------
# Zero-sync telemetry on the smoke DiT (slow; acceptance regression)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_dit():
    import jax
    from repro import configs
    from repro.core import diffusion
    cfg = configs.get("dit-xl-256", "smoke")
    params = diffusion.init_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(7),
                                               a.shape),
        params)
    return cfg, params


def _calibrated(cfg, params, tau, steps=6):
    import jax
    import jax.numpy as jnp
    from repro import cache
    from repro.core import solvers
    pipe = cache.DiffusionPipeline(
        cfg, solvers.ddim(steps),
        f"adaptive:base=smoothcache(alpha=0.5),tau={tau}", cfg_scale=1.5)
    pipe.calibrate(params, jax.random.PRNGKey(1), 2,
                   cond_args={"label": jnp.zeros((2,), jnp.int32)})
    return pipe


def test_fused_telemetry_zero_sync_and_reports_match_host(small_dit,
                                                          monkeypatch):
    """Acceptance: step telemetry ON adds zero host syncs, and the
    per-row CacheReport realized decisions bit-match the host dispatch
    loop's ``return_decisions``."""
    import jax
    import jax.numpy as jnp
    cfg, params = small_dit
    steps, tau = 6, 0.3
    pipe = _calibrated(cfg, params, tau, steps)
    ex = pipe.executor
    label = jnp.zeros((2,), jnp.int32)
    key = jax.random.PRNGKey(4)
    # warm the telemetry program (compilation is not a sync)
    rs0 = ex.start_adaptive_fused_run(
        params, key, 2, schedule=pipe.schedule, tau=tau,
        proxy_map=pipe.proxy_map, label=label, telemetry=True)
    while not rs0.done:
        rs0 = ex.advance_adaptive_fused(params, rs0, n_steps=2)
    ex.host_sync_count = 0
    d2h = {"n": 0}
    real_device_get = jax.device_get

    def counting_device_get(x):
        d2h["n"] += 1
        return real_device_get(x)

    monkeypatch.setattr(jax, "device_get", counting_device_get)
    with jax.transfer_guard_device_to_host("disallow"):
        rs = ex.start_adaptive_fused_run(
            params, key, 2, schedule=pipe.schedule, tau=tau,
            proxy_map=pipe.proxy_map, label=label, telemetry=True)
        while not rs.done:
            rs = ex.advance_adaptive_fused(params, rs, n_steps=3)
    assert d2h["n"] == 0 and ex.host_sync_count == 0
    monkeypatch.undo()
    # one boundary read builds every row's report
    reps = run_cache_reports(rs, 2)
    assert len(reps) == 2
    # realized decisions bit-match the host dispatch loop
    _, d_host = ex.sample_adaptive(
        params, key, 2, schedule=pipe.schedule, tau=tau,
        proxy_map=pipe.proxy_map, label=label, return_decisions=True)
    for rep in reps:
        assert rep.realized == d_host == rs.decisions
        assert rep.num_steps == steps
        # realized is the AND of the rows' desires
        for s in range(steps):
            for t in rep.realized[s]:
                assert all(t in r.desired[s] for r in reps)
        # proxy trajectory recorded: step 0 masked, the rest finite
        assert rep.proxy is not None and rep.proxy[0] is None
        assert all(p is not None and math.isfinite(p)
                   for p in rep.proxy[1:])
    # telemetry never changes the latents: same run without it
    rs_plain = ex.start_adaptive_fused_run(
        params, key, 2, schedule=pipe.schedule, tau=tau,
        proxy_map=pipe.proxy_map, label=label)
    while not rs_plain.done:
        rs_plain = ex.advance_adaptive_fused(params, rs_plain, n_steps=3)
    np.testing.assert_array_equal(np.asarray(rs.x), np.asarray(rs_plain.x))


def test_engine_telemetry_and_tracing_bit_identical(small_dit, tmp_path):
    """Serving with tracer + telemetry on produces bit-identical latents
    to serving with both off, populates per-request cache reports, and
    exports a valid trace."""
    import jax
    from repro.core import solvers
    from repro.core.executor import SmoothCacheExecutor
    cfg, params = small_dit
    steps, tau = 6, 0.3
    pipe = _calibrated(cfg, params, tau, steps)
    path = str(tmp_path / "adaptive.cache.json")
    pipe.save_artifact(path)

    def serve_once(obs):
        clock = serve.VirtualClock()
        solver = solvers.ddim(steps)
        ex = SmoothCacheExecutor(cfg, solver, cfg_scale=1.5)
        store = serve.ArtifactStore(cfg, solver, cfg_scale=1.5)
        store.add_artifact("gen", path)
        kw = {}
        if obs:
            kw = {"tracer": Tracer(clock), "telemetry": True}
        eng = serve.ServeEngine(ex, params, store, clock=clock,
                                max_batch=2, adaptive_chunk=3, **kw)
        eng.submit(*[serve.Request(rid=i, seed=100 + i, policy="gen",
                                   label=i % cfg.num_classes, arrival=0.0)
                     for i in range(2)])
        res = eng.run_until_drained()
        return eng, res, ex

    eng_on, res_on, ex_on = serve_once(True)
    eng_off, res_off, _ = serve_once(False)
    # the engine's spans live on a profiler session's timeline too
    jax.profiler.start_trace(str(tmp_path / "profile"))
    try:
        _, res_prof, ex_prof = serve_once(False)
    finally:
        jax.profiler.stop_trace()
    assert sorted(res_on) == sorted(res_off) == sorted(res_prof) == [0, 1]
    for rid in res_on:
        np.testing.assert_array_equal(res_on[rid], res_off[rid])
        np.testing.assert_array_equal(res_prof[rid], res_off[rid])
    # telemetry and spans stayed sync-free on the fused path
    assert ex_on.host_sync_count == ex_prof.host_sync_count == 0
    assert not eng_off.cache_reports
    assert sorted(eng_on.cache_reports) == [0, 1]
    rec = eng_on.records[0]
    for rid in rec.rids:
        rep = eng_on.cache_reports[rid]
        assert rep.realized == rec.decisions
        assert rep.tau == tau and rep.proxy is not None
        assert rep.proxy[0] is None
    # trace validates after the drain (all spans closed)
    assert not eng_on.tracer.open_spans()
    assert validate_chrome_trace(eng_on.tracer.to_chrome_trace()) > 0
