"""CPU tests of the benchmark's harness at the smoke size.

The command itself refuses the CPU; these tests drive the same steps of a
run (weights, calibration, warm-up, the window, the metrics, the
comparison with the reference) with the look for a TPU skipped, on a
smoke-sized DiT, and check the result line's shape, that the reference
agrees with the program, that the control computed in bfloat16 falls
outside the limit, and that a run with the timed path broken reads
``correct`` false.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import harness

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
CELLS = ("dit-xl-256.smoothcache.poisson", "dit-xl-512.nocache.backlog")


def smoke_cell(workload, max_batch=None):
    """The cell with the smoke-sized DiT of the program's configs, 10
    sampling steps and a short window's load."""
    cell = harness.load_cell(workload)
    c = cell.conf
    c["program"]["variant"] = "smoke"
    c["program"]["overrides"] = {"latent_shape": [8, 8, 4]}
    c["model"].update(depth=2, hidden_size=128, num_heads=4, head_dim=32,
                      mlp_hidden=256, latent_shape=[8, 8, 4])
    c["sampler"]["steps"] = 10
    if max_batch:
        c["engine"]["max_batch"] = max_batch
    if cell.mix["arrivals"] == "backlog":
        cell.mix["depth"] = 2 * c["engine"]["max_batch"]
    else:
        cell.mix["rate_per_s"] = 100.0
    return cell


def fake_trace(t0, t1):
    """Device operations over 80% of the window, and a sleep in the gap."""
    s, e = t0 * 1e9, t1 * 1e9
    w = e - s
    return ([[("fusion.a", s, s + 0.3 * w), ("fusion.b", s + 0.5 * w, e)]],
            [("bench.window", s, e), ("bench.sleep", s + 0.3 * w,
                                      s + 0.5 * w)])


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    # tests write nothing into the checkout
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)


def run(cell, trace=False, seconds=1.0, seed=2 ** 33 + 5, **kw):
    return harness.run_cell(cell, seed, seconds, trace, time.monotonic(),
                            require_tpu=False, calibrate_in_process=True,
                            trace_events=fake_trace if trace else None, **kw)


@pytest.mark.parametrize("workload", CELLS)
def test_command_refuses_the_cpu(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         workload, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("workload", CELLS)
def test_smoke_run_prints_the_contract_line(workload, trace, capsys):
    cell = smoke_cell(workload, max_batch=2)
    out = run(cell, trace=trace)
    harness.print_result(out)
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["count"] == 1 and line["device"]["platform"]
    want = {m["name"] for m in (cell.per_layer if trace
                                else cell.end_to_end)}
    # device peaks are unknown on the CPU, so the shares of a peak are
    # left out there
    want -= {"mfu_busy.poisson", "mfu.backlog"}
    assert want <= set(line["metrics"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert line["window_compiles"] == 0
    check = line["checks"]["latent_rel_err"]
    assert check["value"] < 1e-4 < check["limit"]
    if cell.mix["policy"] != "none":
        check = line["checks"]["schedule_violation"]
        assert 0 <= check["value"] < 1e-4 < check["limit"]
    tail = captured.err.strip().splitlines()[-len(line["checks"]):]
    assert [t.split()[1] for t in tail] == list(line["checks"])


@pytest.mark.parametrize("workload", CELLS)
def test_bfloat16_control_reads_incorrect(workload):
    # all 50 steps: the control's rounding builds up over the sampler
    cell = smoke_cell(workload, max_batch=2)
    cell.conf["sampler"]["steps"] = 50
    keep = {}
    out = run(cell, keep=keep, seconds=0.5)
    assert out["correct"] is True
    low, _, low_skip = harness.reference_check(
        cell, 2 ** 33 + 5, keep["rows"], dtype="bfloat16",
        precision="default")
    values = harness.compared(cell, low, keep["want"], low_skip,
                              keep["curves"])
    ok, checks = harness.judge(cell, values)
    assert ok is False
    assert values["latent_rel_err"] > cell.checks["limits"]["latent_rel_err"]


def test_reference_calibrates_as_the_program_does():
    cell = smoke_cell(CELLS[0], max_batch=2)
    cell.conf["sampler"]["steps"] = 20
    keep = {}
    run(cell, keep=keep, seconds=0.5)
    assert keep["values"]["schedule_violation"] == 0.0
    assert {t: list(v) for t, v in keep["ref_skip"].items()} == \
        {t: list(v) for t, v in keep["skip"].items()}


def test_every_seed_serves_one_model():
    # the seed reorders FFN units and heads: other arrays, the same model,
    # so the same error curves and skip mask for every seed
    import jax
    cell = smoke_cell(CELLS[0])
    ref = harness.reference(cell)
    m, s = cell.conf["model"], cell.conf["sampler"]
    a, b = (ref.make_weights(m, seed) for seed in (2 ** 33 + 5, 12))
    assert any(not np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    labels = harness.calibration_labels(cell)
    noise = ref.key_noise(harness.calibration_key(ref), len(labels),
                          m["latent_shape"])
    alpha, k_max = ref.policy_rule(cell.mix["policy"])
    curves = [ref.error_curves(m, s, w, noise, labels, k_max)
              for w in (a, b)]
    for t in curves[0]:
        np.testing.assert_allclose(curves[0][t], curves[1][t], rtol=1e-4,
                                   atol=1e-6)
    skips = [ref.smoothcache_schedule(c, alpha, k_max) for c in curves]
    assert {t: list(v) for t, v in skips[0].items()} == \
        {t: list(v) for t, v in skips[1].items()}
    x = [np.asarray(ref.sample(m, s, w, noise[:2], labels[:2], skips[0]))
         for w in (a, b)]
    np.testing.assert_allclose(x[0], x[1], rtol=1e-4, atol=1e-5)


def test_loosened_calibration_reads_incorrect(monkeypatch):
    # the program calibrates at a looser alpha than the cell's policy: its
    # mask skips steps the rule computes, and only the schedule shows it
    cell = smoke_cell(CELLS[0], max_batch=2)
    cell.conf["sampler"]["steps"] = 20
    import control
    monkeypatch.setattr(harness, "calibrate_artifact",
                        harness.calibrate_artifact)
    control.plant_calibration_fault("smoothcache:alpha=0.5,k_max=3")
    keep = {}
    out = run(cell, keep=keep, seconds=0.5)
    assert out["correct"] is False
    assert out["checks"]["schedule_violation"]["value"] > \
        cell.checks["limits"]["schedule_violation"]
    assert sum(map(sum, keep["skip"].values())) > \
        sum(map(sum, keep["ref_skip"].values()))


def _state_unchanged(monkeypatch):
    from repro.core.executor import SmoothCacheExecutor

    def advance(self, params, rs, **kw):
        return dataclasses.replace(rs, run_index=rs.run_index + 1)
    monkeypatch.setattr(SmoothCacheExecutor, "advance_run", advance)


def _half_batch(monkeypatch):
    from repro.core.executor import SmoothCacheExecutor
    orig = SmoothCacheExecutor.advance_run

    def advance(self, params, rs, **kw):
        out = orig(self, params, rs, **kw)
        h = out.x.shape[0] // 2
        return dataclasses.replace(out, x=out.x.at[h:2 * h].set(out.x[:h]))
    monkeypatch.setattr(SmoothCacheExecutor, "advance_run", advance)


def _answer_altered(monkeypatch):
    from repro.serve.engine import ServeEngine
    orig = ServeEngine._finish

    def finish(self, fl):
        orig(self, fl)
        rid = fl.mb.rids[0]
        self.results[rid] = -self.results[rid]
    monkeypatch.setattr(ServeEngine, "_finish", finish)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_reads_incorrect(workload, fault, monkeypatch):
    cell = smoke_cell(workload, max_batch=2)
    cell.checks["samples"] = 10 ** 6       # every finished request
    fault(monkeypatch)
    out = run(cell, seconds=0.5)
    assert out["correct"] is False
    assert out["checks"]["latent_rel_err"]["value"] > \
        cell.checks["limits"]["latent_rel_err"]
