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
import hashlib
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
CELLS = ("dit-xl-256.smoothcache.poisson", "dit-xl-512.nocache.backlog",
         "dit-xl-256.nocache.poisson")


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
    records = harness.calibration_records(cell)
    noise = ref.key_noise(harness.calibration_key(ref), len(records),
                          m["latent_shape"])
    alpha, k_max = ref.policy_rule(cell.mix["policy"])
    curves = [ref.error_curves(m, s, w, noise, records, k_max)
              for w in (a, b)]
    for t in curves[0]:
        np.testing.assert_allclose(curves[0][t], curves[1][t], rtol=1e-4,
                                   atol=1e-6)
    skips = [ref.smoothcache_schedule(c, alpha, k_max) for c in curves]
    assert {t: list(v) for t, v in skips[0].items()} == \
        {t: list(v) for t, v in skips[1].items()}
    x = [np.asarray(ref.sample(m, s, w, noise[:2], records[:2], skips[0]))
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


#: the parent's draws of the cells that were there before the harness took
#: conditioning from the reference, per (cell, seed): the count of
#: requests due in a 50-s window, a digest of them and of the next 16
#: draws (offset, request seed, label), the requests the check samples
#: when all are finished, and the first of them's (request seed, label)
DRAWS_PINNED = {
    (CELLS[0], 1): (180, "8df68b4b3db74ba7", [9, 30, 43, 48, 51, 60, 125, 143],
                    (1634616676, 301)),
    (CELLS[0], 2 ** 33 + 5): (180, "7a8d22d9cbca6b2b",
                              [9, 69, 80, 84, 101, 141, 157, 165],
                              (763905611, 709)),
    (CELLS[0], 2200000601): (180, "a88690516b7c6b72",
                             [10, 55, 66, 67, 99, 129, 131, 155],
                             (620216136, 539)),
    (CELLS[1], 1): (16, "f7bac63248428b0e", [0, 2, 5, 12], (1122378210, 135)),
    (CELLS[1], 2 ** 33 + 5): (16, "8191b7d93434d88b", [5, 6, 10, 14],
                              (329646799, 327)),
    (CELLS[1], 2200000601): (16, "c5c4d2d90d546c14", [0, 9, 10, 13],
                             (837357251, 908)),
}
#: the parent's calibration batch of the 256 cell and its noise key
CALIBRATION_PINNED = ([864, 960, 141, 196, 967, 743, 850, 998, 173, 418],
                      [2572633846, 866171279])


def _digest(draws):
    return hashlib.sha256(json.dumps(
        [[None if o is None else round(o, 9), s, c] for o, s, c in draws]
    ).encode()).hexdigest()[:16]


@pytest.mark.parametrize("workload,seed", sorted(DRAWS_PINNED))
def test_draws_are_the_parents(workload, seed):
    import traffic
    cell = harness.load_cell(workload)
    traf = traffic.Traffic(cell.mix, seed, 50.0, harness.conditioner(cell))
    due = traf.initial()
    more = [(None,) + traf.draw() for _ in range(16)]
    sample = harness.check_sample(cell, seed, list(range(len(due))))
    got = (len(due), _digest(due + more), sample, due[sample[0]][1:])
    assert got == DRAWS_PINNED[workload, seed]


def test_calibration_batch_is_the_parents():
    import jax
    cell = harness.load_cell(CELLS[0])
    key = harness.calibration_key(harness.reference(cell))
    got = (harness.calibration_records(cell),
           np.asarray(jax.random.key_data(key)).tolist())
    assert got == CALIBRATION_PINNED


def test_run_time_cell_with_another_solver_and_records():
    # a configuration the harness has never seen, as new files would give
    # it: rectified flow, and records that carry more than a label
    cell = smoke_cell(CELLS[2], max_batch=2)
    cell.name = "dit-smoke.flow.poisson"
    cell.conf["reference"] = "dit_flow"
    cell.conf["sampler"] = {"solver": "rectified_flow", "steps": 10,
                            "cfg_scale": 1.5}
    cell.mix = dict(cell.mix, priorities=3)
    keep = {}
    out = run(cell, keep=keep, trace=True)
    assert harness.solver_of(cell.conf).name == "rectified_flow"
    assert out["correct"] is True and out["failed"] == 0
    assert out["checks"]["latent_rel_err"]["value"] < 1e-4
    records = [keep["rows"][r][2] for r in sorted(keep["rows"])]
    assert all(set(r) == {"label", "priority"} for r in records)
    assert len({r["priority"] for r in records}) > 1


def test_unknown_solver_is_refused():
    cell = smoke_cell(CELLS[0])
    cell.conf["sampler"]["solver"] = "heun"
    with pytest.raises(harness.BenchError, match="rectified_flow"):
        harness.solver_of(cell.conf)
    cell.conf["sampler"]["solver"] = "rectified_flow"   # takes no betas
    with pytest.raises(harness.BenchError, match="sched"):
        harness.solver_of(cell.conf)
