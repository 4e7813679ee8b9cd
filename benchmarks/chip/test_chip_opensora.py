"""CPU tests of the Open-Sora v1.2 cell (``opensora-v12-240p``) at the
smoke size: the program against the plain reference (``references/
stdit3.py``) on the same seeded weights, forward and served latents, the
bfloat16 control outside the check's limit, and the configuration file
against the program's published structure and operation counts.

The smoke cell keeps the reference's T5 memory of 300 × 4096 and shrinks
the rest: 1 spatial and 1 temporal block at d=128, 4 frames of 4 × 5
patches, 10 steps."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import flops
import harness

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
CELL = "opensora-v12-240p.nocache.backlog-captions"
SEED = 2 ** 33 + 17


def smoke_cell():
    cell = harness.load_cell(CELL)
    c = cell.conf
    c["program"]["variant"] = "smoke"
    c["program"]["overrides"] = {"latent_shape": [4, 8, 10, 4],
                                 "text_dim": 4096, "text_len": 300}
    c["model"].update(depth=2, hidden_size=128, num_heads=4, head_dim=32,
                      mlp_hidden=256, latent_shape=[4, 8, 10, 4],
                      cond_dim=64, memory_dim=64)
    for b in c["model"]["branches"]:
        b["count"] = 1
    c["sampler"]["steps"] = 10
    return cell


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    # tests write nothing into the checkout
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)


def fake_trace(t0, t1):
    s, e = t0 * 1e9, t1 * 1e9
    return ([[("fusion.a", s, e)]], [("bench.window", s, e)])


def run(cell, trace=False, **kw):
    return harness.run_cell(cell, SEED, 1.0, trace, time.monotonic(),
                            require_tpu=False, calibrate_in_process=True,
                            trace_events=fake_trace if trace else None, **kw)


def test_forward_matches_the_reference():
    import jax
    import jax.numpy as jnp
    from repro.core import diffusion
    cell = smoke_cell()
    ref = harness.reference(cell)
    m = cell.conf["model"]
    cfg, params = harness.make_params(cell, SEED, ref)
    x = jax.random.normal(jax.random.PRNGKey(1), (2,) + tuple(m["latent_shape"]))
    t = jnp.array([900.0, 250.0])
    records = [{"text_seed": 3, "caption_tokens": 20},
               {"text_seed": 4, "caption_tokens": 150}]
    text, valid = ref._text_and_mask(records)
    with jax.default_matmul_precision("highest"):
        got = diffusion.apply(cfg, params, x, t, memory={
            "text": jnp.asarray(text), "mask": jnp.asarray(valid)})[0]
    want = jax.jit(lambda p: ref._forward(m, "highest", p, x, t,
                                          jnp.asarray(text),
                                          jnp.asarray(valid)))(params)
    # both float32 at highest: the same arithmetic in another order
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err <= 1e-5 and float(jnp.linalg.norm(want)) > 1.0


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout and "needs a TPU" in proc.stderr


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_served_captions_match_the_reference(trace, capsys):
    cell = smoke_cell()
    cell.checks["samples"] = 10 ** 6       # every finished request
    keep = {}
    out = run(cell, trace=trace, keep=keep)
    harness.print_result(out)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["window_compiles"] == 0
    want = {m["name"] for m in (cell.per_layer if trace
                                else cell.end_to_end)} - {"mfu.backlog"}
    assert want <= set(line["metrics"])
    # float32 on the CPU, 10 steps of the same arithmetic: rounding only
    assert line["checks"]["latent_rel_err"]["value"] < 1e-5
    # batches of two requests whose captions differ in length
    lens = {}
    for seeds, _, record in keep["rows"].values():
        lens.setdefault(tuple(seeds), set()).add(record["caption_tokens"])
    assert any(len(v) == 2 for v in lens.values())


def test_bfloat16_control_reads_incorrect():
    # at the cell's own number of steps
    cell = smoke_cell()
    cell.conf["sampler"]["steps"] = harness.load_cell(CELL).conf[
        "sampler"]["steps"]
    keep = {}
    out = run(cell, keep=keep)
    assert out["correct"] is True
    low, _, _ = harness.reference_check(cell, SEED, keep["rows"],
                                        dtype="bfloat16",
                                        precision="default")
    values = harness.compared(cell, low, keep["want"], None, None)
    ok, _ = harness.judge(cell, values)
    assert ok is False
    assert values["latent_rel_err"] > cell.checks["limits"]["latent_rel_err"]


def test_swapped_captions_read_incorrect(monkeypatch):
    # every request served with its batch neighbour's caption
    from repro.serve.engine import ServeEngine
    orig = ServeEngine._batch_cond

    def swapped(self, mb):
        cond = orig(self, mb)
        cond["memory"] = {k: v[::-1] for k, v in cond["memory"].items()}
        return cond
    monkeypatch.setattr(ServeEngine, "_batch_cond", swapped)
    out = run(smoke_cell())
    assert out["correct"] is False


def test_file_is_the_program_and_its_operation_counts():
    from repro.utils import flops as program_flops
    cell = harness.load_cell(CELL)
    m = cell.conf["model"]
    cfg = harness.program_config(cell.conf)
    assert harness.solver_of(cell.conf).name == "rectified_flow"
    assert (flops.frames(m), flops.frame_tokens(m)) == (15, 405)
    want = program_flops.model_macs_by_type(cfg, flops.tokens(m),
                                            cond_len=m["memory_len"],
                                            video_shape=(15, 405))
    assert flops.macs_by_type(m) == pytest.approx(want, rel=1e-12)
    # one row's forward: 6.76 T MACs
    assert flops.row_step_flops(m) / 2 == pytest.approx(6.76e12, rel=5e-3)
    ref = harness.reference(cell)
    assert ref.TEXT_LEN == m["text_len"] and ref.TEXT_DIM == m["text_dim"]
    mix = cell.mix
    rng = np.random.default_rng(5)
    lens = [ref.condition(m, mix, rng)["caption_tokens"] for _ in range(400)]
    assert min(lens) == 16 and max(lens) == 200
