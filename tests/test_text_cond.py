"""Text-conditioned diffusion: Open-Sora v1.2's STDiT3-XL/2 as options of
the one model (adaLN-single blocks, cross-attention without a norm over a
masked caption memory, the caption MLP and null caption, fps, 2-D
positions, the T2I final layer), Open-Sora's timestep shift, and serving
per-request text memory through the executor and ``ServeEngine``.

The smoke-sized model's zero-initialised leaves are perturbed so that
every branch reaches the output."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, serve
from repro.core import diffusion, solvers
from repro.core.executor import SmoothCacheExecutor
from repro.obs import Tracer

CFG_SCALE = 7.0


@pytest.fixture(scope="module")
def small_sora():
    cfg = configs.get("opensora-v12", "smoke")
    params = diffusion.init_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(7),
                                               a.shape), params)
    return cfg, params


def _memory(cfg, lens, seed=3):
    """A text memory of ``len(lens)`` rows of ``cfg.text_len``, row i's
    first ``lens[i]`` valid and the rest zero."""
    text = np.asarray(jax.random.normal(
        jax.random.PRNGKey(seed), (len(lens), cfg.text_len, cfg.text_dim)))
    mask = np.arange(cfg.text_len)[None] < np.asarray(lens)[:, None]
    return {"text": jnp.asarray(np.where(mask[..., None], text, 0.0),
                                jnp.float32),
            "mask": jnp.asarray(mask)}


def test_stdit3_is_the_published_structure():
    cfg = configs.get("opensora-v12", "full")
    assert cfg.layer_types() == ("s_attn", "s_xattn", "s_ffn", "t_attn",
                                 "t_xattn", "t_ffn")
    (s, t), = [st.unit for st in cfg.stages]
    assert cfg.stages[0].repeat == 28 and cfg.d_model == 1152
    for b in (s, t):
        assert b.adaln == "single" and not b.cross_norm
        assert b.mixer.qk_norm and b.mixer.num_heads == 16
        assert b.mixer.head_dim == 72 and b.ffn.d_ff == 4608
        assert b.ffn.activation == "gelu_tanh" and not b.ffn.gated
    assert (s.mixer.pattern, s.mixer.pos_emb) == ("spatial", "none")
    assert (t.mixer.pattern, t.mixer.pos_emb) == ("temporal", "rope")
    assert (cfg.cond_dim, cfg.text_dim, cfg.text_len, cfg.fps) == \
        (1152, 4096, 300, 24)
    assert cfg.input_memory_dim == 4096
    assert diffusion.token_shape(cfg)[0] == 15 * 405
    p = jax.eval_shape(lambda k: diffusion.init_params(k, cfg),
                       jax.random.PRNGKey(0))
    assert p["t_block"]["w"].shape == (1152, 6 * 1152)
    assert p["final_mod"]["table"].shape == (2, 1152)
    assert p["y_null"].shape == (300, 4096)
    assert p["y_proj"]["w1"].shape == (4096, 1152)
    blk = p["backbone"]["stages"][0][0]
    assert blk["mod"]["table"].shape == (28, 6, 1152)
    assert "norm_x" not in blk and blk["cross"]["wk"].shape[1] == 1152


@pytest.mark.parametrize("name,want", [("opensora-v12", "HIGH"),
                                       ("dit-xl-256", "DEFAULT")])
def test_matmul_precision_reaches_every_product(name, want):
    # Open-Sora's every matrix product, the caption MLP and the attention
    # scores included, at three passes; the DiT's left at the caller's
    cfg = configs.get(name, "smoke")
    params = jax.eval_shape(lambda k: diffusion.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2,) + tuple(cfg.latent_shape), jnp.float32)
    t = jax.ShapeDtypeStruct((2,), jnp.float32)
    kw = {}
    if cfg.text_dim:
        kw["memory"] = _memory(cfg, [3, cfg.text_len])
    else:
        kw["label"] = jnp.zeros((2,), jnp.int32)
    with jax.default_matmul_precision("default"):
        text = jax.jit(lambda p, x, t: diffusion.apply(cfg, p, x, t,
                                                       **kw)[0]
                       ).lower(params, x, t).as_text()
    precisions = re.findall(r"dot_general.*precision = \[(\w+), \w+\]",
                            text)
    assert len(precisions) == text.count("stablehlo.dot_general") >= 10
    assert set(precisions) == {want}


def test_rectified_flow_shift_one_is_todays_grid():
    steps = 30
    a, b = solvers.rectified_flow(steps), solvers.rectified_flow(
        steps, shift=1.0)
    grid = jnp.linspace(1.0, 0.0, steps + 1)
    np.testing.assert_array_equal(np.asarray(a.model_times),
                                  np.asarray(grid[:-1] * 1000.0))
    np.testing.assert_array_equal(np.asarray(a.model_times),
                                  np.asarray(b.model_times))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4))
    v = jax.random.normal(jax.random.PRNGKey(1), (2, 4))
    for s in (0, 17, steps - 1):
        np.testing.assert_array_equal(np.asarray(a.step(x, v, s, {})[0]),
                                      np.asarray(b.step(x, v, s, {})[0]))


def test_rectified_flow_shift_is_open_soras_transform():
    steps, shift = 30, 2.4187227469394874
    solver = solvers.rectified_flow(steps, shift=shift)
    t = np.linspace(1.0, 0.0, steps + 1)
    want = shift * t / (1 + (shift - 1) * t)
    # float32 grid against the float64 formula
    np.testing.assert_allclose(np.asarray(solver.model_times),
                               want[:-1] * 1000.0, rtol=1e-6)
    assert float(solver.model_times[0]) == pytest.approx(1000.0)
    assert np.all(np.diff(np.asarray(solver.model_times)) < 0)
    assert np.all(want[1:-1] > t[1:-1])         # the shift moves time up
    # the Euler steps span the whole path: x moves by -v over all steps
    # (30 float32 additions: rounding only)
    x = jnp.zeros((1, 3))
    v = jnp.ones((1, 3))
    for s in range(steps):
        x = solver.step(x, v, s, {})[0]
    np.testing.assert_allclose(np.asarray(x), -1.0, rtol=1e-6)


def test_tagged_blocks_scope_their_branches(small_sora):
    cfg, params = small_sora
    x = jnp.zeros((1,) + tuple(cfg.latent_shape))
    mem = _memory(cfg, [3])
    hlo = jax.jit(lambda p, x, m: diffusion.apply(
        cfg, p, x, jnp.ones((1,)), memory=m)[0]).lower(
            params, x, mem).compile().as_text()
    names = [n.split("/") for n in re.findall(r'op_name="([^"]*)"', hlo)]
    for tag in ("s", "t"):
        for branch in ("attn", "xattn", "ffn", "adaln"):
            assert any(tag in n and branch in n
                       and n.index(tag) < n.index(branch) for n in names)


def _guided(cfg, params, x, t, memory):
    ex = SmoothCacheExecutor(cfg, solvers.rectified_flow(4),
                             cfg_scale=CFG_SCALE)
    return ex._model_call(params, x, t, None, memory, None, skip=None,
                          collect=False)[0]


def test_cfg_takes_the_null_caption(small_sora):
    cfg, params = small_sora
    x = jax.random.normal(jax.random.PRNGKey(4), (2,) + tuple(cfg.latent_shape))
    t = jnp.array([800.0, 300.0])
    mem = _memory(cfg, [2, 7])
    null = {"text": jnp.broadcast_to(params["y_null"], mem["text"].shape),
            "mask": mem["mask"]}
    c = diffusion.apply(cfg, params, x, t, memory=mem)[0]
    u = diffusion.apply(cfg, params, x, t, memory=null)[0]
    # one doubled call against two separate ones: float32 on the CPU
    # agrees to rounding
    np.testing.assert_allclose(np.asarray(_guided(cfg, params, x, t, mem)),
                               np.asarray(u + CFG_SCALE * (c - u)),
                               rtol=1e-4, atol=1e-5)
    zero = {"text": jnp.zeros_like(mem["text"]), "mask": mem["mask"]}
    u0 = diffusion.apply(cfg, params, x, t, memory=zero)[0]
    assert float(jnp.max(jnp.abs(u0 - u))) > 1e-3


def test_masked_memory_rows_do_not_change_a_latent(small_sora):
    cfg, params = small_sora
    ex = SmoothCacheExecutor(cfg, solvers.rectified_flow(3, shift=2.0),
                             cfg_scale=CFG_SCALE)
    mem = _memory(cfg, [2, 5])
    noisy = dict(mem, text=jnp.where(mem["mask"][..., None], mem["text"],
                                     100.0))
    key = jax.random.PRNGKey(11)
    a = ex.sample_compiled(params, key, 2, memory=mem)
    b = ex.sample_compiled(params, key, 2, memory=noisy)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    longer = dict(mem, mask=jnp.broadcast_to(
        jnp.arange(cfg.text_len)[None] < 6, mem["mask"].shape))
    c = ex.sample_compiled(params, key, 2, memory=longer)
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_split_and_merge_keep_memory_rows(small_sora):
    cfg, params = small_sora
    ex = SmoothCacheExecutor(cfg, solvers.rectified_flow(4),
                             cfg_scale=CFG_SCALE)
    from repro.core import schedule as S
    sched = S.no_cache(cfg.layer_types(), 4)
    plan = ex.plan_for(sched)
    mem = _memory(cfg, [1, 8, 4])
    keys = [serve.batch_key([s]) for s in (5, 6, 7)]
    whole = ex.start_run(params, None, 3, plan=plan, schedule=sched,
                         memory=mem, row_keys=keys)
    parts = ex.split_run(whole, [[2, 0], [1]])
    for part, rows in zip(parts, ([2, 0], [1])):
        for k in ("text", "mask"):
            np.testing.assert_array_equal(np.asarray(part.memory[k]),
                                          np.asarray(mem[k])[rows])
    merged = ex.merge_runs(parts)
    np.testing.assert_array_equal(np.asarray(merged.memory["mask"]),
                                  np.asarray(mem["mask"])[[2, 0, 1]])
    done = []
    for rs in (whole, merged):
        while not rs.done:
            rs = ex.advance_run(params, rs)
        done.append(np.asarray(rs.x))
    np.testing.assert_array_equal(done[1], done[0][[2, 0, 1]])
    kind, arrays, _ = ex.export_run(merged)
    assert set(arrays["memory"]) == {"text", "mask"}


def _text_requests(cfg, lens):
    mem = _memory(cfg, [cfg.text_len] * len(lens))
    return [serve.Request(rid=i, seed=40 + i, policy="none",
                          memory=np.asarray(mem["text"][i]),
                          memory_len=n, arrival=0.0)
            for i, n in enumerate(lens)]


def _text_engine(cfg, params, **kw):
    solver = solvers.rectified_flow(3, shift=2.0)
    ex = SmoothCacheExecutor(cfg, solver, cfg_scale=CFG_SCALE)
    store = serve.ArtifactStore(cfg, solver, cfg_scale=CFG_SCALE)
    store.add_policy("none", "none")
    return serve.ServeEngine(ex, params, store, max_batch=2,
                             clock=serve.VirtualClock(), **kw)


def test_engine_serves_each_request_its_own_caption(small_sora):
    cfg, params = small_sora
    reqs = _text_requests(cfg, [2, 7, 5])
    tracer = Tracer(serve.VirtualClock())
    eng = _text_engine(cfg, params, tracer=tracer)
    eng.submit(*reqs)
    res = eng.run_until_drained()
    assert sorted(res) == [0, 1, 2]
    ex = eng.executor
    for rec in eng.records:
        rows = [reqs[r] for r in rec.rids]
        mask = (np.arange(cfg.text_len)[None]
                < np.asarray([r.memory_len for r in rows])[:, None])
        mem = {"text": jnp.asarray(np.stack([r.memory for r in rows])),
               "mask": jnp.asarray(mask)}
        want = ex.sample_compiled(params, serve.batch_key(rec.seeds),
                                  rec.bucket, memory=mem)
        for j, rid in enumerate(rec.rids):
            np.testing.assert_array_equal(np.asarray(want[j]), res[rid])
    # the batch's conditioning went to the device inside its launch
    evs = tracer.to_chrome_trace()["traceEvents"]
    names = [(e["ph"], e["name"]) for e in evs if e["ph"] in "BE"]
    i = names.index(("B", "serve.cond"))
    assert names[i - 1] == ("B", "serve.launch")
    assert names[i + 1] == ("E", "serve.cond")
    ends = [e for e in evs if e["ph"] == "E" and e["name"] == "serve.cond"]
    # two rows of text (float32) and of mask (bool)
    assert ends[0]["args"]["bytes"] == 2 * cfg.text_len * (
        4 * cfg.text_dim + 1)


def test_journal_refuses_a_text_memory(small_sora, tmp_path):
    cfg, params = small_sora
    eng = _text_engine(cfg, params, journal=str(tmp_path / "j.jsonl"))
    plain = serve.Request(rid=9, seed=1, policy="none", arrival=0.0)
    with pytest.raises(ValueError, match="text memory"):
        eng.submit(plain, *_text_requests(cfg, [3]))
    assert len(eng.queue) == 0 and not eng._rids
