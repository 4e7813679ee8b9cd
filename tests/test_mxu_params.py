"""Weights held in the matrix unit's operand form: ``diffusion.mxu_params``
rounds a diffusion model's weight matrices to bfloat16 once, where the TPU
would round them in every program at the default matmul precision, and
``layers.dense`` multiplies a float32 activation by such a weight without
widening it back.

The model asks ``jax.default_backend()``, which sees the CPU here; tests
that need the TPU's choice patch it to return ``"tpu"``.  The smoke-sized
models' zero-initialised leaves are perturbed so every weight reaches the
output."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, serve
from repro.core import diffusion, solvers
from repro.core.executor import SmoothCacheExecutor
from repro.models import layers as L

#: the leaves that feed only a matrix product, by name
MATRICES = {"wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down", "w", "w1",
            "w2"}


def _model(name, **over):
    cfg = configs.get(name, "smoke").replace(**over)
    params = diffusion.init_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(7),
                                               a.shape), params)
    return cfg, params


def _cond(cfg, rows):
    """The conditioning keywords of ``rows`` rows for ``cfg``'s model."""
    kw = {}
    if cfg.num_classes:
        kw["label"] = jnp.arange(rows, dtype=jnp.int32) % cfg.num_classes
    if cfg.text_dim:
        mask = jnp.arange(cfg.text_len)[None] < jnp.array([[3], [cfg.text_len]])
        kw["memory"] = {"text": jax.random.normal(
            jax.random.PRNGKey(3), (rows, cfg.text_len, cfg.text_dim)),
            "mask": jnp.broadcast_to(mask[:1], (rows, cfg.text_len))}
    elif cfg.cond_dim:
        kw["memory"] = jax.random.normal(jax.random.PRNGKey(3),
                                         (rows, 5, cfg.cond_dim))
    return kw


def _forward(cfg, params):
    rows = 2
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (rows,) + tuple(cfg.latent_shape))
    t = jnp.array([10.0, 700.0])
    return diffusion.apply(cfg, params, x, t, **_cond(cfg, rows))[0]


def _named_leaves(params):
    return [(jax.tree_util.keystr(path), path[-1].key, leaf) for path, leaf
            in jax.tree_util.tree_flatten_with_path(params)[0]]


def _rounded(x, w):
    """``x @ w`` with both operands rounded to bfloat16 and multiplied
    exactly: the one-pass product the TPU runs at its default precision."""
    def r(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.matmul(r(x), r(w), precision=jax.lax.Precision.HIGHEST)


def _cast(cfg, params, monkeypatch):
    with monkeypatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return diffusion.mxu_params(cfg, params)


def test_cast_forward_is_the_rounded_float32_forward(monkeypatch):
    """With the cast taken, the DiT forward equals the float32 forward
    whose every weight product takes both operands rounded to bfloat16
    and multiplies them exactly."""
    cfg, params = _model("dit-xl-256")
    got = _forward(cfg, _cast(cfg, params, monkeypatch))
    monkeypatch.setattr(L, "dense", _rounded)
    want = _forward(cfg, params)
    assert got.dtype == jnp.float32
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err <= 1e-6, err
    # and the rounding is not nothing: the exact float32 forward differs
    monkeypatch.undo()
    exact = _forward(cfg, params)
    assert float(jnp.linalg.norm(exact - want)
                 / jnp.linalg.norm(want)) > 1e-4


# the three paper models at the default precision; Open-Sora's wrapper
# (caption MLP, fps MLP, t_block, cross-attention) with its precision
# taken away
MODELS = [("dit-xl-256", {}), ("stable-audio-open", {}),
          ("opensora-v12", {"matmul_precision": None})]


@pytest.mark.parametrize("name,over", MODELS,
                         ids=[m for m, _ in MODELS])
def test_every_cast_product_is_the_rounded_product(name, over,
                                                   monkeypatch):
    """Each weight product of the cast forward, on its own operands, is
    the product of the operands rounded to bfloat16, and every one reads
    a bfloat16 weight.  (Over a whole forward the two paths' float32 sums
    differ in order, and a last-bit difference can round an operand of a
    later product the other way; the DiT case above has none.)"""
    cfg, params = _model(name, **over)
    cast = _cast(cfg, params, monkeypatch)
    real, seen = L.dense, []

    def checked(x, w):
        y = real(x, w)
        want = _rounded(x, w)
        seen.append((w.dtype, float(jnp.linalg.norm(y - want)
                                    / jnp.linalg.norm(want))))
        return y

    monkeypatch.setattr(L, "dense", checked)
    with jax.disable_jit():
        _forward(cfg, cast)
    assert seen and {d for d, _ in seen} == {jnp.dtype(jnp.bfloat16)}
    assert max(e for _, e in seen) <= 1e-6, seen


@pytest.mark.parametrize("name,over", MODELS,
                         ids=[m for m, _ in MODELS])
def test_cast_takes_matrices_only(name, over, monkeypatch):
    """Weight matrices come back in bfloat16; norm scales, biases, the
    label table, the null caption and the adaLN-single tables stay
    float32."""
    cfg, params = _model(name, **over)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cast = diffusion.mxu_params(cfg, params)
    leaves = _named_leaves(cast)
    assert [p for p, _, _ in leaves] == [p for p, _, _ in
                                         _named_leaves(params)]
    kept = set()
    for path, key, leaf in leaves:
        if key in MATRICES:
            assert leaf.dtype == jnp.bfloat16, path
        else:
            assert leaf.dtype == jnp.float32, path
            kept.add(key)
    assert {"scale", "bias"} <= kept
    assert ("label_embed" in kept) == bool(cfg.num_classes)
    assert ("y_null" in kept) == bool(cfg.text_dim)


@pytest.mark.parametrize("backend,over", [
    ("tpu", {}), ("cpu", {}), ("cpu", {"matmul_precision": None})],
    ids=["opensora-high-tpu", "opensora-high-cpu", "opensora-default-cpu"])
def test_no_cast_under_a_stated_precision_or_off_the_tpu(backend, over,
                                                         monkeypatch):
    """Open-Sora states ``high`` (three passes over float32 weights), and
    the CPU multiplies float32 exactly: the parameters come back as the
    same objects."""
    cfg, params = _model("opensora-v12", **over)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cast = diffusion.mxu_params(cfg, params)
    assert all(a is b for a, b in zip(jax.tree.leaves(params),
                                      jax.tree.leaves(cast)))


def test_dense_keeps_float32_products_as_they_were():
    """Where the weight is not narrower than the activation the product
    is ``x @ w``, so a float32 model's program is unchanged."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 4))

    def body(f, *args):         # the program, without the module's name
        return jax.jit(f).lower(*args).as_text().split("\n", 1)[1]

    assert body(L.dense, x, w) == body(lambda x, w: x @ w, x, w)
    got = L.dense(x, w.astype(jnp.bfloat16))
    assert got.dtype == jnp.float32 and got.shape == (2, 3, 4)
    text = jax.jit(L.dense).lower(x, w.astype(jnp.bfloat16)).as_text()
    # the activation is rounded, the weight is not widened
    assert "bf16" in text and "tensor<8x4xf32>" not in text


@pytest.fixture(scope="module")
def small_dit():
    return _model("dit-xl-256")


def _serve(cfg, params, steps=4, rows=3):
    solver = solvers.ddim(steps)
    ex = SmoothCacheExecutor(cfg, solver, cfg_scale=1.5)
    store = serve.ArtifactStore(cfg, solver, cfg_scale=1.5)
    store.add_policy("static2", "static:n=2")
    eng = serve.ServeEngine(ex, params, store, max_batch=4,
                            clock=serve.VirtualClock())
    eng.submit(*[serve.Request(rid=i, seed=50 + i, policy="static2",
                               label=i % cfg.num_classes, arrival=0.0)
                 for i in range(rows)])
    return eng, eng.run_until_drained()


@pytest.mark.parametrize("forced", [False, True], ids=["cpu", "forced"])
def test_engine_serves_cast_weights(small_dit, forced, monkeypatch):
    """``weights_cast_bytes`` is 0 on the CPU and, with the cast forced,
    the bytes of the leaves it rounded; the engine's programs read the
    cast weights, and its latents stay within bfloat16 rounding of the
    float32 engine's."""
    cfg, params = small_dit
    eng0, want = _serve(cfg, params)
    assert eng0.report()["weights_cast_bytes"] == 0
    if not forced:
        assert eng0.params is params
        return
    from repro.serve import engine as engine_mod
    real = diffusion.mxu_params

    def on_tpu(cfg, params):
        with monkeypatch.context() as mp:
            mp.setattr(jax, "default_backend", lambda: "tpu")
            return real(cfg, params)

    monkeypatch.setattr(engine_mod.diffusion, "mxu_params", on_tpu)
    eng, got = _serve(cfg, params)
    cast = on_tpu(cfg, params)
    expect = sum(b.nbytes for a, b in zip(jax.tree.leaves(params),
                                          jax.tree.leaves(cast))
                 if b.dtype == jnp.bfloat16)
    assert expect > 0
    assert eng.report()["weights_cast_bytes"] == expect
    assert {leaf.dtype for leaf in jax.tree.leaves(eng.params)} == \
        {jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)}
    assert sorted(got) == sorted(want)
    for rid in want:
        a, b = np.asarray(want[rid]), np.asarray(got[rid])
        assert a.dtype == b.dtype == np.float32
        err = np.linalg.norm(a - b) / np.linalg.norm(a)
        assert 0 < err < 1e-2, (rid, err)
