"""Per-kernel allclose sweeps vs. the pure-jnp oracles (interpret mode)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import flash_attention_ref, ssd_ref, ssd_sequential_ref
from repro.kernels.ssd import ssd


def _interpret_kernels(monkeypatch):
    """Route the model's kernel calls through interpret mode: the wrappers
    lower natively unless the caller passes ``interpret=True``."""
    from repro.kernels import ops
    for name in ("flash_attention", "ssd"):
        monkeypatch.setattr(ops, name, functools.partial(
            getattr(ops, name), interpret=True))


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("b,l,h,kv,d", [
    (2, 64, 4, 4, 32),
    (2, 64, 4, 1, 32),      # MQA
    (1, 96, 8, 2, 64),      # GQA 4:1
    (1, 128, 16, 8, 64),
    (2, 40, 4, 2, 16),      # non-multiple length → padding path
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(b, l, h, kv, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(l * h + d), 3)
    q = jax.random.normal(ks[0], (b, l, h, d), dtype)
    k = jax.random.normal(ks[1], (b, l, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, l, kv, d), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("causal,window,softcap", [
    (True, None, None),
    (True, 16, None),
    (True, None, 50.0),
    (False, None, None),
    (True, 8, 30.0),
])
def test_flash_attention_masks(causal, window, softcap):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 32))
    k = jax.random.normal(ks[1], (2, 64, 2, 32))
    v = jax.random.normal(ks[2], (2, 64, 2, 32))
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, block_q=16, block_k=16,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                              softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5,
                               rtol=5e-5)


def _on_tpu(monkeypatch):
    """Make the model's selection see a TPU backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _dit_attention():
    from repro.configs import dit_xl
    return dit_xl._block().mixer         # 16 heads of 72, non-causal


@pytest.mark.parametrize("rows", [2, 16])
@pytest.mark.parametrize("l", [256, 1024])
def test_flash_attention_served_form(l, rows):
    """The kernel as the model calls it on the TPU — DiT-XL/2's 16 heads of
    72, non-causal, blocks chosen from the shape, q, k, v in bfloat16 and a
    float32 output — matches ``_sdpa`` at the same operand precision: on q,
    k, v rounded to bfloat16.  The kernel also rounds P to bfloat16 for the
    MXU (unit roundoff 2^-8), so the tolerance is 2^-9 absolute and
    relative."""
    from repro.models import attention as A

    ks = jax.random.split(jax.random.PRNGKey(l + rows), 3)
    q, k, v = (jax.random.normal(kk, (rows, l, 16, 72)).astype(jnp.bfloat16)
               for kk in ks)
    scale = 1.0 / np.sqrt(72)
    out = flash_attention(q, k, v, causal=False, scale=scale,
                          out_dtype=jnp.float32, interpret=True)
    assert out.dtype == jnp.float32
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    bias = jnp.zeros((1, l, l), jnp.float32)
    ref = np.concatenate([      # two rows at a time bounds the score tensor
        np.asarray(A._sdpa(q[r:r + 2], k[r:r + 2], v[r:r + 2], bias,
                           softcap=None, scale=scale))
        for r in range(0, rows, 2)])
    np.testing.assert_allclose(np.asarray(out), ref, atol=2 ** -9,
                               rtol=2 ** -9)


def test_flash_kernel_selection_rule(monkeypatch):
    """On the TPU the kernel replaces ``_sdpa`` for non-causal, unwindowed,
    uncapped, unsharded self-attention at or above FLASH_MIN_TOKENS; any
    other shape, and any other backend, keeps ``_sdpa``."""
    import dataclasses

    from jax.sharding import Mesh

    from repro import shardctx
    from repro.models import attention as A

    spec = _dit_attention()
    n = A.FLASH_MIN_TOKENS
    assert n > 256                       # the 256-token cell stays on _sdpa
    assert not A.takes_flash_kernel(spec, 1024)       # CPU backend
    _on_tpu(monkeypatch)
    assert A.takes_flash_kernel(spec, 1024)           # DiT-XL/2 at 512x512
    assert A.takes_flash_kernel(spec, n)
    assert not A.takes_flash_kernel(spec, n - 1)
    assert not A.takes_flash_kernel(spec, 256)
    for other in (dict(causal=True), dict(cross=True), dict(window=128),
                  dict(logit_softcap=50.0)):
        assert not A.takes_flash_kernel(dataclasses.replace(spec, **other),
                                        1024), other
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with shardctx.use(mesh):
        assert not A.takes_flash_kernel(spec, 1024)


@pytest.mark.parametrize("backend,use_flash,length,taken", [
    ("cpu", False, 1024, False),     # today's path off the TPU
    ("cpu", True, 256, True),        # use_flash still forces the kernel
    ("tpu", False, 1024, True),      # selected by shape
    ("tpu", False, 256, False),      # below the threshold
])
def test_gqa_full_routes_to_kernel(monkeypatch, backend, use_flash, length,
                                   taken):
    from repro.kernels import ops
    from repro.models import attention as A

    calls = []

    def spy(q, k, v, **kw):
        calls.append((q.dtype, kw["out_dtype"]))
        return jnp.zeros(q.shape, kw["out_dtype"])

    monkeypatch.setattr(ops, "flash_attention", spy)
    if backend == "tpu":
        _on_tpu(monkeypatch)
    spec = _dit_attention()
    params = A.init(jax.random.PRNGKey(0), spec, 1152)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, length, 1152))
    A.apply(spec, params, x, use_flash=use_flash)
    assert bool(calls) == taken
    if taken:           # bfloat16 operands on the TPU, float32 out
        mxu = jnp.bfloat16 if backend == "tpu" else jnp.float32
        assert calls == [(mxu, jnp.float32)]


@pytest.mark.parametrize("b,l,h,p,g,n,chunk", [
    (2, 64, 4, 16, 1, 16, 16),
    (1, 96, 8, 32, 2, 32, 32),
    (2, 33, 2, 16, 1, 8, 16),   # padding path
    (1, 16, 2, 8, 2, 8, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel(b, l, h, p, g, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(l + h), 5)
    x = jax.random.normal(ks[0], (b, l, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)) - 1.0)
    a = jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=1.0))
    bb = jax.random.normal(ks[3], (b, l, g, n), dtype)
    cc = jax.random.normal(ks[4], (b, l, g, n), dtype)
    y, hT = ssd(x, dt, a, bb, cc, chunk=chunk, interpret=True)
    ys, hTs = ssd_sequential_ref(x, dt, a, bb, cc)
    tol = dict(atol=1e-1, rtol=1e-1) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ys, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hTs),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=1e-2)


def test_ssd_chunked_oracle_matches_sequential():
    """The model's jnp chunked path is itself validated against the O(L)
    recurrence (two independent oracles)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (2, 64, 4, 16))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 64, 4)))
    a = jnp.exp(jax.random.uniform(ks[2], (4,), minval=0.0, maxval=1.5))
    bb = jax.random.normal(ks[3], (2, 64, 1, 16))
    cc = jax.random.normal(ks[4], (2, 64, 1, 16))
    yc, hc = ssd_ref(x, dt, a, bb, cc, chunk=16)
    ys, hs = ssd_sequential_ref(x, dt, a, bb, cc)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(ys), atol=1e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(hc), np.asarray(hs), atol=1e-4,
                               rtol=1e-3)


def test_ssd_initial_state():
    """h0 threading matches splitting a sequence in two."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (1, 32, 2, 8))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 32, 2)))
    a = jnp.exp(jax.random.uniform(ks[2], (2,), minval=0.0, maxval=1.0))
    bb = jax.random.normal(ks[3], (1, 32, 1, 8))
    cc = jax.random.normal(ks[4], (1, 32, 1, 8))
    y_full, h_full = ssd_ref(x, dt, a, bb, cc, chunk=8)
    y1, h1 = ssd_ref(x[:, :16], dt[:, :16], a, bb[:, :16], cc[:, :16], chunk=8)
    y2, h2 = ssd_ref(x[:, 16:], dt[:, 16:], a, bb[:, 16:], cc[:, 16:],
                     chunk=8, h0=h1)
    np.testing.assert_allclose(np.asarray(y_full[:, 16:]), np.asarray(y2),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(h2), atol=1e-5,
                               rtol=1e-4)


def test_model_forward_with_flash_kernel_matches(monkeypatch):
    """use_flash=True routes attention through the Pallas kernel (interpret
    mode on CPU, asked for explicitly) — must match the jnp path through a
    whole model."""
    from repro import configs
    from repro.models import transformer as T

    _interpret_kernels(monkeypatch)
    cfg = configs.get("qwen3-14b", "smoke")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                              cfg.vocab_size)
    ref, _ = T.forward(cfg, params, toks, use_flash=False)
    out, _ = T.forward(cfg, params, toks, use_flash=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_ssm_model_with_kernel_matches(monkeypatch):
    from repro import configs
    from repro.models import transformer as T

    _interpret_kernels(monkeypatch)
    cfg = configs.get("mamba2-1.3b", "smoke")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    ref, _ = T.forward(cfg, params, toks, use_flash=False)
    out, _ = T.forward(cfg, params, toks, use_flash=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)
