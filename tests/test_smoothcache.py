"""SmoothCache core: schedule generation properties (hypothesis), executor
equivalence, calibration error-curve invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import configs
from repro.core import calibration, diffusion, schedule as S, solvers
from repro.core.executor import SmoothCacheExecutor


# ---------------------------------------------------------------------------
# Schedule properties
# ---------------------------------------------------------------------------

def _curves(err_rows, k_max=3):
    """Build an (S, K+1) curve array from per-step base errors, err at lag k
    = base * k (monotone in k)."""
    s = len(err_rows)
    out = np.full((s, k_max + 1), np.nan)
    out[:, 0] = 0.0
    for i in range(s):
        for k in range(1, min(k_max, i) + 1):
            out[i, k] = err_rows[i] * k
    return {"attn": out}


@given(st.lists(st.floats(0.0, 1.0), min_size=2, max_size=64),
       st.floats(0.01, 2.0), st.integers(1, 5))
@settings(max_examples=200, deadline=None)
def test_schedule_invariants(rows, alpha, k_max):
    sch = S.smoothcache(_curves(rows, k_max), alpha, k_max)
    v = sch.skip["attn"]
    assert not v[0], "step 0 must always compute"
    # no skip-run longer than k_max
    run = 0
    for b in v:
        run = run + 1 if b else 0
        assert run <= k_max
    assert sch.num_steps == len(rows)


@given(st.lists(st.floats(0.0, 1.0), min_size=2, max_size=48),
       st.floats(0.0, 1.0), st.floats(0.0, 1.0))
@settings(max_examples=100, deadline=None)
def test_schedule_monotone_in_alpha(rows, a1, a2):
    """With lag-monotone error curves, a larger α never computes more."""
    lo, hi = min(a1, a2), max(a1, a2)
    c = _curves(rows)
    s_lo = S.smoothcache(c, lo)
    s_hi = S.smoothcache(c, hi)
    assert s_hi.skip["attn"].sum() >= s_lo.skip["attn"].sum()


def test_alpha_zero_never_skips():
    rows = [0.5] * 20
    sch = S.smoothcache(_curves(rows), 0.0)
    assert sch.skip["attn"].sum() == 0


def test_alpha_huge_skips_max():
    rows = [0.1] * 21
    sch = S.smoothcache(_curves(rows), 1e9, k_max=3)
    # compute every 4th step: steps 0,4,8,... → 16 skips of 21 steps
    assert sch.skip["attn"].sum() == 15 or sch.skip["attn"].sum() == 16


def test_fora_uniform():
    sch = S.fora(["attn", "ffn"], 50, 2)
    for t in ("attn", "ffn"):
        assert not sch.skip[t][0]
        assert sch.skip[t][1::2].all()
        assert not sch.skip[t][2::2].any()


def test_alpha_for_budget_search():
    rng = np.random.RandomState(0)
    rows = list(rng.uniform(0.05, 0.5, size=50))
    curves = _curves(rows)
    alpha = S.alpha_for_budget(curves, target_compute_fraction=0.6)
    sch = S.smoothcache(curves, alpha)
    assert abs(sch.compute_fraction("attn") - 0.6) < 0.15


def test_schedule_json_roundtrip():
    sch = S.fora(["attn"], 10, 3)
    sch2 = S.Schedule.from_json(sch.to_json())
    assert (sch2.skip["attn"] == sch.skip["attn"]).all()
    assert sch2.num_steps == 10


# ---------------------------------------------------------------------------
# Executor equivalence + calibration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_dit():
    cfg = configs.get("dit-xl-256", "smoke")
    params = diffusion.init_params(jax.random.PRNGKey(0), cfg)
    # perturb zero-inits so branches matter
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(7), a.shape),
        params)
    return cfg, params


def test_noskip_schedule_equals_plain(small_dit):
    cfg, params = small_dit
    ex = SmoothCacheExecutor(cfg, solvers.ddim(6), cfg_scale=1.5)
    label = jnp.zeros((2,), jnp.int32)
    sch = S.no_cache(cfg.layer_types(), 6)
    x1 = ex.sample(params, jax.random.PRNGKey(1), 2, schedule=sch, label=label)
    x2 = ex.sample(params, jax.random.PRNGKey(1), 2, schedule=None, label=label)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))


def test_cached_sampling_close_but_cheaper(small_dit):
    cfg, params = small_dit
    ex = SmoothCacheExecutor(cfg, solvers.ddim(8), cfg_scale=1.5)
    label = jnp.zeros((2,), jnp.int32)
    curves, _, _ = calibration.calibrate(
        ex, params, jax.random.PRNGKey(1), 2, cond_args={"label": label})
    sch = S.smoothcache(curves, alpha=0.5, k_max=3)
    assert any(v.any() for v in sch.skip.values()), "expect some skips"
    xc = ex.sample(params, jax.random.PRNGKey(2), 2, schedule=sch, label=label)
    xp = ex.sample(params, jax.random.PRNGKey(2), 2, schedule=None, label=label)
    assert bool(jnp.all(jnp.isfinite(xc)))
    rel = float(jnp.linalg.norm(xc - xp) / (jnp.linalg.norm(xp) + 1e-9))
    assert rel < 0.5, f"cached output diverged wildly: {rel}"


def test_calibration_curve_invariants(small_dit):
    cfg, params = small_dit
    ex = SmoothCacheExecutor(cfg, solvers.ddim(6))
    curves, per_sample, _ = calibration.calibrate(
        ex, params, jax.random.PRNGKey(3), 3,
        cond_args={"label": jnp.zeros((3,), jnp.int32)})
    for t, c in curves.items():
        assert c.shape == (6, 4)
        assert np.allclose(c[:, 0], 0.0)          # lag 0 → zero error
        assert np.isnan(c[0, 1])                  # no lag-1 at step 0
        valid = c[1:, 1]
        assert np.all(valid[np.isfinite(valid)] >= 0)
        assert per_sample[t].shape == (3, 6, 4)


@pytest.mark.parametrize("rows", [None, 2])
def test_error_curve_stream_matches_loop_reference(rows):
    """The streaming curves (device-side errors, a k_max-step window) equal
    the per-layer host loop over every stored step, to float32 rounding;
    ``rows`` keeps the conditioned half of a CFG-doubled batch."""
    cfg = configs.get("dit-xl-256", "smoke")
    repeat = cfg.stages[0].repeat
    names = cfg.stages[0].unit[0].branch_names()
    types = cfg.stages[0].unit[0].branch_types()
    k_max, steps, batch = 3, 6, 4
    rng = np.random.RandomState(0)
    trees = [[({n: rng.randn(repeat, batch, 5, 3).astype(np.float32)
                for n in names},)] for _ in range(steps)]
    stream = calibration.ErrorCurveStream(cfg, k_max, rows=rows)
    for tree in trees:
        stream.push(jax.tree.map(jnp.asarray, tree))
        assert len(stream._window) <= k_max
    curves, per_sample = stream.curves()

    keep = batch if rows is None else rows
    for n, t in zip(names, types):
        want = np.full((keep, steps, k_max + 1), np.nan)
        want[:, :, 0] = 0.0
        for s in range(steps):
            for k in range(1, min(k_max, s) + 1):
                errs = []
                for r in range(repeat):
                    cur = trees[s][0][0][n][r, :keep].astype(np.float64)
                    prev = trees[s - k][0][0][n][r, :keep].astype(np.float64)
                    errs.append(np.abs(cur - prev).sum(axis=(1, 2))
                                / np.abs(cur).sum(axis=(1, 2)))
                want[:, s, k] = np.mean(errs, axis=0)
        np.testing.assert_allclose(per_sample[t], want, rtol=1e-5)
        np.testing.assert_allclose(curves[t], np.mean(want, axis=0),
                                   rtol=1e-5)


def test_solver_step_counts(small_dit):
    cfg, params = small_dit
    for mk in (solvers.ddim(5), solvers.rectified_flow(5),
               solvers.dpmpp_3m_sde(5)):
        ex = SmoothCacheExecutor(cfg, mk)
        x = ex.sample(params, jax.random.PRNGKey(0), 1,
                      label=jnp.zeros((1,), jnp.int32))
        assert x.shape == (1,) + tuple(cfg.latent_shape)
        assert bool(jnp.all(jnp.isfinite(x)))


def test_distinct_masks_bounded(small_dit):
    """Compiled-variant count is bounded by 2^|types| (graph-compilation
    compatibility claim of the paper §2.2)."""
    cfg, params = small_dit
    types = cfg.layer_types()
    rng = np.random.RandomState(0)
    sch = S.Schedule(
        {t: np.r_[False, rng.rand(9) < 0.5] for t in types}, 10)
    assert len(sch.distinct_masks()) <= 2 ** len(types)
