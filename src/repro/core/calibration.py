"""SmoothCache calibration: run uncached sampling trajectories, record every
layer's pre-residual branch output at every step, and build the per-type L1
relative error curves of paper Fig. 2 / Eq. 4.

The error at step s for lag k is

    err[t][s, k] = mean_{j ∈ layers of type t}
                   ||L̃_{j}(s) − L̃_{j}(s−k)||₁ / ||L̃_{j}(s)||₁

averaged over calibration samples; per-sample curves are also returned so
the Fig. 2 confidence intervals can be reproduced.

Under classifier-free guidance the executor doubles the batch to
``[cond; uncond]``; calibration keeps only the **conditioned half**, so
per-sample curves have leading dim ``calib_batch`` (not ``2*calib_batch``)
and the mean curves never mix guided and unguided error statistics.

For input-adaptive policies (:class:`repro.cache.AdaptivePolicy`) the same
pass additionally records a cheap per-step **proxy signal** — the relative
L1 change of the model input (the latent) between consecutive steps, the
exact quantity the runtime rule can compute before each model call — and
:func:`fit_proxy_map` fits a per-type linear proxy→error mapping that the
runtime accumulates against its threshold τ.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig


def _branches_by_type(cfg: ModelConfig, branch_tree, rows: Optional[int]
                      ) -> Dict[str, List[jax.Array]]:
    """Group the per-stage scan-stacked branch outputs by layer type:
    {type: [(repeat, B, ...) arrays] in depth order}, keeping the leading
    ``rows`` batch rows when given.  Arrays stay where they are (on device
    for a sampling pass)."""
    out: Dict[str, List[jax.Array]] = {}
    for si, st in enumerate(cfg.stages):
        stage_branches = branch_tree[si]          # tuple per block in unit
        for bi, b in enumerate(st.unit):
            bo = stage_branches[bi]
            for name, t in zip(b.branch_names(), b.branch_types()):
                if bo is None or name not in bo:
                    continue
                arr = bo[name]                    # (repeat, B, N, d)
                out.setdefault(t, []).append(
                    arr if rows is None else arr[:, :rows])
    return out


@jax.jit
def _pair_errors(cur: Dict[str, List[jax.Array]],
                 prev: Dict[str, List[jax.Array]]) -> Dict[str, jax.Array]:
    """||cur − prev||₁ / ||cur||₁ per layer and sample, reduced over every
    axis but (layer, batch): {type: (layers, B)}."""
    out = {}
    for t in cur:
        errs = []
        for c, p in zip(cur[t], prev[t]):
            ax = tuple(range(2, c.ndim))
            num = jnp.sum(jnp.abs(c - p), axis=ax)
            den = jnp.sum(jnp.abs(c), axis=ax) + 1e-12
            errs.append(num / den)
        out[t] = jnp.concatenate(errs, axis=0)
    return out


class ErrorCurveStream:
    """Streaming builder of the Fig. 2 error curves.  :meth:`push` takes
    one step's branch tree; only the last ``k_max`` steps are kept, where
    the tree lives (on device for a sampling pass), and each push reads
    back just the (layers, B) error matrices.  Memory is therefore
    independent of the step count — holding every step's branch outputs
    on the host would take ~33 GB for 10 DiT-XL samples over 50 steps.

    ``rows`` keeps the leading batch rows only (the conditioned half of a
    CFG-doubled batch)."""

    def __init__(self, cfg: ModelConfig, k_max: int = 3,
                 rows: Optional[int] = None):
        self.cfg = cfg
        self.k_max = k_max
        self.rows = rows
        self._window: List[Dict[str, List[jax.Array]]] = []
        self._bsz = 0
        #: per step: {type: {k: (B,) layer-mean error}}, 1 ≤ k ≤ min(k_max, s)
        self._errs: List[Dict[str, Dict[int, np.ndarray]]] = []

    def push(self, branch_tree) -> None:
        cur = _branches_by_type(self.cfg, branch_tree, self.rows)
        self._bsz = next(iter(cur.values()))[0].shape[1]
        errs: Dict[str, Dict[int, np.ndarray]] = {t: {} for t in cur}
        for k, prev in enumerate(reversed(self._window), start=1):
            for t, e in _pair_errors(cur, prev).items():
                errs[t][k] = np.mean(np.asarray(e), axis=0)   # layer mean
        self._errs.append(errs)
        self._window = (self._window + [cur])[-self.k_max:]

    def curves(self) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
        """(mean_curves {t: (S, K+1)}, per_sample {t: (B, S, K+1)}).
        Entries with k > s are NaN; the k=0 column is 0."""
        s_total = len(self._errs)
        types = sorted(self._errs[0])
        bsz, k_max = self._bsz, self.k_max
        mean_curves = {t: np.full((s_total, k_max + 1), np.nan)
                       for t in types}
        per_sample = {t: np.full((bsz, s_total, k_max + 1), np.nan)
                      for t in types}
        for t in types:
            for s, errs in enumerate(self._errs):
                per_sample[t][:, s, 0] = 0.0
                mean_curves[t][s, 0] = 0.0
                for k, e in errs[t].items():
                    per_sample[t][:, s, k] = e
                    mean_curves[t][s, k] = float(np.mean(e))
        return mean_curves, per_sample


# ---------------------------------------------------------------------------
# Proxy signal (input-adaptive policies)
# ---------------------------------------------------------------------------

def rel_l1_change(cur, prev):
    """||cur − prev||₁ / ||prev||₁ over the whole tensor — THE proxy
    formula, written backend-agnostically (``__abs__``/``.sum()``) so the
    calibration pass (numpy, float64) and the executor's jitted runtime
    proxy (jax, device dtype) provably compute the same signal from one
    definition."""
    return abs(cur - prev).sum() / (abs(prev).sum() + 1e-12)


def rel_l1_change_rows(cur, prev):
    """Per-sample :func:`rel_l1_change`: reduce over every axis but the
    leading batch axis, returning one proxy signal per row.  Same
    arithmetic as the whole-tensor form restricted to each row, so a
    batch-1 run and row i of a batch-B run see the same signal — the
    per-sample decision analogue of the executor's per-row bitwise
    latent stability."""
    axes = tuple(range(1, cur.ndim))
    return (abs(cur - prev).sum(axis=axes)
            / (abs(prev).sum(axis=axes) + 1e-12))


def runtime_rule(proxy, acc, lag, a, b, tau, k_max, force_compute=False):
    """One evaluation of the adaptive reuse rule, vectorized over layer
    types: estimate the per-type lag-1 error from the proxy signal
    (``max(a·proxy + b, 0)`` — clamped, so an adversarial fit can never
    shrink the accumulator while skipping), skip a type while the error
    accumulated since its last compute stays under ``tau`` and the cache
    age stays ≤ ``k_max``, and return the updated accumulator/lag state.

    THE decision arithmetic: the executor's fused sampling program inlines
    it into its ``fori_loop`` body and the host-dispatch path jits it
    standalone, so fused and host decision sequences agree bit-for-bit.
    ``acc``/``a``/``b`` are float32, ``lag`` int32; ``force_compute``
    (step 0, empty cache) overrides every skip."""
    delta = jnp.maximum(a * proxy + b, 0.0)
    skip = ((lag + 1 <= k_max) & (acc + delta < tau)
            & jnp.logical_not(force_compute))
    acc = jnp.where(skip, acc + delta, 0.0)
    lag = jnp.where(skip, lag + 1, 0)
    return skip, acc, lag


def batch_rule(proxy_rows, acc, lag, a, b, tau, k_max, force_compute=False):
    """Per-sample adaptive rule over a batch: each row evaluates
    :func:`runtime_rule` arithmetic against its OWN ``(B, T)``
    accumulator/lag state from its own proxy signal, yielding the
    per-row *desired* skip bits ``want (B, T)``; the batch *realizes*
    their AND (``realized (T,)`` — any row needing a type's compute
    forces the whole batch to compute it, since one model call refreshes
    that type's cache for every row).

    acc/lag update against the REALIZED bits: a forced compute refreshes
    the cache for all rows, so every row's accumulator for that type
    resets — each row's state tracks the error actually accrued in its
    cache entries, not a counterfactual solo trajectory.  A batch of one
    therefore realizes exactly its solo trajectory, which is what makes
    split/merge and boundary regroup deterministic per row."""
    delta = jnp.maximum(a * proxy_rows[:, None] + b[None, :], 0.0)  # (B, T)
    want = ((lag + 1 <= k_max) & (acc + delta < tau)
            & jnp.logical_not(force_compute))
    realized = jnp.all(want, axis=0)                                # (T,)
    acc = jnp.where(realized[None, :], acc + delta, 0.0)
    lag = jnp.where(realized[None, :], lag + 1, 0)
    return want, realized, acc, lag


def proxy_signal(cur, prev) -> float:
    """Relative L1 change of the model input between consecutive steps —
    one scalar per step over the whole batch tensor.  This is the runtime
    decision signal: it needs only the latents, so it is computable
    *before* the model call it gates."""
    return float(rel_l1_change(np.asarray(cur, np.float64),
                               np.asarray(prev, np.float64)))


def proxies_from_inputs(inputs: List[np.ndarray]) -> np.ndarray:
    """Per-step proxy signals from the model-input trajectory.
    ``proxies[0]`` is NaN (no previous input); ``proxies[s]`` compares the
    inputs of steps s and s−1."""
    out = np.full(len(inputs), np.nan)
    for s in range(1, len(inputs)):
        out[s] = proxy_signal(inputs[s], inputs[s - 1])
    return out


@dataclasses.dataclass(frozen=True)
class ProxyMap:
    """Fitted per-type linear map from the proxy signal to the one-step
    (lag-1) relative output error: ``est_t(p) = max(a_t·p + b_t, 0)``.

    The clamp at zero is load-bearing: an adversarial fit (negative slope
    or intercept) would otherwise yield negative per-type estimates, so the
    accumulator could *decrease* while a type keeps skipping and postpone
    its recompute indefinitely.  Both the scalar :meth:`est` and the device
    rule (:func:`runtime_rule` over :meth:`stacked` coefficients) clamp.

    The runtime rule accumulates ``est_t(proxy_s)`` over consecutive
    reuse steps and recomputes type ``t`` once the sum would cross τ —
    TeaCache-style, but with the mapping *fitted during calibration* and
    shipped in the :class:`~repro.cache.artifact.CacheArtifact` so serving
    never recalibrates."""
    coeffs: Dict[str, Tuple[float, float]]   # type → (a, b)
    mean_proxy: float = float("nan")         # calibration-mean proxy (diag)

    def est(self, t: str, proxy: float) -> float:
        a, b = self.coeffs[t]
        return max(a * float(proxy) + b, 0.0)

    def stacked(self, types) -> Tuple[np.ndarray, np.ndarray]:
        """Device representation: per-type ``(a, b)`` coefficients stacked
        into two float32 arrays in the given type order — what the fused
        sampling program (and the host decide step, for parity) evaluates
        as one vectorized ``max(a·p + b, 0)``."""
        missing = [t for t in types if t not in self.coeffs]
        if missing:
            raise KeyError(f"proxy_map lacks coefficients for {missing}; "
                           f"have {self.types()}")
        a = np.asarray([self.coeffs[t][0] for t in types], np.float32)
        b = np.asarray([self.coeffs[t][1] for t in types], np.float32)
        return a, b

    def types(self):
        return sorted(self.coeffs)

    def to_jsonable(self) -> Dict:
        return {"coeffs": {t: [float(a), float(b)]
                           for t, (a, b) in sorted(self.coeffs.items())},
                "mean_proxy": None if np.isnan(self.mean_proxy)
                else float(self.mean_proxy)}

    @staticmethod
    def from_jsonable(d: Mapping) -> "ProxyMap":
        mp = d.get("mean_proxy")
        return ProxyMap(
            coeffs={t: (float(a), float(b))
                    for t, (a, b) in d["coeffs"].items()},
            mean_proxy=float("nan") if mp is None else float(mp))


def fit_proxy_map(curves: Mapping[str, np.ndarray],
                  proxies: np.ndarray) -> ProxyMap:
    """Least-squares fit of the lag-1 error column against the proxy
    signal, per layer type.  Degenerate data (fewer than two finite points,
    or a constant proxy) falls back to the constant map ``b = mean(err)``,
    which still yields a sensible accumulate-and-threshold rule."""
    coeffs = {}
    for t, err in curves.items():
        xs = np.asarray(proxies, np.float64)
        ys = np.asarray(err[:, 1], np.float64)       # lag-1 column
        ok = np.isfinite(xs) & np.isfinite(ys)
        xs, ys = xs[ok], ys[ok]
        if xs.size >= 2 and np.ptp(xs) > 1e-12:
            a, b = np.polyfit(xs, ys, 1)
        else:
            a, b = 0.0, float(np.mean(ys)) if ys.size else 0.0
        coeffs[t] = (float(a), float(b))
    finite = np.asarray(proxies)[np.isfinite(proxies)]
    return ProxyMap(coeffs=coeffs,
                    mean_proxy=float(np.mean(finite)) if finite.size
                    else float("nan"))


# ---------------------------------------------------------------------------
# Calibration passes
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CalibrationRecord:
    """Everything one uncached calibration pass produces."""
    curves: Dict[str, np.ndarray]        # {type: (S, K+1)} mean curves
    per_sample: Dict[str, np.ndarray]    # {type: (calib_batch, S, K+1)}
    proxies: np.ndarray                  # (S,) per-step proxy signal
    proxy_map: ProxyMap                  # fitted proxy→lag-1-error map
    x0: np.ndarray                       # final denoised latents
    cfg_halved: bool                     # True → cond half of a CFG batch


def calibrate_record(executor, params, key, batch: int, *, cond_args=None,
                     k_max: int = 3) -> CalibrationRecord:
    """Run one uncached sampling pass with ``batch`` calibration samples
    (paper uses 10), recording branch outputs *and* the per-step proxy
    signal, and fit the proxy→error map.

    Under CFG the executor doubles the batch to ``[cond; uncond]``; only
    the conditioned half enters the curves (``per_sample`` leading dim is
    exactly ``batch``)."""
    cond_args = cond_args or {}
    cfg_halved = executor.cfg_scale is not None
    # keep the conditioned half of the [cond; uncond] doubled batch
    stream = ErrorCurveStream(executor.cfg, k_max,
                              rows=batch if cfg_halved else None)

    x_init, _ = executor.initial_latent(key, batch)
    x0, traj = executor.sample(params, key, batch, schedule=None,
                               collect_hook=lambda s, tree: stream.push(tree),
                               return_trajectory=True, **cond_args)
    # model input at step s: the initial noise for s=0, else the latent
    # produced by step s−1
    inputs = [np.asarray(x_init)] + [np.asarray(x) for x in traj[:-1]]
    proxies = proxies_from_inputs(inputs)
    curves, per_sample = stream.curves()
    return CalibrationRecord(
        curves=curves, per_sample=per_sample, proxies=proxies,
        proxy_map=fit_proxy_map(curves, proxies), x0=np.asarray(x0),
        cfg_halved=cfg_halved)


def calibrate(executor, params, key, batch: int, *, cond_args=None,
              k_max: int = 3):
    """Back-compat wrapper over :func:`calibrate_record`:
    returns (mean_curves, per_sample, trajectory x₀)."""
    rec = calibrate_record(executor, params, key, batch,
                           cond_args=cond_args, k_max=k_max)
    return rec.curves, rec.per_sample, rec.x0
