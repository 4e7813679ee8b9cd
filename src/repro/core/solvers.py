"""Diffusion samplers used by the paper: DDIM (DiT-XL), DPM-Solver++(3M) SDE
(Stable Audio Open) and Rectified-Flow Euler (OpenSora).

All solvers are expressed as a pair:

    timesteps(num_steps)         → per-step model times t_s (static)
    step(x, model_out, s, state) → (x_next, state)

so the SmoothCache executor owns the model-call loop and can substitute
cached layer outputs at any step.  The model interface is ε-prediction for
DDIM/DPM++ (VP schedule) and velocity for rectified flow.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import diffusion


@dataclasses.dataclass
class Solver:
    name: str
    num_steps: int
    model_times: jnp.ndarray                 # (S,) times fed to the model
    init_state: Callable[[], dict]
    step: Callable                           # (x, model_out, s, state, key)
    stochastic: bool = False
    # step() accepts a *traced* step index and a structure-stable state, so
    # the executor may run it inside lax.fori_loop / lax.scan segments
    scannable: bool = True

    def __post_init__(self):
        # every path's solver step runs under one trace scope
        step = self.step

        def scoped(*args, **kwargs):
            with jax.named_scope("solver"):
                return step(*args, **kwargs)

        self.step = scoped


# ---------------------------------------------------------------------------
# DDIM (η = 0) on the VP schedule — the paper's DiT-XL protocol
# ---------------------------------------------------------------------------

def ddim(num_steps: int, sched=None, num_train_steps: int = 1000) -> Solver:
    sched = sched or diffusion.vp_schedule(num_train_steps)
    ts = jnp.linspace(num_train_steps - 1, 0, num_steps).round().astype(jnp.int32)
    ab = sched["alpha_bar"][ts]                                  # (S,)
    ab_next = jnp.concatenate([sched["alpha_bar"][ts[1:]], jnp.ones((1,))])

    def step(x, eps, s, state, key=None):
        a, an = ab[s], ab_next[s]
        shape = (1,) * x.ndim
        x0 = (x - jnp.sqrt(1 - a) * eps) / jnp.sqrt(a)
        x = jnp.sqrt(an) * x0 + jnp.sqrt(1 - an) * eps
        return x, state

    return Solver("ddim", num_steps, ts.astype(jnp.float32),
                  lambda: {}, step)


# ---------------------------------------------------------------------------
# DPM-Solver++(3M) SDE — the paper's Stable Audio Open protocol
# (k-diffusion formulation on σ = sqrt(1-ᾱ)/sqrt(ᾱ); model stays ε-pred,
#  converted to x̂₀ internally)
# ---------------------------------------------------------------------------

def dpmpp_3m_sde(num_steps: int, sched=None, num_train_steps: int = 1000,
                 eta: float = 1.0) -> Solver:
    sched = sched or diffusion.vp_schedule(num_train_steps)
    ts = jnp.linspace(num_train_steps - 1, 1, num_steps).round().astype(jnp.int32)
    ab = sched["alpha_bar"][ts]
    sigmas = jnp.sqrt((1 - ab) / ab)                             # VE view
    sigmas = jnp.concatenate([sigmas, jnp.zeros((1,))])

    def init_state():
        return {"d1": None, "d2": None, "h1": None, "h2": None}

    def step(x_vp, eps, s, state, key=None):
        # VP → VE coordinates (s is a static python step index)
        a = ab[s]
        x = x_vp / jnp.sqrt(a)
        sig, sig_next = sigmas[s], sigmas[s + 1]
        denoised = x - sig * eps           # x̂₀ in VE coords
        if s == num_steps - 1:             # final step: σ→0, x = x̂₀
            x_new = denoised
        else:
            t, snext = -jnp.log(sig), -jnp.log(sig_next)
            h = snext - t
            h_eta = h * (eta + 1.0)
            x_new = jnp.exp(-h_eta) * x + (-jnp.expm1(-h_eta)) * denoised
            if state["d2"] is not None:
                r0, r1 = state["h1"] / h, state["h2"] / h
                d1_0 = (denoised - state["d1"]) / r0
                d1_1 = (state["d1"] - state["d2"]) / r1
                d1 = d1_0 + (d1_0 - d1_1) * r0 / (r0 + r1)
                d2 = (d1_0 - d1_1) / (r0 + r1)
                phi2 = jnp.expm1(-h_eta) / h_eta + 1.0
                phi3 = phi2 / h_eta - 0.5
                x_new = x_new + phi2 * d1 - phi3 * d2
            elif state["d1"] is not None:
                r = state["h1"] / h
                d = (denoised - state["d1"]) / r
                phi2 = jnp.expm1(-h_eta) / h_eta + 1.0
                x_new = x_new + phi2 * d
            if eta > 0 and key is not None:
                noise = jax.random.normal(key, x.shape, x.dtype)
                x_new = x_new + noise * sig_next * jnp.sqrt(
                    -jnp.expm1(-2.0 * h * eta))
            state = {"d1": denoised, "d2": state["d1"],
                     "h1": h, "h2": state["h1"]}
        # back to VP coordinates at the *next* sigma level
        ab_next = 1.0 / (1.0 + sigmas[s + 1] ** 2)
        return x_new * jnp.sqrt(ab_next), state

    # not scannable: step() branches in Python on the step index (final-step
    # σ→0 shortcut) and the multistep state changes *structure* (None → array)
    # over the first three steps
    return Solver("dpmpp_3m_sde", num_steps, ts.astype(jnp.float32),
                  init_state, step, stochastic=True, scannable=False)


# ---------------------------------------------------------------------------
# Rectified-Flow Euler — the paper's OpenSora protocol
# (model predicts v = ε − x₀; integrate x from t=1 (noise) to t=0)
# ---------------------------------------------------------------------------

def rectified_flow(num_steps: int, num_train_steps: int = 1000,
                   shift: float = 1.0) -> Solver:
    """Euler over ``num_steps`` steps of the grid t = 1 − i/num_steps,
    moved by Open-Sora's timestep transform t' = shift·t / (1 + (shift −
    1)·t) (its ``use_timestep_transform``; Open-Sora v1.2 takes shift =
    sqrt(H·W / 512²) · sqrt(latent frames)); shift = 1 leaves the grid
    as it is.  The model sees t' · 1000, as during training."""
    tgrid = jnp.linspace(1.0, 0.0, num_steps + 1)
    if shift != 1.0:
        tgrid = shift * tgrid / (1.0 + (shift - 1.0) * tgrid)

    def step(x, v, s, state, key=None):
        dt = tgrid[s + 1] - tgrid[s]           # negative
        return x + dt * v, state

    return Solver("rectified_flow", num_steps, tgrid[:-1] * 1000.0,
                  lambda: {}, step)


SOLVERS = {
    "ddim": ddim,
    "dpmpp_3m_sde": dpmpp_3m_sde,
    "rectified_flow": rectified_flow,
}
