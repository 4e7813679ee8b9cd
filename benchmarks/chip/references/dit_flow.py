"""Plain reference of a class-conditional DiT sampled by rectified flow.

No cell of ``BENCHMARK.json`` names it: the harness's CPU tests build a
cell with it at run time, to show that a configuration with another
solver and another kind of conditioning record runs through the harness
as new files only.  It takes the weights, the noise and the forward of
``dit.py`` and replaces the DDIM update by the Euler step of rectified
flow (the model's output is a velocity; x moves from t = 1, noise, to
t = 0 over ``steps`` equal steps, the model seeing t · 1000), with
classifier-free guidance.

A request's conditioning record is ``{"label": class, "priority": p}``:
the label conditions the model, the priority (uniform over the mix's
``priorities`` levels) only orders the engine's queue.  Every branch is
computed; the reference has no SmoothCache calibration.
"""
from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_dit_of_flow", os.path.join(os.path.dirname(__file__), "dit.py"))
dit = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dit)

MODEL_SEED = dit.MODEL_SEED
seed_key = dit.seed_key
make_weights = dit.make_weights
key_noise = dit.key_noise
batch_noise = dit.batch_noise


def condition(m, mix, rng) -> dict:
    """One request's record: a uniform class and a uniform priority."""
    return {"label": int(rng.integers(0, m["num_classes"])),
            "priority": int(rng.integers(0, mix["priorities"]))}


def request_args(record) -> dict:
    return {"label": record["label"], "priority": record["priority"]}


def calibration_records(m, mix, rng, n: int) -> list:
    return [condition(m, mix, rng) for _ in range(n)]


def cond_args(records) -> dict:
    return {"label": jnp.asarray([r["label"] for r in records], jnp.int32)}


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _step(mkey, prec, cfg_scale, params, x, t, dt, labels, null):
    m = dict(mkey)
    x2 = jnp.concatenate([x, x], axis=0)
    lab2 = jnp.concatenate([labels, jnp.full_like(labels, null)], axis=0)
    t2 = jnp.full((x2.shape[0],), t, jnp.float32)
    zeros = jnp.zeros(dit._branch_shape(m, x2.shape[0]), x.dtype)
    pred, _, _ = dit._forward(m, prec, params, x2, t2, lab2, zeros, zeros,
                              False, False)
    c, u = jnp.split(pred.astype(jnp.float32), 2, axis=0)
    v = u + cfg_scale * (c - u)
    return (x.astype(jnp.float32) + dt * v).astype(x.dtype)


def sample(m, sampler, params, noise, records, skip=None, *,
           dtype=jnp.float32, precision="highest"):
    """Rectified-flow Euler with classifier-free guidance from ``noise``
    (R, *latent) for the R ``records``.  Returns the final latents as a
    float32 numpy array."""
    if skip and any(np.any(v) for v in skip.values()):
        raise ValueError("the flow reference computes every branch")
    mkey = dit._model_key(m)
    tgrid = np.linspace(1.0, 0.0, sampler["steps"] + 1).astype(np.float32)
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = jnp.asarray(noise, dtype)
    labels = jnp.asarray([r["label"] for r in records], jnp.int32)
    for s in range(sampler["steps"]):
        x = _step(mkey, precision, float(sampler["cfg_scale"]), params, x,
                  tgrid[s] * np.float32(1000.0), tgrid[s + 1] - tgrid[s],
                  labels, m["num_classes"])
    return np.asarray(x, np.float32)
