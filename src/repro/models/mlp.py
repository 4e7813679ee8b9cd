"""Feed-forward layers: (gated) MLPs."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import MLPSpec
from repro.models import layers as L


def init(key, spec: MLPSpec, d_model: int, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {"w_up": L.dense_init(ks[0], d_model, spec.d_ff, dtype),
         "w_down": L.dense_init(ks[1], spec.d_ff, d_model, dtype)}
    if spec.gated:
        p["w_gate"] = L.dense_init(ks[2], d_model, spec.d_ff, dtype)
    return p


def apply(spec: MLPSpec, params, x):
    act = L.activation(spec.activation)
    up = L.dense(x, params["w_up"])
    if spec.gated:
        up = act(L.dense(x, params["w_gate"])) * up
    else:
        up = act(up)
    return L.dense(up, params["w_down"])
