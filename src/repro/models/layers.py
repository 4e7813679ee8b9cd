"""Primitive layers: norms, activations, RoPE, embeddings, linear init."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.float32, scale: Optional[float] = None):
    """Truncated-normal fan-in init (LeCun-ish), matching common LLM inits."""
    std = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    w = jax.random.truncated_normal(key, -2.0, 2.0, (in_dim, out_dim), jnp.float32) * std
    return w.astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32):
    w = jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02
    return w.astype(dtype)


def dense(x, w):
    """``x @ w`` for a weight matrix.  A weight held narrower than ``x``
    (bfloat16 from ``diffusion.mxu_params``) takes ``x`` rounded to its
    dtype and gives the product in ``x``'s dtype, accumulated in float32,
    so the program never widens the weight back."""
    if jnp.dtype(w.dtype).itemsize >= jnp.dtype(x.dtype).itemsize:
        return x @ w
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=x.dtype)


def zeros(shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


def ones(shape, dtype=jnp.float32):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# Norms (params: {"scale": (d,)} [+ {"bias"} for layernorm])
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.zeros((d,), dtype)}     # gemma-style (1+scale)


def rmsnorm(params, x, eps: float = 1e-6):
    # statistics in f32, scaling applied in the stream dtype: keeps the
    # (B, L, D) primal/cotangent chain in bf16 so TP backward all-reduces
    # stay bf16 (gemma2 §Perf-2 iter 3 — f32 cotangents doubled ICI bytes)
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * (1.0 + params["scale"]).astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params, x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    out = (x - mu.astype(x.dtype)) * inv
    return out * params["scale"].astype(x.dtype) + params["bias"].astype(x.dtype)


def norm_init(kind: str, d: int, dtype=jnp.float32):
    return rmsnorm_init(d, dtype) if kind == "rmsnorm" else layernorm_init(d, dtype)


def apply_norm(kind: str, params, x):
    return rmsnorm(params, x) if kind == "rmsnorm" else layernorm(params, x)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def activation(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
        "relu": jax.nn.relu,
    }[name]


def softcap(x, cap: float):
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., L, H, Dh) rotated pairwise-half style; positions: (..., L)."""
    dh = x.shape[-1]
    inv = rope_freqs(dh, theta)                              # (dh/2,)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., L, dh/2)
    cos = jnp.cos(ang)[..., :, None, :]                      # (..., L, 1, dh/2)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Positional / timestep embeddings
# ---------------------------------------------------------------------------

def sinusoidal_embedding(positions, dim: int, max_period: float = 10000.0):
    """positions: (...,) → (..., dim). Also used for diffusion timesteps."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    args = positions[..., None].astype(jnp.float32) * freqs
    emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, [(0, 0)] * (emb.ndim - 1) + [(0, 1)])
    return emb
